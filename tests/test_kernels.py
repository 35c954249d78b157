"""Kernels and the step's building blocks vs oracles: shape/dtype sweeps +
hypothesis.

The Pallas `hash_partition` and `segment_histogram` kernels against their
refs; the reduce's probe counts and first matches, its run bounds and merge
locate, and the map's router against numpy and the refs."""
import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st


from repro.kernels import join_probe as jp
from repro.kernels import map_pack as mp
from repro.kernels import ops, ref
from repro.kernels import scatter_pack as sp

SHAPES = [1, 7, 128, 1000, 1024, 4096, 5000]
DTYPES = [np.int32, np.uint32, np.int16]
SEEDS = [1, 2654435761, 0x9E3779B1]


@pytest.mark.parametrize("n", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("nbuckets", [1, 2, 16, 128])
def test_hash_partition_matches_ref(n, dtype, nbuckets):
    rng = np.random.default_rng(n * nbuckets)
    keys = rng.integers(0, np.iinfo(np.int16).max, size=n).astype(dtype)
    ids, hist = ops.hash_partition(jnp.asarray(keys), seed=SEEDS[0], nbuckets=nbuckets)
    ids_r, hist_r = ref.hash_partition_ref(jnp.asarray(keys), SEEDS[0], nbuckets)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_r))
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(hist_r))
    assert int(hist.sum()) == n
    assert ids.min() >= 0 and ids.max() < nbuckets


def test_hash_partition_matches_numpy_router():
    """Kernel hash == core.hypercube.multiply_shift (one hash family everywhere)."""
    from repro.core import multiply_shift
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 2**15, size=2048, dtype=np.int64)
    for seed in SEEDS:
        for nb in (1, 8, 64):
            ids, _ = ops.hash_partition(jnp.asarray(keys, jnp.int32), seed=seed, nbuckets=nb)
            np.testing.assert_array_equal(np.asarray(ids), multiply_shift(keys, seed, nb))


def _probe(probe, build):
    """`join_probe_host` on (n, w) key matrices, every row valid."""
    return jp.join_probe_host(jnp.asarray(probe), jnp.ones(len(probe), bool),
                              jnp.asarray(build), jnp.ones(len(build), bool))


def _numpy_matches(probe, build):
    """(n_probe, n_build) exact-key match matrix."""
    return (probe[:, None, :] == build[None, :, :]).all(axis=-1)


@pytest.mark.parametrize("np_, nb", [(1, 1), (17, 523), (512, 512), (1000, 100), (2048, 64)])
@pytest.mark.parametrize("w", [1, 2, 3])
def test_match_counts_matches_ref(np_, nb, w):
    """The probe's per-left-row counts are the exact-key match counts, on
    keys of 1 to 3 columns."""
    rng = np.random.default_rng(np_ + nb)
    probe = rng.integers(0, 50 if w == 1 else 6, size=(np_, w)).astype(np.int32)
    build = rng.integers(0, 50 if w == 1 else 6, size=(nb, w)).astype(np.int32)
    counts, _, _ = _probe(probe, build)
    np.testing.assert_array_equal(np.asarray(counts),
                                  _numpy_matches(probe, build).sum(axis=1))


@pytest.mark.parametrize("np_, nb", [(17, 523), (512, 512), (1000, 1500)])
def test_first_match_matches_ref(np_, nb):
    """A left row's first match, perm[lo], is its earliest-arriving equal
    right row (or none)."""
    rng = np.random.default_rng(np_)
    probe = rng.integers(0, 30, size=(np_, 1)).astype(np.int32)
    build = rng.integers(0, 30, size=(nb, 1)).astype(np.int32)
    counts, lo, perm = (np.asarray(x) for x in _probe(probe, build))
    got = np.where(counts > 0, perm[np.clip(lo, 0, nb - 1)], -1)
    eq = _numpy_matches(probe, build)
    np.testing.assert_array_equal(got, np.where(eq.any(axis=1),
                                                eq.argmax(axis=1), -1))


@pytest.mark.parametrize("shape", [(64,), (7, 9), (2, 3, 100), (5000,)])
@pytest.mark.parametrize("n_bins", [1, 8, 384])
def test_segment_histogram_matches_ref(shape, n_bins):
    rng = np.random.default_rng(42)
    vals = rng.integers(-2, n_bins + 3, size=shape).astype(np.int32)
    out = ops.segment_histogram(jnp.asarray(vals), n_bins)
    expect = ref.segment_histogram_ref(jnp.asarray(vals).reshape(-1), n_bins)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(1, 3000),
    logb=st.integers(0, 8),
    seed=st.integers(1, 2**31 - 1),
)
def test_hash_partition_property(n, logb, seed):
    seed |= 1   # odd seeds (universal family)
    nb = 1 << logb
    rng = np.random.default_rng(n)
    keys = rng.integers(0, 2**31 - 1, size=n, dtype=np.int64).astype(np.int32)
    ids, hist = ops.hash_partition(jnp.asarray(keys), seed=seed, nbuckets=nb)
    ids_r, hist_r = ref.hash_partition_ref(jnp.asarray(keys), seed, nb)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_r))
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(hist_r))
    # Equal keys always collide (consistency — the join correctness invariant).
    if n > 1:
        keys2 = np.full(n, keys[0], dtype=np.int32)
        ids2, _ = ops.hash_partition(jnp.asarray(keys2), seed=seed, nbuckets=nb)
        assert len(np.unique(np.asarray(ids2))) == 1


@settings(max_examples=20, deadline=None)
@given(
    np_=st.integers(1, 600), nb=st.integers(1, 600),
    dom=st.integers(1, 40), seed=st.integers(0, 2**31 - 1),
)
def test_match_counts_property(np_, nb, dom, seed):
    rng = np.random.default_rng(seed)
    probe = rng.integers(0, dom, size=(np_, 1)).astype(np.int32)
    build = rng.integers(0, dom, size=(nb, 1)).astype(np.int32)
    out = np.asarray(_probe(probe, build)[0])
    # Total matches == full join cardinality on the key column.
    expect_total = sum(int((build == p).sum()) for p in probe[:, 0])
    assert out.sum() == expect_total
    np.testing.assert_array_equal(out,
                                  _numpy_matches(probe, build).sum(axis=1))


def _sorted_keys(n, w, dom, seed):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, dom, size=(n, w)).astype(np.int32)
    return keys[np.lexsort(keys.T[::-1])]


def _packed(keys, dom):
    """Lexicographically sorted (n, w) keys as one sorted int32 word each."""
    return (keys * dom ** np.arange(keys.shape[1])[::-1]).sum(axis=1) \
        .astype(np.int32)


@pytest.mark.parametrize("n,w", [(1, 1), (7, 2), (2048, 2), (2049, 3), (5000, 1)])
def test_segment_scan_matches_ref(n, w):
    """`_run_bounds` gives each row of a sorted array the start of its run:
    np.unique's first index of the row's value."""
    dom = max(n // 3, 2)
    word = _packed(_sorted_keys(n, w, dom, n), dom)
    start, end = jp._run_bounds(jnp.asarray(word))
    _, first, inv = np.unique(word, return_index=True, return_inverse=True)
    np.testing.assert_array_equal(np.asarray(start), first[inv])
    assert (np.asarray(end) > np.arange(n)).all()


@pytest.mark.parametrize("n,w", [(1, 1), (17, 2), (2048, 1), (3000, 2)])
def test_run_lengths_matches_ref(n, w):
    """`_run_bounds`: end - start is each row's run length, and every left
    row's `sorted_locate` position is the start of its key's run."""
    dom = max(n // 4, 2)
    word = _packed(_sorted_keys(n, w, dom, n + 1), dom)
    start, end = (np.asarray(x) for x in jp._run_bounds(jnp.asarray(word)))
    _, first, inv, cnt = np.unique(word, return_index=True,
                                   return_inverse=True, return_counts=True)
    np.testing.assert_array_equal(end - start, cnt[inv])
    np.testing.assert_array_equal(end, first[inv] + cnt[inv])
    pos, nxt = jp.sorted_locate(jnp.asarray(word), jnp.asarray(word[::-1]))
    np.testing.assert_array_equal(np.asarray(pos), start[::-1])
    np.testing.assert_array_equal(np.asarray(nxt), word[::-1])


@pytest.mark.parametrize("case", ["all_equal", "all_distinct"])
def test_run_lengths_edge_runs(case):
    n = 300
    word = (np.zeros(n) if case == "all_equal" else np.arange(n)) \
        .astype(np.int32)
    start, end = (np.asarray(x) for x in jp._run_bounds(jnp.asarray(word)))
    if case == "all_equal":
        assert (start == 0).all() and (end == n).all()
    else:
        np.testing.assert_array_equal(start, np.arange(n))
        np.testing.assert_array_equal(end, np.arange(1, n + 1))


# The map's router, read through the tag column of `scatter_pack_host`'s
# buffer: on one device and with every copy valid, the stable sort keeps
# copy order, so row i's unwrapped logical cell lands in slot i.

def _routed_cells(rows, recipe, k):
    """(logical cell per row, (1, k) count matrix) of a one-route recipe."""
    spec = ((recipe, (0,), 0, (), ()),)
    rows = jnp.asarray(rows)
    buf, over = sp.scatter_pack_host(rows, jnp.zeros((k,), jnp.int32),
                                     routes=spec, k=k, n_dev=1,
                                     cap=rows.shape[0])
    assert int(over) == 0
    counts = mp.map_count_host(rows, routes=spec, k=k, n_src=1)
    return np.asarray(buf)[0, :, -1], np.asarray(counts)


@pytest.mark.parametrize("n,width", [(1, 2), (100, 3), (2048, 2), (5000, 5)])
def test_route_cells_matches_ref(n, width):
    rng = np.random.default_rng(n)
    rows = rng.integers(0, 2**15, size=(n, width)).astype(np.int32)
    recipe = tuple((c, SEEDS[c % len(SEEDS)] | 1, 1 << (c + 1), (c + 1) * 7)
                   for c in range(width))
    k = 1 << 10                                     # above every cell id
    cells, counts = _routed_cells(rows, recipe, k)
    expect = np.asarray(ref.route_cells_ref(jnp.asarray(rows), recipe))
    np.testing.assert_array_equal(cells, expect)
    np.testing.assert_array_equal(counts[0], np.bincount(expect % k,
                                                         minlength=k))


def test_route_cells_matches_hypercube_router():
    """The map's router == core.hypercube's per-attribute routing."""
    from repro.core import Hypercube, hash_seed
    rng = np.random.default_rng(0)
    rows = rng.integers(0, 1000, size=(500, 2)).astype(np.int32)
    cube = Hypercube(("A", "B"), (4, 8), offset=0, salt=3)
    strides = cube.strides()
    recipe = ((0, hash_seed("A", 3), 4, strides[0]),
              (1, hash_seed("B", 3), 8, strides[1]))
    cells, counts = _routed_cells(rows, recipe, 32)
    ridx, dest = cube.route(("A", "B"), rows)
    np.testing.assert_array_equal(cells, dest)     # fanout=1: dest per row
    np.testing.assert_array_equal(counts[0], np.bincount(dest, minlength=32))


def test_route_cells_share_one_skipped():
    rows = np.arange(64, dtype=np.int32).reshape(32, 2)
    cells, _ = _routed_cells(rows, ((0, 12345, 1, 99), (1, 999 | 1, 4, 3)),
                             16)
    expect = ref.route_cells_ref(jnp.asarray(rows), ((1, 999 | 1, 4, 3),))
    np.testing.assert_array_equal(cells, np.asarray(expect))
