"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""
import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st


from repro.kernels import ops, ref

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


@pytest.mark.parametrize("np_, nb", [(1, 1), (17, 523), (512, 512), (1000, 100), (2048, 64)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_match_counts_matches_ref(np_, nb, dtype):
    rng = np.random.default_rng(np_ + nb)
    probe = rng.integers(0, 50, size=np_).astype(dtype)
    build = rng.integers(0, 50, size=nb).astype(dtype)
    out = ops.match_counts(jnp.asarray(probe), jnp.asarray(build))
    expect = ref.match_counts_ref(jnp.asarray(probe, jnp.int32),
                                  jnp.asarray(build, jnp.int32))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("np_, nb", [(17, 523), (512, 512), (1000, 1500)])
def test_first_match_matches_ref(np_, nb):
    rng = np.random.default_rng(np_)
    probe = rng.integers(0, 30, size=np_).astype(np.int32)
    build = rng.integers(0, 30, size=nb).astype(np.int32)
    out = ops.first_match(jnp.asarray(probe), jnp.asarray(build))
    expect = ref.first_match_ref(jnp.asarray(probe), jnp.asarray(build))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


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
    probe = rng.integers(0, dom, size=np_).astype(np.int32)
    build = rng.integers(0, dom, size=nb).astype(np.int32)
    out = np.asarray(ops.match_counts(jnp.asarray(probe), jnp.asarray(build)))
    # Total matches == full join cardinality on the key column.
    expect_total = sum(int((build == p).sum()) for p in probe)
    assert out.sum() == expect_total
    np.testing.assert_array_equal(
        out, np.asarray(ref.match_counts_ref(jnp.asarray(probe), jnp.asarray(build))))


def _sorted_keys(n, w, dom, seed):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, dom, size=(n, w)).astype(np.int32)
    return keys[np.lexsort(keys.T[::-1])]


@pytest.mark.parametrize("n,w", [(1, 1), (7, 2), (2048, 2), (2049, 3), (5000, 1)])
def test_segment_scan_matches_ref(n, w):
    keys = _sorted_keys(n, w, max(n // 3, 2), n)
    seg, start = ops.segment_scan(jnp.asarray(keys))
    seg_r, start_r = ref.segment_scan_ref(jnp.asarray(keys))
    np.testing.assert_array_equal(np.asarray(seg), np.asarray(seg_r))
    np.testing.assert_array_equal(np.asarray(start), np.asarray(start_r))
    # Independent numpy oracle: dense rank == np.unique inverse on sorted rows.
    _, inv = np.unique(keys, axis=0, return_inverse=True)
    np.testing.assert_array_equal(np.asarray(seg), inv)


@pytest.mark.parametrize("n,w", [(1, 1), (17, 2), (2048, 1), (3000, 2)])
def test_run_lengths_matches_ref(n, w):
    keys = _sorted_keys(n, w, max(n // 4, 2), n + 1)
    out = ops.run_lengths(jnp.asarray(keys))
    expect = ref.run_lengths_ref(jnp.asarray(keys))
    for got, want in zip(out, expect):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    _, inv, cnt = np.unique(keys, axis=0, return_inverse=True,
                            return_counts=True)
    np.testing.assert_array_equal(np.asarray(out[2]), cnt[inv])


@pytest.mark.parametrize("case", ["all_equal", "all_distinct"])
def test_run_lengths_edge_runs(case):
    n = 300
    keys = (np.zeros((n, 2)) if case == "all_equal"
            else np.arange(2 * n).reshape(n, 2)).astype(np.int32)
    seg, start, length = ops.run_lengths(jnp.asarray(keys))
    if case == "all_equal":
        assert int(seg.max()) == 0 and int(start.max()) == 0
        assert (np.asarray(length) == n).all()
    else:
        np.testing.assert_array_equal(np.asarray(seg), np.arange(n))
        np.testing.assert_array_equal(np.asarray(start), np.arange(n))
        assert (np.asarray(length) == 1).all()


@pytest.mark.parametrize("n,width", [(1, 2), (100, 3), (2048, 2), (5000, 5)])
def test_route_cells_matches_ref(n, width):
    rng = np.random.default_rng(n)
    rows = rng.integers(0, 2**15, size=(n, width)).astype(np.int32)
    recipe = tuple((c, SEEDS[c % len(SEEDS)] | 1, 1 << (c + 1), (c + 1) * 7)
                   for c in range(width))
    out = ops.route_cells(jnp.asarray(rows), recipe)
    expect = ref.route_cells_ref(jnp.asarray(rows), recipe)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_route_cells_matches_hypercube_router():
    """Fused kernel == core.hypercube per-attribute routing composition."""
    from repro.core import Hypercube, hash_seed
    rng = np.random.default_rng(0)
    rows = rng.integers(0, 1000, size=(500, 2)).astype(np.int32)
    cube = Hypercube(("A", "B"), (4, 8), offset=0, salt=3)
    strides = cube.strides()
    recipe = ((0, hash_seed("A", 3), 4, strides[0]),
              (1, hash_seed("B", 3), 8, strides[1]))
    out = np.asarray(ops.route_cells(jnp.asarray(rows), recipe))
    ridx, dest = cube.route(("A", "B"), rows)
    np.testing.assert_array_equal(out, dest)     # fanout=1: dest per row


def test_route_cells_share_one_skipped():
    rows = jnp.asarray(np.arange(64, dtype=np.int32).reshape(32, 2))
    out = ops.route_cells(rows, ((0, 12345, 1, 99), (1, 999 | 1, 4, 3)))
    expect = ref.route_cells_ref(rows, ((1, 999 | 1, 4, 3),))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))
