"""scatter_pack / expand_rows vs their gather-formulation oracles.

The scatter-assemble megakernel must be BIT-identical to `map_pack` (whose
`_assemble_tagged` inverse-permutation gather it retires) on every path: the
Pallas kernel (interpret mode here, compiled on TPU), the vectorized-XLA host
twin, the kernels/ref.py oracle, and the `kernels.ops` dispatcher.  Coverage
mirrors test_map_pack.py: k in {1, 8, 256} with the placement fold engaged,
multi-residual recipes with replication fanout > 1, m = 0, all-invalid rows,
capacity-overflow parity, and tile-boundary rank carry.

`expand_rows` must be POSITIONALLY identical to the searchsorted + gather
expansion it replaces (`expand_rows_ref` keeps that formulation; the host
twin finds each slot's left row by a merge sort instead, and is checked
against `np.searchsorted` and the ref) on real probe outputs and on
degenerate shapes: ragged caps that end mid-group, overflow truncation,
zero-size sides, zero total matches, and zero-count rows at either end.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro.core.executor import _Route, _probe_sort, _route_specs
from repro.core.placement import lpt_placement, modulo_placement
from repro.kernels import join_probe as jp
from repro.kernels import map_pack as mp
from repro.kernels import ops as kops
from repro.kernels import scatter_pack as sp
from repro.kernels.ref import expand_rows_ref, scatter_pack_ref
from repro.launch.hlo_analysis import count_ops

SEED_A, SEED_B = 0x9E3779B1, 0x85EBCA77          # odd multiply-shift seeds


def _routes_for(k: int) -> list[_Route]:
    """Synthetic multi-residual recipe (same shape as test_map_pack's):
    hashed attrs, fanout > 1 via replication, eq / not-in constraints."""
    if k == 1:
        return [_Route("T", ((0, SEED_A, 1, 1),), (0,), 0, k, (), ())]
    half, quarter = max(k // 2, 1), max(k // 4, 1)
    return [
        _Route("T", ((0, SEED_A, half, 1),), (0, half), 0, k, (),
               ((1, (7, 13)),)),
        _Route("T", ((0, SEED_B, quarter, 1), (2, SEED_A, 2, quarter)),
               (0,), quarter, k, ((1, 7),), ()),
    ]


def _rand_rows(rng, m, w=3, domain=50, invalid_frac=0.1):
    rows = rng.integers(0, domain, size=(m, w)).astype(np.int32)
    rows[rng.random(m) < invalid_frac] = -1
    return rows


def _assert_matches_map_pack(rows, routes, ptable, k, n_dev, cap):
    """Every scatter_pack path vs the map_pack gather oracle, bit for bit."""
    rows = jnp.asarray(rows, jnp.int32)
    spec = _route_specs(routes)
    pt = jnp.asarray(ptable)
    buf_o, over_o = mp.map_pack_host(rows, pt, routes=spec, k=k, n_dev=n_dev,
                                     cap=cap)
    buf_o, over_o = np.asarray(buf_o), int(over_o)
    paths = {
        "kernel": sp.scatter_pack(rows, pt, routes=spec, k=k, n_dev=n_dev,
                                  cap=cap, interpret=True),
        "host": sp.scatter_pack_host(rows, pt, routes=spec, k=k, n_dev=n_dev,
                                     cap=cap),
        "ref": scatter_pack_ref(rows, pt, spec, k, n_dev, cap),
        "ops": kops.scatter_pack(rows, spec, pt, k, n_dev, cap),
    }
    for name, (buf, over) in paths.items():
        np.testing.assert_array_equal(np.asarray(buf), buf_o,
                                      err_msg=f"path={name} k={k}")
        assert int(over) == over_o, f"path={name} k={k}"
    return buf_o, over_o


@pytest.mark.parametrize("k,n_dev", [(1, 1), (8, 4), (256, 8)])
@pytest.mark.parametrize("m", [0, 1, 63, 257])              # ragged, off-block
def test_scatter_pack_matches_map_pack(k, n_dev, m):
    rng = np.random.default_rng(m * 1000 + k)
    routes = _routes_for(k)
    ptable = lpt_placement(rng.uniform(0, 100, k), n_dev).table
    rows = _rand_rows(rng, m)
    fanout = mp.route_fanout(_route_specs(routes))
    assert k == 1 or fanout > 1                             # replication live
    cap = max(4, (2 * m * fanout) // max(n_dev, 1))
    _assert_matches_map_pack(rows, routes, ptable, k, n_dev, cap)


@pytest.mark.parametrize("k,n_dev", [(8, 4), (256, 8)])
def test_scatter_pack_all_invalid(k, n_dev):
    routes = _routes_for(k)
    buf, over = _assert_matches_map_pack(
        np.full((70, 3), -1, np.int32), routes,
        modulo_placement(k, n_dev).table, k, n_dev, 4)
    assert over == 0
    assert (buf == -1).all()


def _cap_for(case: str, m: int, hist: np.ndarray) -> int:
    """The capacity an overflow case runs at: 2, or relative to the copies
    m, or (`fit_after`) the largest bucket after the first, so bucket 0
    overflows and the next buckets fit, one exactly."""
    if case == "fit_after":
        return int(hist[1:].max())
    return {"2": 2, "m": m, "m-1": m - 1, "2m": 2 * m}[case]


@pytest.mark.parametrize("k,n_dev,ptable,case", [
    pytest.param(8, 4, None, "2", id="8-4"),
    pytest.param(256, 8, None, "2", id="256-8"),
    pytest.param(1, 1, None, "m", id="1-1-all-valid-cap-m"),
    pytest.param(1, 1, None, "m-1", id="1-1-all-valid-cap-m-1"),
    pytest.param(1, 1, None, "2m", id="1-1-all-valid-cap-2m"),
    pytest.param(8, 4, (0, 0, 0, 0, 1, 1, 2, 2), "fit_after",
                 id="8-4-overflow-then-fit"),
])
def test_scatter_pack_overflow_parity(k, n_dev, ptable, case):
    """Caps at and past the copies' count: trash-row routing and the sort's
    per-device slices must not disturb the buffer or the counts, also where
    a device's slice starts past an overflowed bucket."""
    rng = np.random.default_rng(k)
    routes = _routes_for(k)
    rows = _rand_rows(rng, 150, invalid_frac=0.0)
    table = (modulo_placement(k, n_dev).table if ptable is None
             else np.asarray(ptable, np.int32))
    m = 150 * mp.route_fanout(_route_specs(routes))
    # Copies per device, from the oracle at a capacity nothing overflows.
    full, _ = scatter_pack_ref(jnp.asarray(rows), jnp.asarray(table),
                               _route_specs(routes), k, n_dev, m)
    hist = (np.asarray(full)[..., -1] >= 0).sum(axis=1)
    cap = _cap_for(case, m, hist)
    buf, over = _assert_matches_map_pack(rows, routes, table, k, n_dev, cap)
    assert over == np.maximum(hist - cap, 0).sum()
    np.testing.assert_array_equal((buf[..., -1] >= 0).sum(axis=1),
                                  np.minimum(hist, cap))
    if case == "2":
        assert over > 0
    if k == 1:
        assert hist.sum() == m                      # every copy valid
    if case == "fit_after":
        assert hist[0] > cap and (hist[1:] <= cap).all() and hist[1] > 0


@pytest.mark.parametrize("n_dev", [1, 4])
def test_scatter_pack_host_lowers_without_scatter(n_dev):
    """The host twin assembles by one sort: a row scatter (or the rank
    pass's scatter-add) coming back fails here.  Teeth: the counter sees
    the scatter `map_pack_host` keeps for its inverse permutation."""
    k = 8
    spec = _route_specs(_routes_for(k))
    rows = jnp.asarray(_rand_rows(np.random.default_rng(5), 64))
    pt = jnp.asarray(modulo_placement(k, n_dev).table)

    def hlo(fn):
        return jax.jit(functools.partial(
            fn, routes=spec, k=k, n_dev=n_dev, cap=32)).lower(
                rows, pt).compile().as_text()

    text = hlo(sp.scatter_pack_host)
    assert count_ops(text, "scatter") == 0
    assert count_ops(text, "sort") == 1
    assert count_ops(hlo(mp.map_pack_host), "scatter") >= 1


def test_scatter_pack_tile_boundary_carry():
    """Shrinking block_copies forces multi-tile grids: the carried histogram
    and the in-kernel stores must agree across tile boundaries."""
    k, n_dev = 8, 4
    rng = np.random.default_rng(8)
    routes = _routes_for(k)
    rows = jnp.asarray(_rand_rows(rng, 300))
    spec = _route_specs(routes)
    pt = jnp.asarray(modulo_placement(k, n_dev).table)
    buf_o, over_o = mp.map_pack_host(rows, pt, routes=spec, k=k, n_dev=n_dev,
                                     cap=512)
    for bc in (8, 64, 1024):
        buf, over = sp.scatter_pack(rows, pt, routes=spec, k=k, n_dev=n_dev,
                                    cap=512, block_copies=bc, interpret=True)
        np.testing.assert_array_equal(np.asarray(buf), np.asarray(buf_o),
                                      err_msg=f"block_copies={bc}")
        assert int(over) == int(over_o)


@settings(max_examples=10, deadline=None)
@given(
    st.integers(min_value=0, max_value=250),                # m
    st.sampled_from([(1, 1), (8, 4), (256, 8)]),            # (k, n_dev)
    st.integers(min_value=1, max_value=10),                 # cap (overflows)
    st.integers(min_value=0, max_value=2**31 - 1),          # seed
)
def test_scatter_pack_property_bit_identical(m, kn, cap, seed):
    k, n_dev = kn
    rng = np.random.default_rng(seed)
    routes = _routes_for(k)
    ptable = lpt_placement(rng.uniform(0, 100, k), n_dev).table
    _assert_matches_map_pack(_rand_rows(rng, m), routes, ptable, k, n_dev,
                             cap)


# -- expand_rows --------------------------------------------------------------

def _probe_inputs(rng, n_l, n_r, domain=6, wl=3, wr=4):
    """Random fragments + a REAL probe output (counts, lo, perm) from the
    sort-merge formulation — the distribution expand_rows actually sees."""
    left = rng.integers(0, domain, (n_l, wl)).astype(np.int32)
    right = rng.integers(0, domain, (n_r, wr)).astype(np.int32)
    lk = jnp.asarray(left[:, :1])
    rk = jnp.asarray(right[:, :1])
    l_valid = jnp.asarray(rng.random(n_l) > 0.2)
    r_valid = jnp.asarray(rng.random(n_r) > 0.2)
    counts, lo, perm = _probe_sort(lk, l_valid, rk, r_valid, False)
    return (jnp.asarray(left), jnp.asarray(right), counts, lo, perm)


def _numpy_expand_valid(left, right, counts, lo, perm, cap):
    """Valid-region oracle: slot t of group i holds left[i] ++ right[perm[
    lo[i] + t_within]] in (left row, right arrival) order, truncated at cap."""
    left, right = np.asarray(left), np.asarray(right)
    counts, lo, perm = map(np.asarray, (counts, lo, perm))
    out, t = [], 0
    for i in range(len(counts)):
        for j in range(int(counts[i])):
            if t >= cap:
                return np.asarray(out, np.int32).reshape(-1, left.shape[1]
                                                         + right.shape[1])
            out.append(np.concatenate([left[i], right[perm[lo[i] + j]]]))
            t += 1
    return np.asarray(out, np.int32).reshape(-1, left.shape[1]
                                             + right.shape[1])


def _assert_expand_paths_agree(left, right, counts, lo, perm, cap):
    """Kernel / host / ref / ops, positionally identical everywhere; the
    valid region checked against the explicit numpy loop."""
    out_o, val_o = sp.expand_rows_host(left, right, counts, lo, perm, cap=cap)
    out_o, val_o = np.asarray(out_o), np.asarray(val_o)
    paths = {
        "kernel": sp.expand_rows(left, right, counts, lo, perm, cap=cap,
                                 interpret=True),
        "ref": expand_rows_ref(left, right, counts, lo, perm, cap),
        "ops": kops.expand_rows(left, right, counts, lo, perm, cap),
    }
    for name, (out, val) in paths.items():
        np.testing.assert_array_equal(np.asarray(out), out_o,
                                      err_msg=f"path={name} cap={cap}")
        np.testing.assert_array_equal(np.asarray(val), val_o,
                                      err_msg=f"path={name} cap={cap}")
    want = _numpy_expand_valid(left, right, counts, lo, perm, cap)
    np.testing.assert_array_equal(out_o[val_o], want)
    assert val_o.sum() == min(int(np.asarray(counts).sum()), cap)
    return out_o, val_o


@pytest.mark.parametrize("n_l,n_r", [(1, 1), (24, 16), (80, 120)])
@pytest.mark.parametrize("seed", [0, 1])
def test_expand_rows_matches_gather_oracle(n_l, n_r, seed):
    rng = np.random.default_rng(seed * 100 + n_l)
    left, right, counts, lo, perm = _probe_inputs(rng, n_l, n_r)
    total = int(np.asarray(counts).sum())
    # Slack, exact, ragged mid-group, and overflow caps.
    for cap in sorted({total + 64, max(total, 1), max(total // 2 + 1, 1), 7}):
        _assert_expand_paths_agree(left, right, counts, lo, perm, cap)


def test_expand_rows_fanout_groups():
    """Heavy duplication: every left row matches many right rows, and the
    within-group order must be right-ARRIVAL order (perm grouping)."""
    rng = np.random.default_rng(7)
    left = jnp.asarray(np.stack([np.full(6, 3), np.arange(6)], 1), jnp.int32)
    right = jnp.asarray(np.stack([np.full(30, 3), np.arange(30)], 1),
                        jnp.int32)
    counts, lo, perm = _probe_sort(left[:, :1], jnp.ones(6, bool),
                                   right[:, :1], jnp.ones(30, bool), False)
    assert int(np.asarray(counts).max()) == 30          # full fanout
    out, val = _assert_expand_paths_agree(left, right, counts, lo, perm, 256)
    got = out[val]
    # Group of left row 0: right rows in arrival order 0..29.
    np.testing.assert_array_equal(got[:30, 3], np.arange(30))


def test_expand_rows_zero_matches_and_zero_sizes():
    rng = np.random.default_rng(9)
    # Disjoint keys: total == 0, all-INVALID output.
    left = jnp.asarray(rng.integers(0, 5, (10, 2)), jnp.int32)
    right = jnp.asarray(rng.integers(50, 55, (8, 2)), jnp.int32)
    counts, lo, perm = _probe_sort(left[:, :1], jnp.ones(10, bool),
                                   right[:, :1], jnp.ones(8, bool), False)
    out, val = _assert_expand_paths_agree(left, right, counts, lo, perm, 16)
    assert val.sum() == 0
    # Zero-size sides: the static guard path, all paths agree.
    z = jnp.zeros((0, 2), jnp.int32)
    zc = jnp.zeros((0,), jnp.int32)
    for l, r, c in ((z, right, zc),
                    (left, z, jnp.zeros((10,), jnp.int32))):
        pz = jnp.arange(r.shape[0], dtype=jnp.int32)
        lz = jnp.zeros((l.shape[0],), jnp.int32)
        _assert_expand_paths_agree(l, r, c, lz, pz, 8)


_COUNT_CASES = {
    "leading_zeros": [0, 0, 2, 1, 3],
    "trailing_zeros": [2, 1, 3, 0, 0, 0],
    "interior_zero_runs": [1, 0, 0, 3, 0, 2, 0, 0, 1],
    "all_zero": [0, 0, 0, 0],
    "one_left_row": [5],
    "one_left_row_zero": [0],
}


@pytest.mark.parametrize("cap_vs_total", [-3, 0, 7])
@pytest.mark.parametrize("case", sorted(_COUNT_CASES))
def test_expand_rows_host_locates_slots_like_searchsorted(case,
                                                          cap_vs_total):
    """`expand_rows_host` maps slot t to its left row by a merge sort:
    li must be `np.searchsorted(off, t, "right") - 1` (clipped) for every
    slot, including the slots past `counts.sum()`, and the expansion
    positionally identical to the searchsorted oracle in kernels/ref.py."""
    counts = np.asarray(_COUNT_CASES[case], np.int32)
    total = int(counts.sum())
    cap = max(total + cap_vs_total, 1)
    n_l = len(counts)
    off = np.cumsum(counts) - counts
    t = np.arange(cap, dtype=np.int32)
    pos, _ = jp.sorted_locate(jnp.asarray(off, jnp.int32), jnp.asarray(t),
                              side="right")
    np.testing.assert_array_equal(np.asarray(pos),
                                  np.searchsorted(off, t, side="right"))
    n_r = max(total, 1)
    rng = np.random.default_rng(n_l * 10 + cap)
    left = jnp.asarray(np.stack([np.arange(n_l), np.arange(n_l) * 7], 1),
                       jnp.int32)
    right = jnp.asarray(rng.integers(0, 99, (n_r, 2)), jnp.int32)
    perm = jnp.asarray(rng.permutation(n_r), jnp.int32)
    out, val = sp.expand_rows_host(left, right, jnp.asarray(counts),
                                   jnp.asarray(off, jnp.int32), perm,
                                   cap=cap)
    li = np.clip(np.searchsorted(off, t, side="right") - 1, 0, n_l - 1)
    np.testing.assert_array_equal(np.asarray(out)[:, 0], li)
    out_r, val_r = expand_rows_ref(left, right, jnp.asarray(counts),
                                   jnp.asarray(off, jnp.int32), perm, cap)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_r))
    np.testing.assert_array_equal(np.asarray(val), np.asarray(val_r))


def test_expand_rows_tile_boundaries():
    """Multi-tile grids (explicit tiny block, so groups straddle tile edges)
    must stay positionally identical to the single-pass host twin; and the
    VMEM auto-shrink really shrinks once the one-hots outgrow the budget."""
    rng = np.random.default_rng(11)
    left, right, counts, lo, perm = _probe_inputs(rng, 60, 80, domain=10)
    total = int(np.asarray(counts).sum())
    cap = max(total + 32, 64)
    out_o, val_o = sp.expand_rows_host(left, right, counts, lo, perm,
                                       cap=cap)
    for block in (8, 16, 64):
        out, val = sp.expand_rows(left, right, counts, lo, perm, cap=cap,
                                  block=block, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out_o),
                                      err_msg=f"block={block}")
        np.testing.assert_array_equal(np.asarray(val), np.asarray(val_o),
                                      err_msg=f"block={block}")
    assert sp._expand_block(256, 3000, 4000) < 256      # the shrink engages
