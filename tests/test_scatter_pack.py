"""scatter_pack / expand_rows vs their gather-formulation oracles.

The scatter-assemble megakernel must be BIT-identical to `map_pack` (whose
`_assemble_tagged` inverse-permutation gather it retires) on every path: the
Pallas kernel (interpret mode here, compiled on TPU), the vectorized-XLA host
twin, the kernels/ref.py oracle, and the `kernels.ops` dispatcher.  Coverage
mirrors test_map_pack.py: k in {1, 8, 256} with the placement fold engaged,
multi-residual recipes with replication fanout > 1, m = 0, all-invalid rows,
capacity-overflow parity, and tile-boundary rank carry.

`expand_rows` must be POSITIONALLY identical to the searchsorted + gather
expansion it replaces (`expand_rows_host` keeps that formulation verbatim —
it doubles as the oracle) on real probe outputs and on degenerate shapes:
ragged caps that end mid-group, overflow truncation, zero-size sides, and
zero total matches.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from hypothesis import given, settings, strategies as st
from repro.core.executor import _Route, _probe_sort, _route_specs
from repro.core.placement import lpt_placement, modulo_placement
from repro.kernels import map_pack as mp
from repro.kernels import ops as kops
from repro.kernels import scatter_pack as sp
from repro.kernels.ref import expand_rows_ref, scatter_pack_ref

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


@pytest.mark.parametrize("k,n_dev", [(8, 4), (256, 8)])
def test_scatter_pack_overflow_parity(k, n_dev):
    """Tiny caps force overflow; trash-row routing must not disturb counts."""
    rng = np.random.default_rng(k)
    routes = _routes_for(k)
    rows = _rand_rows(rng, 150, invalid_frac=0.0)
    _, over = _assert_matches_map_pack(
        rows, routes, modulo_placement(k, n_dev).table, k, n_dev, 2)
    assert over > 0


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
