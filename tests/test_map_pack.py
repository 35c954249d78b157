"""map_pack megakernel vs the staged route->fold->pack oracle — every path.

The fused map phase has three implementations that must be bit-identical to
the staged `_route_relation` -> `_fold_dests` -> `_pack_buckets` composition
(kept in core.executor solely as this oracle): the Pallas kernel (interpret
mode here, compiled on TPU), its vectorized-XLA host twin (the non-TPU hot
path), and the dead-simple ref in kernels/ref.py.  Coverage: k in {1, 8, 256}
with n_devices < k (the placement fold engaged), multi-residual recipes with
replication fanout > 1, eq / not-in type constraints, m = 0, all-invalid
rows, capacity-overflow parity, and the scatter-free COUNTING mode against
the staged count-matrix formula `_count_pass` used to compute.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from hypothesis import given, settings, strategies as st
from repro.core.executor import (_Route, _build_routes, _count_matrix,
                                 _fold_dests, _pack_buckets, _route_relation,
                                 _route_specs)
from repro.core.placement import lpt_placement, modulo_placement
from repro.kernels import map_pack as mp
from repro.kernels import ops as kops
from repro.kernels.ref import map_count_ref, map_pack_ref

SEED_A, SEED_B = 0x9E3779B1, 0x85EBCA77          # odd multiply-shift seeds


def _routes_for(k: int, w: int = 3) -> list[_Route]:
    """Synthetic multi-residual recipe: hashed attrs, fanout > 1 via
    replication offsets, an eq- and a not-in-constrained route."""
    if k == 1:
        return [_Route("T", ((0, SEED_A, 1, 1),), (0,), 0, k, (), ())]
    half, quarter = max(k // 2, 1), max(k // 4, 1)
    return [
        # residual 0: hash col0 over half the cells, replicate twice.
        _Route("T", ((0, SEED_A, half, 1),), (0, half), 0, k, (),
               ((1, (7, 13)),)),
        # residual 1: col1 frozen to a HH value, hash col0 x col2 grid.
        _Route("T", ((0, SEED_B, quarter, 1), (2, SEED_A, 2, quarter)),
               (0,), quarter, k, ((1, 7),), ()),
    ]


def _staged(rows, routes, ptable, n_dev, cap):
    """The oracle: today's staged composition on the pure-jnp ref path."""
    dest, tagged = _route_relation(rows, routes, False)
    phys = _fold_dests(dest, jnp.asarray(ptable), False)
    return _pack_buckets(phys, tagged, n_dev, cap, False)


def _all_paths(rows, spec, ptable, k, n_dev, cap):
    pt = jnp.asarray(ptable)
    return {
        "kernel": mp.map_pack(rows, pt, routes=spec, k=k, n_dev=n_dev,
                              cap=cap, interpret=True),
        "host": mp.map_pack_host(rows, pt, routes=spec, k=k, n_dev=n_dev,
                                 cap=cap),
        "ref": map_pack_ref(rows, pt, spec, k, n_dev, cap),
        "ops": kops.map_pack(rows, spec, pt, k, n_dev, cap),
    }


def _assert_matches_staged(rows, routes, ptable, k, n_dev, cap):
    rows = jnp.asarray(rows, jnp.int32)
    spec = _route_specs(routes)
    buf_o, over_o = _staged(rows, routes, ptable, n_dev, cap)
    buf_o, over_o = np.asarray(buf_o), int(over_o)
    for name, (buf, over) in _all_paths(rows, spec, ptable, k, n_dev,
                                        cap).items():
        np.testing.assert_array_equal(np.asarray(buf), buf_o,
                                      err_msg=f"path={name} k={k}")
        assert int(over) == over_o, f"path={name} k={k}"
    return buf_o, over_o


def _staged_counts(rows, routes, k, n_src):
    """The `_count_pass` oracle branch: staged routing + `_count_matrix`."""
    dest, _ = _route_relation(rows, routes, False)
    return np.asarray(_count_matrix(dest, rows.shape[0], k, n_src))


def _rand_rows(rng, m, w=3, domain=50, invalid_frac=0.1):
    rows = rng.integers(0, domain, size=(m, w)).astype(np.int32)
    rows[rng.random(m) < invalid_frac] = -1                 # padding rows
    return rows


@pytest.mark.parametrize("k,n_dev", [(1, 1), (8, 4), (256, 8)])
@pytest.mark.parametrize("m", [0, 1, 63, 257])              # ragged, off-block
def test_pack_matches_staged_oracle(k, n_dev, m):
    rng = np.random.default_rng(m * 1000 + k)
    routes = _routes_for(k)
    ptable = lpt_placement(rng.uniform(0, 100, k), n_dev).table
    rows = _rand_rows(rng, m)
    fanout = mp.route_fanout(_route_specs(routes))
    assert k == 1 or fanout > 1                             # replication live
    cap = max(4, (2 * m * fanout) // max(n_dev, 1))
    _assert_matches_staged(rows, routes, ptable, k, n_dev, cap)


@pytest.mark.parametrize("k,n_dev", [(8, 4), (256, 8)])
def test_pack_all_invalid(k, n_dev):
    routes = _routes_for(k)
    buf, over = _assert_matches_staged(
        np.full((70, 3), -1, np.int32), routes,
        modulo_placement(k, n_dev).table, k, n_dev, 4)
    assert over == 0
    assert (buf == -1).all()


@pytest.mark.parametrize("k,n_dev", [(8, 4), (256, 8)])
def test_pack_overflow_parity(k, n_dev):
    """Tiny caps force overflow; counts must match the staged path exactly."""
    rng = np.random.default_rng(k)
    routes = _routes_for(k)
    rows = _rand_rows(rng, 150, invalid_frac=0.0)
    _, over = _assert_matches_staged(
        rows, routes, modulo_placement(k, n_dev).table, k, n_dev, 2)
    assert over > 0


def test_pack_adversarial_all_cells_one_device():
    """Every cell folded to device 0: ranks stream through one bucket."""
    k, n_dev = 32, 8
    rng = np.random.default_rng(3)
    routes = _routes_for(k)
    table = np.zeros(k, np.int32)
    rows = _rand_rows(rng, 120)
    buf, _ = _assert_matches_staged(rows, routes, table, k, n_dev, 1024)
    assert (buf[1:] == -1).all()                            # only device 0 fed


def test_pack_real_plan_routes():
    """Recipes from a real SkewShares plan (multi-residual, HH constraints)."""
    from repro.core import plan_skew_join, two_way
    from repro.data import skewed_join_dataset
    k, n_dev = 64, 8
    q = two_way()
    data = skewed_join_dataset(q, 400, 40, skew={"B": 1.6}, seed=41)
    plan = plan_skew_join(q, data, k)
    assert len(plan.residuals) >= 2
    routes = _build_routes(plan)
    ptable = lpt_placement(np.asarray(plan.cell_loads(data), float),
                           n_dev).table
    for rel in ("R", "S"):
        rows = np.concatenate(
            [data[rel], np.full((9, 2), -1)]).astype(np.int32)
        _assert_matches_staged(rows, routes[rel], ptable, k, n_dev, 2048)


@pytest.mark.parametrize("k,n_src", [(1, 1), (8, 4), (256, 8)])
@pytest.mark.parametrize("m", [0, 64, 200])
def test_count_matches_staged_formula(k, n_src, m):
    rng = np.random.default_rng(m + k)
    routes = _routes_for(k)
    rows = jnp.asarray(_rand_rows(rng, m))
    spec = _route_specs(routes)
    expect = _staged_counts(rows, routes, k, n_src)
    for name, got in {
        "kernel": mp.map_count(rows, routes=spec, k=k, n_src=n_src,
                               interpret=True),
        "host": mp.map_count_host(rows, routes=spec, k=k, n_src=n_src),
        "ref": map_count_ref(rows, spec, k, n_src),
        "ops": kops.map_count(rows, spec, k, n_src),
    }.items():
        np.testing.assert_array_equal(np.asarray(got), expect,
                                      err_msg=f"path={name} k={k} m={m}")


def test_count_histogram_totals_valid_copies_only():
    k, n_src = 8, 4
    routes = _routes_for(k)
    rows = jnp.asarray(_rand_rows(np.random.default_rng(6), 96))
    spec = _route_specs(routes)
    dest, _ = _route_relation(rows, routes, False)
    counts = np.asarray(mp.map_count_host(rows, routes=spec, k=k,
                                          n_src=n_src))
    assert counts.sum() == int((np.asarray(dest) >= 0).sum())


def test_kernel_rank_carry_across_tiles():
    """Tile boundaries must not break the carried histogram: force several
    grid steps by shrinking block_copies below m·fanout."""
    k, n_dev = 8, 4
    rng = np.random.default_rng(8)
    routes = _routes_for(k)
    rows = jnp.asarray(_rand_rows(rng, 300))
    spec = _route_specs(routes)
    ptable = modulo_placement(k, n_dev).table
    buf_o, over_o = _staged(rows, routes, ptable, n_dev, 512)
    for bc in (8, 64, 1024):
        buf, over = mp.map_pack(rows, jnp.asarray(ptable), routes=spec, k=k,
                                n_dev=n_dev, cap=512, block_copies=bc,
                                interpret=True)
        np.testing.assert_array_equal(np.asarray(buf), np.asarray(buf_o),
                                      err_msg=f"block_copies={bc}")
        assert int(over) == int(over_o)


@settings(max_examples=15, deadline=None)
@given(
    st.integers(min_value=0, max_value=250),                # m
    st.sampled_from([(1, 1), (8, 4), (256, 8)]),            # (k, n_dev)
    st.integers(min_value=1, max_value=10),                 # cap (overflows)
    st.integers(min_value=0, max_value=2**31 - 1),          # seed
)
def test_pack_property_bit_identical_to_staged(m, kn, cap, seed):
    k, n_dev = kn
    rng = np.random.default_rng(seed)
    routes = _routes_for(k)
    ptable = lpt_placement(rng.uniform(0, 100, k), n_dev).table
    _assert_matches_staged(_rand_rows(rng, m), routes, ptable, k, n_dev, cap)


# -- executor integration (needs the 8-device mesh) --------------------------

needs_mesh = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


@needs_mesh
def test_executor_fused_vs_staged_bit_identical():
    """fuse_map=True and =False must agree on every output AND capacity."""
    from repro.core import canonical, plan_skew_join, reference_join, two_way
    from repro.core.executor import ExecutorConfig, ShardedJoinExecutor
    from repro.data import skewed_join_dataset
    from jax.sharding import AxisType
    mesh = jax.make_mesh((8,), ("cells",), axis_types=(AxisType.Auto,))
    q = two_way()
    data = skewed_join_dataset(q, 600, 50, skew={"B": 1.6}, seed=42)
    plan = plan_skew_join(q, data, 32)
    out = {}
    for fuse in (True, False):
        ex = ShardedJoinExecutor(plan, mesh, config=ExecutorConfig(
            out_capacity=1 << 17, fuse_map=fuse))
        s = ex.session().prepare(data)
        assert s.count_passes == 1          # prepare routes data exactly once
        out[fuse] = (s.caps, s.run_batch())
    caps_f, res_f = out[True]
    caps_s, res_s = out[False]
    assert caps_f == caps_s
    for key in ("rows", "valid", "shuffle_overflow", "join_overflow",
                "recv_counts"):
        np.testing.assert_array_equal(res_f[key], res_s[key], err_msg=key)
    got = res_f["rows"][res_f["valid"]]
    np.testing.assert_array_equal(canonical(got), reference_join(q, data))


@needs_mesh
def test_prepare_skips_count_pass_when_given_everything():
    """Explicit caps + placement leave nothing to derive: zero routing."""
    from repro.core import plan_skew_join, two_way
    from repro.core.executor import ExecutorConfig, ShardedJoinExecutor
    from repro.data import skewed_join_dataset
    from jax.sharding import AxisType
    q = two_way()
    data = skewed_join_dataset(q, 200, 30, seed=43)
    plan = plan_skew_join(q, data, 8)
    mesh = jax.make_mesh((8,), ("cells",), axis_types=(AxisType.Auto,))
    ex = ShardedJoinExecutor(plan, mesh,
                             config=ExecutorConfig(out_capacity=1 << 16))
    s = ex.session().prepare(data, caps={r.name: 512 for r in q.relations},
                             placement=modulo_placement(8, 8))
    assert s.count_passes == 0
