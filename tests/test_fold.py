"""Logical-cell folding: k >> n_devices plans on the 8-device CPU mesh.

The tentpole's correctness contract: ANY power-of-two k >= n_devices executes
bit-exactly against the numpy reference, because every routed copy carries its
logical cell id and the local join matches only within equal ids — placement
(LPT, modulo, or adversarial) moves load, never results.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (CellPlacement, canonical, lpt_placement,
                        modulo_placement, plan_skew_join, reference_join,
                        running_example, two_way)
from repro.core.executor import (ExecutorConfig, ShardedJoinExecutor,
                                 quantize_capacity)
from repro.data import skewed_join_dataset

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices")

N_DEV = 8


def _mesh():
    from jax.sharding import AxisType
    return jax.make_mesh((N_DEV,), ("cells",), axis_types=(AxisType.Auto,))


def _check_exact(q, data, ex, placement=None):
    s = ex.session().prepare(data, placement=placement)
    res = s.run_batch()
    assert int(res["shuffle_overflow"].sum()) == 0
    assert int(res["join_overflow"].sum()) == 0
    got = res["rows"][res["valid"]]
    np.testing.assert_array_equal(canonical(got), reference_join(q, data))
    return s, res


# k = n_dev (identity), 4·n_dev, 64·n_dev — the ISSUE's fold ladder.
@pytest.mark.parametrize("k", [N_DEV, 4 * N_DEV, 64 * N_DEV])
def test_folded_two_way_bit_exact(k):
    q = two_way()
    data = skewed_join_dataset(q, 600, 40, skew={"B": 1.9}, seed=31)
    plan = plan_skew_join(q, data, k)
    ex = ShardedJoinExecutor(plan, _mesh(),
                             config=ExecutorConfig(out_capacity=1 << 18))
    s, _ = _check_exact(q, data, ex)
    expect = "modulo" if k == N_DEV else "lpt"
    assert s.placement.strategy == expect
    assert s.placement.k == k and s.placement.n_devices == N_DEV


@pytest.mark.parametrize("k", [4 * N_DEV, 64 * N_DEV])
def test_folded_three_way_running_example(k):
    q = running_example()
    data = skewed_join_dataset(q, 100, 50, skew={"B": 1.5, "C": 1.2}, seed=32)
    plan = plan_skew_join(q, data, k, max_hh_per_attr=3)
    ex = ShardedJoinExecutor(plan, _mesh(),
                             config=ExecutorConfig(out_capacity=1 << 16))
    _check_exact(q, data, ex)


def test_cross_residual_cells_share_device():
    """Two logical cells of DIFFERENT residual joins pinned to one device.

    This is the invariant the logical-cell tag guards: constituents arriving
    at a shared device via different residuals must not cross-join.  The
    placement explicitly folds cell 0 of residual block 0 and the first cell
    of residual block 1 onto device 0."""
    q = two_way()
    data = skewed_join_dataset(q, 600, 30, skew={"B": 1.9}, seed=33)
    k = 32
    plan = plan_skew_join(q, data, k)
    assert len(plan.residuals) >= 2, "skew must yield several residual joins"
    table = np.arange(k, dtype=np.int32) % N_DEV
    c0 = plan.residuals[0].cube.offset % k
    c1 = plan.residuals[1].cube.offset % k
    assert c0 != c1
    table[c0] = table[c1] = 0
    ex = ShardedJoinExecutor(plan, _mesh(),
                             config=ExecutorConfig(out_capacity=1 << 18))
    s, _ = _check_exact(q, data, ex,
                        placement=CellPlacement(table, N_DEV))
    assert s.placement.strategy == "explicit"


def test_adversarial_all_cells_on_one_device():
    """Every logical cell folded onto device 0 — the extreme shared-cell
    case.  Slower, never wrong (the other 7 devices receive only padding)."""
    q = two_way()
    data = skewed_join_dataset(q, 400, 40, skew={"B": 1.7}, seed=34)
    plan = plan_skew_join(q, data, 32)
    adv = CellPlacement(np.zeros(32, np.int32), N_DEV)
    ex = ShardedJoinExecutor(plan, _mesh(),
                             config=ExecutorConfig(out_capacity=1 << 18))
    _, res = _check_exact(q, data, ex, placement=adv)
    assert (res["recv_counts"][1:] == 0).all()
    assert res["recv_counts"][0] > 0


def test_lpt_balances_at_least_as_well_as_modulo():
    """Delivered per-device load (recv_counts): LPT <= modulo, same results."""
    q = two_way()
    data = skewed_join_dataset(q, 2000, 60, skew={"B": 1.8}, seed=35)
    plan = plan_skew_join(q, data, 64)
    loads = plan.cell_loads(data)
    cfg = ExecutorConfig(out_capacity=1 << 18)
    ex = ShardedJoinExecutor(plan, _mesh(), config=cfg)
    _, res_lpt = _check_exact(q, data, ex,
                              placement=lpt_placement(loads, N_DEV))
    _, res_mod = _check_exact(q, data, ex,
                              placement=modulo_placement(64, N_DEV))
    assert res_lpt["recv_counts"].sum() == res_mod["recv_counts"].sum()
    assert res_lpt["recv_counts"].max() <= res_mod["recv_counts"].max()


def test_session_caps_match_plan_hook_with_placement():
    """Jitted count pass + host fold == the numpy shuffle_capacity oracle."""
    q = two_way()
    data = skewed_join_dataset(q, 600, 50, skew={"B": 1.5}, seed=36)
    plan = plan_skew_join(q, data, 32)
    ex = ShardedJoinExecutor(plan, _mesh())
    s = ex.session().prepare(data)
    assert s.placement is not None and s.placement.strategy == "lpt"
    for rel in q.relations:
        sharded = ex._shard(np.asarray(data[rel.name]))
        worst = plan.shuffle_capacity(rel.name, sharded, N_DEV, s.placement)
        expect = quantize_capacity(
            int(np.ceil(worst * ex.config.capacity_factor)),
            ex.config.cap_bucket)
        assert s.caps[rel.name] == expect, rel.name


def test_folded_warm_path_no_recompile():
    """Folding keeps the session guarantees: second batch = zero rebuilds,
    and a DIFFERENT placement reuses the same executable (table is traced)."""
    q = two_way()
    data = skewed_join_dataset(q, 400, 50, skew={"B": 1.6}, seed=37)
    plan = plan_skew_join(q, data, 32)
    ex = ShardedJoinExecutor(plan, _mesh(),
                             config=ExecutorConfig(out_capacity=1 << 18))
    s = ex.session().prepare(data)
    s.run_batch()
    assert ex.compile_count == 1
    s.run_batch()
    s.run_batch(data)
    assert ex.compile_count == 1
    # Same caps, different placement table -> still the same compiled step.
    s2 = ex.session().prepare(data, caps=s.caps,
                              placement=modulo_placement(32, N_DEV))
    res = s2.run_batch()
    assert ex.compile_count == 1
    got = res["rows"][res["valid"]]
    np.testing.assert_array_equal(canonical(got), reference_join(q, data))


def test_k_smaller_than_mesh_raises():
    q = two_way()
    data = skewed_join_dataset(q, 100, 20, seed=38)
    plan = plan_skew_join(q, data, 4)
    with pytest.raises(ValueError, match="folding maps many"):
        ShardedJoinExecutor(plan, _mesh())


def test_non_power_of_two_k_raises():
    q = two_way()
    data = skewed_join_dataset(q, 100, 20, seed=39)
    plan = plan_skew_join(q, data, 24)
    with pytest.raises(ValueError, match="not a power of two"):
        ShardedJoinExecutor(plan, _mesh())


def test_mismatched_placement_raises():
    q = two_way()
    data = skewed_join_dataset(q, 100, 20, seed=40)
    plan = plan_skew_join(q, data, 32)
    wrong = modulo_placement(16, N_DEV)
    with pytest.raises(ValueError, match="placement maps"):
        ShardedJoinExecutor(plan, _mesh(), placement=wrong)
    ex = ShardedJoinExecutor(plan, _mesh())
    with pytest.raises(ValueError, match="placement maps"):
        ex.session().prepare(data, placement=wrong)


@pytest.mark.parametrize("strategy", ["lpt", "modulo"])
def test_fold_dests_matches_numpy(strategy):
    """The map's placement fold vs `CellPlacement.device_of`: every copy in
    `scatter_pack_host`'s buffer sits on the device its wrapped logical
    cell folds to, and each device holds exactly its cells' copies."""
    from repro.core.placement import place_cells
    from repro.kernels.map_pack import route_fanout
    from repro.kernels.scatter_pack import scatter_pack_host
    rng = np.random.default_rng(41)
    k = 64
    p = place_cells(rng.uniform(0, 100, k), k, N_DEV, strategy)
    # One route over k cells, fanout 2: col 0 hashed over k/2 cells, each
    # row replicated to both halves.
    spec = ((((0, 0x9E3779B1, k // 2, 1),), (0, k // 2), 0, (), ()),)
    rows = rng.integers(0, 1000, size=(1024, 2)).astype(np.int32)
    rows[rng.random(1024) < 0.1] = -1                     # padding rows
    m = len(rows) * route_fanout(spec)
    buf, over = scatter_pack_host(jnp.asarray(rows), jnp.asarray(p.table),
                                  routes=spec, k=k, n_dev=N_DEV, cap=m)
    assert int(over) == 0
    buf = np.asarray(buf)
    valid = buf[..., -1] >= 0
    dev = np.broadcast_to(np.arange(N_DEV)[:, None], valid.shape)[valid]
    cells = buf[valid][:, -1] % k
    np.testing.assert_array_equal(dev, p.device_of(cells))
    assert valid.sum() == 2 * (rows[:, 0] >= 0).sum()
