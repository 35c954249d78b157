"""The count pass `map_count` vs the staged count formula — every path.

`map_count` (the Pallas kernel, in interpret mode here, compiled on TPU),
its vectorized-XLA twin `map_count_host` and the `kernels.ops` entry point
must agree bit for bit with `map_count_ref`, which materializes every routed
copy and scatter-adds it.  Coverage: k in {1, 8, 256} with n_src < k,
multi-residual recipes with replication fanout > 1, eq / not-in type
constraints, m = 0 and invalid rows.  On the mesh, prepare runs the pass
exactly once, or not at all when it is handed capacities and a placement.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.executor import _Route, _route_specs
from repro.core.placement import modulo_placement
from repro.kernels import map_pack as mp
from repro.kernels import ops as kops
from repro.kernels.ref import _map_route_ref, map_count_ref

SEED_A, SEED_B = 0x9E3779B1, 0x85EBCA77          # odd multiply-shift seeds


def _routes_for(k: int, w: int = 3) -> list[_Route]:
    """Synthetic multi-residual recipe: hashed attrs, fanout > 1 via
    replication offsets, an eq- and a not-in-constrained route."""
    if k == 1:
        return [_Route("T", ((0, SEED_A, 1, 1),), (0,), 0, (), ())]
    half, quarter = max(k // 2, 1), max(k // 4, 1)
    return [
        # residual 0: hash col0 over half the cells, replicate twice.
        _Route("T", ((0, SEED_A, half, 1),), (0, half), 0, (),
               ((1, (7, 13)),)),
        # residual 1: col1 frozen to a HH value, hash col0 x col2 grid.
        _Route("T", ((0, SEED_B, quarter, 1), (2, SEED_A, 2, quarter)),
               (0,), quarter, ((1, 7),), ()),
    ]


def _rand_rows(rng, m, w=3, domain=50, invalid_frac=0.1):
    rows = rng.integers(0, domain, size=(m, w)).astype(np.int32)
    rows[rng.random(m) < invalid_frac] = -1                 # padding rows
    return rows


@pytest.mark.parametrize("k,n_src", [(1, 1), (8, 4), (256, 8)])
@pytest.mark.parametrize("m", [0, 64, 200])
def test_count_matches_staged_formula(k, n_src, m):
    rng = np.random.default_rng(m + k)
    routes = _routes_for(k)
    rows = jnp.asarray(_rand_rows(rng, m))
    spec = _route_specs(routes)
    expect = np.asarray(map_count_ref(rows, spec, k, n_src))
    for name, got in {
        "kernel": mp.map_count(rows, routes=spec, k=k, n_src=n_src,
                               interpret=True),
        "host": mp.map_count_host(rows, routes=spec, k=k, n_src=n_src),
        "ops": kops.map_count(rows, spec, k, n_src),
    }.items():
        np.testing.assert_array_equal(np.asarray(got), expect,
                                      err_msg=f"path={name} k={k} m={m}")


def test_count_histogram_totals_valid_copies_only():
    k, n_src = 8, 4
    routes = _routes_for(k)
    rows = jnp.asarray(_rand_rows(np.random.default_rng(6), 96))
    spec = _route_specs(routes)
    _, wrapped = _map_route_ref(rows, spec, k)
    counts = np.asarray(mp.map_count_host(rows, routes=spec, k=k,
                                          n_src=n_src))
    assert counts.sum() == int((np.asarray(wrapped) >= 0).sum())


# -- executor integration (needs the 8-device mesh) --------------------------

needs_mesh = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


@needs_mesh
def test_executor_prepare_counts_once_and_is_exact():
    """prepare runs the count pass once; its matrices are each device
    shard's `map_count_ref`, and the capacities derived from them hold the
    step's copies: no overflow, the reference join."""
    from repro.core import canonical, plan_skew_join, reference_join, two_way
    from repro.core.executor import ExecutorConfig, ShardedJoinExecutor
    from repro.data import skewed_join_dataset
    from jax.sharding import AxisType
    mesh = jax.make_mesh((8,), ("cells",), axis_types=(AxisType.Auto,))
    q = two_way()
    data = skewed_join_dataset(q, 600, 50, skew={"B": 1.6}, seed=42)
    plan = plan_skew_join(q, data, 32)
    ex = ShardedJoinExecutor(plan, mesh, config=ExecutorConfig(
        out_capacity=1 << 17))
    s = ex.session().prepare(data)
    assert s.count_passes == 1          # prepare routes data exactly once
    for rel, mat in zip(q.relations, s._count_mats):
        sharded = ex._shard(np.asarray(data[rel.name]))
        np.testing.assert_array_equal(
            mat, np.asarray(map_count_ref(jnp.asarray(sharded),
                                          ex.route_specs[rel.name],
                                          plan.k, 8)), err_msg=rel.name)
    res = s.run_batch()
    assert res["shuffle_overflow"].sum() == 0
    assert res["join_overflow"].sum() == 0
    got = res["rows"][res["valid"]]
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
