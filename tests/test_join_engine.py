"""Multi-tenant join serving: bucketing, executable cache, scheduling."""
import jax
import numpy as np
import pytest

from repro.core import canonical, reference_join, two_way
from repro.core.adapt import AdaptPolicy, TenantDriftBank
from repro.data import mixed_workload, skewed_join_dataset
from jax.sharding import AxisType
from repro.serve import JoinServingEngine

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices")


def _mesh():
    return jax.make_mesh((8,), ("cells",), axis_types=(AxisType.Auto,))


def _engine(**kw):
    return JoinServingEngine(_mesh(), k=8, **kw)


def _check_exact(req, query, data):
    assert req.done
    got = canonical(req.rows)
    expect = canonical(reference_join(query, data))
    assert got.shape == expect.shape
    np.testing.assert_array_equal(got, expect)


def test_single_tenant_warm_reuse_and_exact():
    """Same-bucket requests of one tenant share one prepared session: one
    prepare, one compile ladder, every result exact."""
    eng = _engine()
    q = two_way()
    reqs = [(eng.submit("t", q, d), d)
            for d in (skewed_join_dataset(q, 400, 800, seed=s)
                      for s in range(4))]
    eng.run()
    for req, d in reqs:
        _check_exact(req, q, d)
    st = eng.stats
    assert st["tenants"]["t"]["prepares"] == 1
    assert eng.cache.stats["hits"] == 3 and eng.cache.stats["misses"] == 1
    # Steady state: a fifth same-bucket request compiles nothing.
    c0 = eng.cache.compile_count()
    req = eng.submit("t", q, skewed_join_dataset(q, 400, 800, seed=9))
    eng.run()
    assert eng.cache.compile_count() == c0
    assert req.done


def test_multi_tenant_interleaved_exact_with_split_stats():
    """Three structurally distinct tenants interleave on one mesh; results
    stay exact per request and the shared sessions' counters split out into
    per-tenant stats."""
    eng = _engine()
    reqs = [(eng.submit(t, q, d), q, d)
            for t, q, d in mixed_workload(9, seed=0)]
    eng.run()
    for req, q, d in reqs:
        _check_exact(req, q, d)
    st = eng.stats
    assert st["requests"] == 9
    assert set(st["tenants"]) == {"pairs", "chain3", "chain4"}
    for name, ts in st["tenants"].items():
        assert ts["requests"] == 3, name
        assert ts["rows_in"] > 0
        assert ts["batches"] >= 3            # retries add attempts
    # Distinct structures -> distinct executors, never shared.
    assert eng.cache.stats["executors"] == 3


def test_multi_tenant_steady_replay_is_warm():
    """After two cycles of the three-tenant mix, a replay with fresh data
    at the same sizes compiles nothing and hits the session cache at a rate
    of at least 0.9; every request stays exact."""
    eng = _engine()
    for t, q, d in mixed_workload(12, seed=0):
        eng.submit(t, q, d)
    eng.run()
    c0, h0, m0 = eng.cache.compile_count(), eng.cache.hits, eng.cache.misses
    reqs = [(eng.submit(t, q, d), q, d)
            for t, q, d in mixed_workload(6, seed=1)]
    eng.run()
    for req, q, d in reqs:
        _check_exact(req, q, d)
    assert len({str(q) for _, q, _ in reqs}) == 3    # distinct structures
    assert eng.cache.compile_count() == c0           # zero recompiles
    hits, misses = eng.cache.hits - h0, eng.cache.misses - m0
    assert hits / max(hits + misses, 1) >= 0.9


def test_shape_bucketing_shares_executables():
    """Requests whose row counts land in one geometric bucket share a
    prepared session (cache hit); a count past the bucket edge is a miss."""
    eng = _engine()
    q = two_way()
    for n, seed in ((300, 1), (400, 2), (500, 3)):   # all -> bucket 512
        eng.submit("t", q, skewed_join_dataset(q, n, 800, seed=seed))
    eng.run()
    assert eng.cache.stats == dict(eng.cache.stats, hits=2, misses=1)
    eng.submit("t", q, skewed_join_dataset(q, 600, 800, seed=4))  # bucket 1024
    eng.run()
    assert eng.cache.stats["misses"] == 2
    assert eng.cache.stats["sessions"] == 2
    assert eng.cache.stats["executors"] == 1         # same structure


def test_structural_collision_does_not_share_steps():
    """Two tenants colliding on (k, route specs) but differing in shapes
    share ONE executor yet get distinct sessions and distinct compiled
    steps — and both stay exact."""
    eng = _engine()
    q = two_way()
    d_small = skewed_join_dataset(q, 300, 900, seed=5)
    d_big = skewed_join_dataset(q, 900, 900, seed=6)
    r1 = eng.submit("small", q, d_small)
    r2 = eng.submit("big", q, d_big)
    eng.run()
    _check_exact(r1, q, d_small)
    _check_exact(r2, q, d_big)
    cs = eng.cache.stats
    assert cs["executors"] == 1                      # structures collide
    assert cs["sessions"] == 2                       # shapes do not
    t_small = eng.tenants["small"]
    t_big = eng.tenants["big"]
    assert t_small.struct_key == t_big.struct_key
    (ex,) = eng.cache._executors.values()
    shapes = {key[0] for key in ex._step_cache}
    assert len(shapes) >= 2                          # one step per shape


def test_session_eviction_reprepares_warm_and_bit_exact():
    """Evicting a live tenant's session must be transparent: the next
    request re-prepares (a miss) but the executor's step cache keeps the
    bucket's executable, so ZERO new compiles — and the replayed request is
    bit-exact."""
    eng = _engine(max_sessions=1)
    q = two_way()
    d_a = skewed_join_dataset(q, 300, 800, seed=7)   # bucket 512
    d_b = skewed_join_dataset(q, 900, 800, seed=8)   # bucket 1024
    rows_a = {}
    for d, key in ((d_a, "a"), (d_b, "b")):          # cold cycle
        req = eng.submit("t", q, d)
        eng.run()
        rows_a[key] = canonical(req.rows)
    assert eng.cache.stats["evictions"] >= 1         # bound forced eviction
    c0 = eng.cache.compile_count()
    p0 = eng.tenants["t"].stats["prepares"]
    for d, key in ((d_a, "a"), (d_b, "b")):          # replay: evict + re-prepare
        req = eng.submit("t", q, d)
        eng.run()
        np.testing.assert_array_equal(canonical(req.rows), rows_a[key])
    assert eng.cache.compile_count() == c0           # warm re-prepare
    assert eng.tenants["t"].stats["prepares"] == p0 + 2


def test_round_robin_scheduling_drains_all_tenants():
    """`max_live` bounds each round; rotation keeps every tenant served."""
    eng = _engine(max_live=2)
    q = two_way()
    reqs = []
    for t in ("a", "b", "c"):
        for s in (1, 2):
            d = skewed_join_dataset(q, 200, 500, seed=s)
            reqs.append((eng.submit(t, q, d), d))
    served = eng.step_round()
    assert served == 2                               # bounded by max_live
    eng.run()
    for req, d in reqs:
        _check_exact(req, q, d)
    assert all(t.stats["requests"] == 2 for t in eng.tenants.values())


def test_tenant_query_switch_rejected():
    eng = _engine()
    q = two_way()
    eng.submit("t", q, skewed_join_dataset(q, 100, 200, seed=1))
    eng.run()
    from repro.core import running_example
    q3 = running_example()
    with pytest.raises(ValueError, match="switched query structure"):
        eng.submit("t", q3, skewed_join_dataset(q3, 100, 200, seed=1))


def test_per_tenant_adaptation_is_isolated():
    """With adapt= enabled, a hair-trigger policy re-places the tenant that
    drifts without touching the others' detectors — and every post-action
    result stays exact."""
    policy = AdaptPolicy(replace_threshold=0.001, replan_threshold=0.99,
                         window=2, patience=1, min_batches=1,
                         replace_cooldown=1, replan_cooldown=99)
    eng = _engine(adapt=policy)
    q = two_way()
    reqs = []
    for s in range(4):
        # Shifting seeds move load between cells -> TV drift > 0.001.
        d = skewed_join_dataset(q, 400, 600, skew={"B": 0.8}, seed=40 + s)
        reqs.append((eng.submit("drifty", q, d), d))
    d_stable = skewed_join_dataset(q, 300, 600, seed=50)
    stable_req = eng.submit("calm", q, d_stable)
    eng.run()
    for req, d in reqs:
        _check_exact(req, q, d)
    _check_exact(stable_req, q, d_stable)
    assert eng.tenants["drifty"].stats["replacements"] >= 1
    # Isolation: each tenant has its OWN detector, windowing only its own
    # stream — drifty's four batches never advance calm's single-batch one.
    det_d, det_c = eng.adapt.get("drifty"), eng.adapt.get("calm")
    assert det_d is not det_c
    assert det_d.batches >= 4 and det_c.batches == 1
    assert det_d.history                          # acted on drifty


def test_drift_bank_routes_by_tenant():
    """Host-side: the bank keeps per-tenant windows — one tenant's drift
    never advances another's streaks."""
    bank = TenantDriftBank(AdaptPolicy(replace_threshold=0.05,
                                       replan_threshold=0.9, patience=2,
                                       min_batches=1))
    base = np.ones(8)
    bank.register("a", base)
    bank.register("b", base)
    shifted = np.array([8, 1, 1, 1, 1, 1, 1, 1], float)
    assert bank.observe("a", shifted) == "stable"    # patience 1/2
    assert bank.observe("a", shifted) == "replace"   # patience 2/2
    assert bank.observe("b", base) == "stable"       # unaffected
    assert bank.observe("unknown", shifted) == "stable"
    bank.rebaseline("a", shifted, action="replace")
    assert bank.get("a").history and not bank.get("b").history


def test_mixed_workload_deterministic():
    """Same arguments -> byte-identical request stream (bench replays)."""
    a = list(mixed_workload(6, seed=3))
    b = list(mixed_workload(6, seed=3))
    names = [t for t, _, _ in a]
    assert len(set(names)) == 3                      # >= 3 distinct queries
    for (ta, qa, da), (tb, qb, db) in zip(a, b):
        assert ta == tb and qa == qb
        for name in da:
            np.testing.assert_array_equal(da[name], db[name])
    c = list(mixed_workload(6, seed=4))
    assert any(not np.array_equal(da[n], dc[n])
               for (_, _, da), (_, _, dc) in zip(a, c) for n in da)
