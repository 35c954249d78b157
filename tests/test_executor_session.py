"""ExecutorSession: device-resident prepare/run_batch, warm-path guarantees."""
import numpy as np
import pytest
import jax

from repro.core import canonical, plan_skew_join, reference_join, two_way
from repro.core.executor import (ExecutorConfig, ShardedJoinExecutor,
                                 quantize_capacity)
from repro.data import skewed_join_dataset

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices")


def _mesh():
    from jax.sharding import AxisType
    return jax.make_mesh((8,), ("cells",), axis_types=(AxisType.Auto,))


def _executor(data, q, **cfg_kw):
    plan = plan_skew_join(q, data, 8)
    cfg = ExecutorConfig(**{"out_capacity": 65536, **cfg_kw})
    return plan, ShardedJoinExecutor(plan, _mesh(), config=cfg)


def test_session_matches_reference_join():
    q = two_way()
    data = skewed_join_dataset(q, 500, 40, skew={"B": 1.7}, seed=21)
    _, ex = _executor(data, q)
    res = ex.session().prepare(data).run_batch()
    got = res["rows"][res["valid"]]
    np.testing.assert_array_equal(canonical(got), reference_join(q, data))


def test_session_capacity_matches_plan_hook():
    """The jitted on-device capacity pass == the numpy shuffle_capacity hook
    (rounded up to the config's capacity bucket grid)."""
    q = two_way()
    data = skewed_join_dataset(q, 600, 50, skew={"B": 1.5}, seed=22)
    plan, ex = _executor(data, q)
    s = ex.session().prepare(data)
    for rel in q.relations:
        sharded = ex._shard(np.asarray(data[rel.name]))
        worst = plan.shuffle_capacity(rel.name, sharded, plan.k)
        raw = int(np.ceil(worst * ex.config.capacity_factor))
        expect = quantize_capacity(raw, ex.config.cap_bucket)
        assert s.caps[rel.name] == expect, rel.name
        assert expect >= raw                      # bucketing only adds room


def test_session_run_batch_streams_chunks():
    """Smaller same-schema chunks ride the warm executable, exact results."""
    q = two_way()
    data = skewed_join_dataset(q, 800, 60, skew={"B": 1.6}, seed=23)
    chunk = {name: arr[: len(arr) // 2] for name, arr in data.items()}
    _, ex = _executor(data, q)
    s = ex.session().prepare(data)
    res_full = s.run_batch()
    compiles_after_prepare = ex.compile_count
    res_chunk = s.run_batch(chunk)
    assert ex.compile_count == compiles_after_prepare   # warm path, no rebuild
    got_full = res_full["rows"][res_full["valid"]]
    got_chunk = res_chunk["rows"][res_chunk["valid"]]
    np.testing.assert_array_equal(canonical(got_full), reference_join(q, data))
    np.testing.assert_array_equal(canonical(got_chunk),
                                  reference_join(q, chunk))


def test_session_no_recompile_on_second_batch():
    """Second same-shaped run_batch must hit the jit cache (CI guard twin)."""
    q = two_way()
    data = skewed_join_dataset(q, 400, 50, seed=24)
    _, ex = _executor(data, q)
    s = ex.session().prepare(data)
    s.run_batch()
    assert ex.compile_count == 1
    (step,) = ex._step_cache.values()
    # Private jax counter — skip that leg if an upgrade removes it; the
    # public compile_count assertions are the contract.
    cache_size = getattr(step, "_cache_size", None)
    assert cache_size is None or cache_size() == 1
    s.run_batch()
    s.run_batch(data)                                   # same shapes via chunks
    assert ex.compile_count == 1
    assert cache_size is None or cache_size() == 1


def test_sessions_share_executor_step_cache():
    q = two_way()
    data = skewed_join_dataset(q, 300, 30, seed=25)
    _, ex = _executor(data, q)
    ex.session().prepare(data).run_batch()
    ex.session().prepare(data).run_batch()              # same shapes + caps
    assert ex.compile_count == 1


def test_session_caps_override():
    """prepare(caps=...) bypasses the capacity pass; tiny caps must overflow."""
    q = two_way()
    data = skewed_join_dataset(q, 600, 10, skew={"B": 1.9}, seed=26)
    _, ex = _executor(data, q)
    caps = {r.name: 1 for r in q.relations}
    res = ex.session().prepare(data, caps=caps).run_batch()
    assert res["shuffle_overflow"].sum() > 0


def test_run_batch_oversized_chunk_warns_and_recompiles():
    """A chunk larger than the prepared shapes can't ride the warm path: it
    must WARN (not silently recompile), bump compile_count, and still be
    exact.  The documented escape hatch — re-prepare() — restores the warm
    path for the new size."""
    q = two_way()
    data = skewed_join_dataset(q, 400, 50, skew={"B": 1.4}, seed=28)
    big = skewed_join_dataset(q, 900, 50, skew={"B": 1.4}, seed=29)
    _, ex = _executor(data, q)
    s = ex.session().prepare(data)
    s.run_batch()
    assert ex.compile_count == 1
    with pytest.warns(UserWarning, match="exceed the prepared"):
        res = s.run_batch(big)
    assert ex.compile_count == 2                        # surfaced recompile
    got = res["rows"][res["valid"]]
    np.testing.assert_array_equal(canonical(got), reference_join(q, big))
    # Escape hatch: re-prepare re-derives shapes/caps; no warning, warm after.
    s.prepare(big)
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        s.run_batch(big)
    compiles = ex.compile_count
    s.run_batch(big)
    assert ex.compile_count == compiles                 # warm again


def test_session_empty_plan():
    q = two_way()
    data = {"R": np.zeros((0, 2), np.int64),
            "S": np.stack([np.arange(20), np.arange(20)], axis=1)}
    plan = plan_skew_join(q, data, 8)
    ex = ShardedJoinExecutor(plan, _mesh(),
                             config=ExecutorConfig(out_capacity=64))
    res = ex.session().prepare(data).run_batch()
    assert res["valid"].sum() == 0


def test_run_batch_before_prepare_raises():
    q = two_way()
    data = skewed_join_dataset(q, 100, 20, seed=27)
    _, ex = _executor(data, q)
    with pytest.raises(RuntimeError, match="before prepare"):
        ex.session().run_batch()


def test_capacity_bucketing_shares_executables():
    """Two same-shaped datasets whose raw derived caps differ but land in the
    same capacity bucket share ONE compiled step (the warm-cache win that
    bucketing buys; ratio 2.0 = power-of-two grid)."""
    from repro.core.executor import quantize_capacity

    q = two_way()
    d1 = skewed_join_dataset(q, 500, 40, skew={"B": 1.5}, seed=25)
    d2 = skewed_join_dataset(q, 500, 40, skew={"B": 1.6}, seed=26)
    _, ex = _executor(d1, q)
    s1 = ex.session().prepare(d1)
    s1.run_batch()
    s2 = ex.session().prepare(d2)
    if s1.caps == s2.caps:                      # same buckets (the usual case)
        s2.run_batch()
        assert ex.compile_count == 1
    # The grid itself: idempotent on grid points, strictly rounds up between.
    for c in (1, 2, 4, 1024):
        assert quantize_capacity(c, 2.0) == c
    assert quantize_capacity(3, 2.0) == 4
    assert quantize_capacity(1000, 2.0) == 1024
    assert quantize_capacity(7, 1.0) == 7       # ratio <= 1 disables the grid


def test_run_with_retry_escalates_only_failing_caps():
    """Tiny explicit caps on one relation: run_with_retry recovers exactly,
    escalates only that relation's cap on the bucket grid, and the session
    stats keep every failed attempt's overflow visible."""
    from repro.core import canonical, reference_join
    from repro.core.executor import RetryPolicy

    q = two_way()
    data = skewed_join_dataset(q, 500, 40, skew={"B": 1.7}, seed=27)
    _, ex = _executor(data, q)
    probe = ex.session().prepare(data)          # derived (sufficient) caps
    caps = dict(probe.caps)
    caps["R"] = 2                               # force R's shuffle to overflow
    s = ex.session().prepare(data, caps=caps, placement=probe.placement)
    res = s.run_with_retry()
    got = res["rows"][res["valid"]]
    np.testing.assert_array_equal(canonical(got), reference_join(q, data))
    assert s.stats["retries"] >= 1
    assert s.stats["retries"] <= RetryPolicy().max_retries
    assert s.caps["R"] > 2                      # escalated...
    assert s.caps["S"] == caps["S"]             # ...but only the failing cap
    assert s.stats["shuffle_overflow"][:, 0].sum() > 0      # R overflowed
    assert s.stats["shuffle_overflow"][:, 1].sum() == 0     # S never did
    assert res["shuffle_overflow"].sum() == 0   # delivered result is clean


def test_overflow_error_carries_per_device_breakdown():
    """result_rows on an overflowed result raises CapacityOverflowError with
    per-device, per-phase, per-relation counters (machine-readable + in the
    message)."""
    from repro.core.executor import CapacityOverflowError

    q = two_way()
    data = skewed_join_dataset(q, 500, 40, skew={"B": 1.7}, seed=28)
    _, ex = _executor(data, q)
    probe = ex.session().prepare(data)
    caps = dict(probe.caps, R=2)
    s = ex.session().prepare(data, caps=caps, placement=probe.placement)
    res = s.run_batch()
    assert res["shuffle_overflow"].sum() > 0
    with pytest.raises(CapacityOverflowError, match=r"(?s)shuffle\[R\]") as ei:
        raise CapacityOverflowError.from_result(res, ("R", "S"))
    err = ei.value
    assert err.shuffle_by_rel.shape == (8, 2)
    np.testing.assert_array_equal(err.shuffle_by_rel,
                                  res["shuffle_overflow_by_rel"])
    assert err.shuffle_by_rel[:, 0].sum() > 0   # attributed to R, not S
    assert err.shuffle_by_rel[:, 1].sum() == 0


def test_prepare_rejects_corrupted_inputs():
    """Sub-sentinel values, wrong width, float dtype: all rejected with the
    relation named, before anything is uploaded."""
    from repro.core.executor import InputValidationError

    q = two_way()
    data = skewed_join_dataset(q, 200, 20, seed=29)
    _, ex = _executor(data, q)
    bad = {k: np.array(v, copy=True) for k, v in data.items()}
    bad["R"][3, 0] = -7
    with pytest.raises(InputValidationError,
                       match=r"relation 'R'.*corrupted.*row 3"):
        ex.session().prepare(bad)
    wide = dict(data, S=np.hstack([data["S"], data["S"][:, :1]]))
    with pytest.raises(InputValidationError, match=r"relation 'S'.*columns"):
        ex.session().prepare(wide)
    floaty = dict(data, R=data["R"].astype(np.float64))
    with pytest.raises(InputValidationError, match=r"relation 'R'.*integer"):
        ex.session().prepare(floaty)
    # run_batch chunks go through the same gate.
    s = ex.session().prepare(data)
    with pytest.raises(InputValidationError, match=r"relation 'R'"):
        s.run_batch(bad)


def test_step_cache_bounded_with_eviction_counter():
    """`max_cached_steps` bounds the compiled-step LRU: the oldest signature
    is evicted (counted in `evicted_steps`), re-running it recompiles but
    stays exact, and warm lookups count in `step_hits`."""
    q = two_way()
    data = skewed_join_dataset(q, 300, 30, skew={"B": 1.2}, seed=31)
    _, ex = _executor(data, q, max_cached_steps=2)
    expect = reference_join(q, data)
    probe = ex.session().prepare(data)
    base = dict(probe.caps)

    def run_with(scale):
        caps = {name: quantize_capacity(c * scale) for name, c in base.items()}
        s = ex.session().prepare(data, caps=caps, placement=probe.placement)
        res = s.run_batch()
        np.testing.assert_array_equal(canonical(res["rows"][res["valid"]]),
                                      expect)

    run_with(1)                       # signature A
    run_with(2)                       # signature B -> cache full
    assert ex.compile_count == 2 and ex.evicted_steps == 0
    run_with(4)                       # signature C evicts A (LRU)
    assert ex.evicted_steps == 1
    assert len(ex._step_cache) == 2
    hits0 = ex.step_hits
    run_with(4)                       # C is warm
    assert ex.step_hits == hits0 + 1 and ex.compile_count == 3
    run_with(1)                       # A was evicted -> recompiles, still exact
    assert ex.compile_count == 4
    assert ex.evicted_steps == 2      # re-inserting A evicted B
    assert len(ex._step_cache) == 2
