"""Chaos suite: every fault the self-healing session claims to survive,
injected deterministically (ft/chaos.py), recovery asserted BIT-EXACT
against the fault-free reference_join oracle.

Scenarios (the ISSUE 6 acceptance matrix):
  * capacity overflow  -> bounded retry, bucket-aligned escalation, exact
                          result, and a ladder already walked by this
                          executor compiles ZERO new executables;
  * retry budget       -> RetryBudgetExceededError with the per-device,
                          per-phase breakdown (never an unbounded loop);
  * device loss        -> dropped heartbeats age out, the device is evicted,
                          cells re-fold over survivors (traced table: the
                          re-fold never recompiles), evicted device receives
                          zero rows, output exact;
  * straggler          -> injected per-device delay strikes out, same
                          eviction/re-fold path, output exact;
  * corrupted rows     -> rejected by input validation naming the relation,
                          session stays usable and the clean retry is exact.
"""
import numpy as np
import pytest
import jax

from repro.core import canonical, plan_skew_join, reference_join, two_way
from repro.core.executor import (CapacityOverflowError, ExecutorConfig,
                                 InputValidationError, RetryBudgetExceededError,
                                 RetryPolicy, ShardedJoinExecutor)
from repro.data import skewed_join_dataset
from repro.ft import ChaosInjector
from repro.serve import SelfHealingSession

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices")

N_DEV = 8


def _mesh():
    from jax.sharding import AxisType
    return jax.make_mesh((N_DEV,), ("cells",), axis_types=(AxisType.Auto,))


def _executor(data, q, k=32, **cfg_kw):
    plan = plan_skew_join(q, data, k)
    cfg = ExecutorConfig(**{"out_capacity": 65536, **cfg_kw})
    return plan, ShardedJoinExecutor(plan, _mesh(), config=cfg)


def _exact(res, q, data):
    got = res["rows"][res["valid"]]
    np.testing.assert_array_equal(canonical(got), reference_join(q, data))


# -- overflow ---------------------------------------------------------------

def test_overflow_retry_recovers_exactly():
    """Chaos-squeezed caps overflow; bounded retry escalates within the
    bucket grid and delivers the exact fault-free result."""
    q = two_way()
    data = skewed_join_dataset(q, 500, 40, skew={"B": 1.7}, seed=51)
    _, ex = _executor(data, q)
    chaos = ChaosInjector(N_DEV, seed=0)
    chaos.squeeze_caps(0.3)                    # forced-tiny caps -> overflow
    eng = SelfHealingSession(ex, chaos=chaos).prepare(data)
    res = eng.run_batch()
    _exact(res, q, data)
    st = eng.stats
    assert st["retries"] >= 1                           # it DID overflow
    assert st["retries"] <= RetryPolicy().max_retries
    assert st["shuffle_overflow"].sum() >= 1            # attempts kept visible
    assert res["shuffle_overflow"].sum() == 0           # delivered result clean
    assert res["join_overflow"].sum() == 0


def test_overflow_retry_ladder_is_warm_second_time():
    """A retry ladder the executor has walked once compiles NOTHING when a
    second session (same shapes, same squeezed start caps) walks it again —
    the capacity-bucket grid is what makes retries cheap."""
    q = two_way()
    data = skewed_join_dataset(q, 500, 40, skew={"B": 1.7}, seed=51)
    _, ex = _executor(data, q)

    def healed_run():
        chaos = ChaosInjector(N_DEV, seed=0)
        chaos.squeeze_caps(0.3)
        eng = SelfHealingSession(ex, chaos=chaos).prepare(data)
        res = eng.run_batch()
        _exact(res, q, data)
        return eng

    first = healed_run()
    assert first.stats["retries"] >= 1
    compiles_after_first = ex.compile_count
    second = healed_run()
    assert second.stats["retries"] == first.stats["retries"]
    assert ex.compile_count == compiles_after_first     # zero new executables


def test_retry_budget_exceeded_raises_with_breakdown():
    q = two_way()
    data = skewed_join_dataset(q, 500, 40, skew={"B": 1.7}, seed=51)
    _, ex = _executor(data, q)
    chaos = ChaosInjector(N_DEV, seed=0)
    chaos.squeeze_caps(0.3)
    eng = SelfHealingSession(ex, retry=RetryPolicy(max_retries=0),
                             chaos=chaos).prepare(data)
    with pytest.raises(RetryBudgetExceededError,
                       match=r"(?s)retry budget exhausted.*dev 0"):
        eng.run_batch()
    # The taxonomy nests: budget exhaustion IS a capacity overflow.
    with pytest.raises(CapacityOverflowError):
        SelfHealingSession(ex, retry=RetryPolicy(max_retries=0),
                           chaos=chaos).prepare(data).run_batch()


# -- device loss ------------------------------------------------------------

def test_device_loss_refolds_over_survivors_exactly():
    """Dropped heartbeats age out on the virtual clock; the dead device is
    evicted, cells re-fold over the 7 survivors with zero recompiles, the
    evicted device receives zero rows, and output stays bit-exact."""
    q = two_way()
    data = skewed_join_dataset(q, 600, 50, skew={"B": 1.6}, seed=52)
    _, ex = _executor(data, q)
    dead = 3
    chaos = ChaosInjector(N_DEV, seed=0)
    chaos.drop_heartbeats(dead)
    eng = SelfHealingSession(ex, chaos=chaos, heartbeat_timeout_s=2.5,
                             suspect_timeout_s=1.5,
                             step_seconds=1.0).prepare(data)
    _exact(eng.run_batch(), q, data)            # healthy batch, beats recorded
    while eng.evicted == [] and eng.session.stats["batches"] < 16:
        res = eng.run_batch()
        _exact(res, q, data)
    assert eng.evicted == [dead]
    assert eng.alive == [d for d in range(N_DEV) if d != dead]
    assert eng.refolds == 1
    assert eng.refold_compiles == 0             # caps stayed in their bucket
    compiles_before = ex.compile_count
    res = eng.run_batch()                       # degraded-mode batch
    _exact(res, q, data)
    assert ex.compile_count == compiles_before  # traced table: warm step
    assert res["recv_counts"][dead] == 0        # evicted device gets nothing
    assert (np.delete(res["recv_counts"], dead) > 0).all()


def test_evicting_every_device_refuses():
    from repro.core.executor import DeviceLossError

    q = two_way()
    data = skewed_join_dataset(q, 300, 30, seed=53)
    _, ex = _executor(data, q)
    eng = SelfHealingSession(ex).prepare(data)
    for d in range(N_DEV - 1):
        eng.evict_device(d)
    with pytest.raises(DeviceLossError, match="no surviving devices"):
        eng.evict_device(N_DEV - 1)
    _exact(eng.run_batch(), q, data)            # all cells on one device: exact


# -- stragglers -------------------------------------------------------------

def test_straggler_is_evicted_and_result_exact():
    q = two_way()
    data = skewed_join_dataset(q, 600, 50, skew={"B": 1.6}, seed=54)
    _, ex = _executor(data, q)
    slow = 5
    chaos = ChaosInjector(N_DEV, seed=0)
    chaos.delay_device(slow, 30.0)              # 30s/step on a sub-second step
    eng = SelfHealingSession(ex, chaos=chaos, straggler_threshold=1.5,
                             evict_after=2).prepare(data)
    while eng.evicted == [] and eng.session.stats["batches"] < 8:
        _exact(eng.run_batch(), q, data)
    assert eng.evicted == [slow]
    assert eng.refold_compiles == 0             # caps stayed in their bucket
    compiles_before = ex.compile_count
    res = eng.run_batch()
    _exact(res, q, data)
    assert ex.compile_count == compiles_before  # traced table: warm step
    assert res["recv_counts"][slow] == 0


# -- corruption -------------------------------------------------------------

def test_corrupted_rows_rejected_then_clean_retry_exact():
    """Scheduled corruption is rejected by input validation (naming the
    relation and row) BEFORE routing; the session stays usable and the next
    clean chunk delivers the exact result on the warm step."""
    q = two_way()
    data = skewed_join_dataset(q, 500, 40, skew={"B": 1.5}, seed=55)
    _, ex = _executor(data, q)
    chaos = ChaosInjector(N_DEV, seed=3)
    eng = SelfHealingSession(ex, chaos=chaos).prepare(data)
    _exact(eng.run_batch(data), q, data)        # step 0: clean
    chaos.corrupt_rows("R", n_rows=2)           # due at the current step
    with pytest.raises(InputValidationError, match=r"relation 'R'.*corrupted"):
        eng.run_batch(data)
    compiles = ex.compile_count
    res = eng.run_batch(data)                   # corruption was one-shot
    _exact(res, q, data)
    assert ex.compile_count == compiles         # still the warm executable
