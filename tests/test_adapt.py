"""Online skew adaptation: drift detection units + the live adaptive loop.

Host-side section: `tv_distance` / `AdaptPolicy` / `DriftDetector` driven
with synthetic count sequences — stable load never acts, gradual drift
re-places before it re-plans, a step shift re-plans a bounded number of
times (no thrash), a sketch-proven new heavy hitter forces the replan arm.

Device section (8 virtual devices): the `SelfHealingSession` adaptation
axis end to end on the deterministic drifting stream generator —
organic re-placement and same-structure re-plan both deliver BIT-EXACT
results with ZERO new compiles (the traced-table / plan-cache contract),
while a genuinely new heavy hitter compiles and says so in the honesty
counters.
"""
import numpy as np
import pytest
import jax

from repro.core import canonical, reference_join, two_way
from repro.core.adapt import AdaptPolicy, DriftDetector, tv_distance
from repro.core.executor import ExecutorConfig, ShardedJoinExecutor
from repro.core.heavy_hitters import exact_heavy_hitters
from repro.core.skewjoin import plan_from_hhs, plan_skew_join
from repro.data import drifting_join_batch
from repro.serve import SelfHealingSession

# ---------------------------------------------------------------------------
# tv_distance
# ---------------------------------------------------------------------------

def test_tv_identity_and_disjoint():
    p = np.array([3.0, 1.0, 0.0])
    assert tv_distance(p, p) == 0.0
    assert tv_distance(p, 10 * p) == 0.0          # normalization invariance
    assert tv_distance([1, 0], [0, 1]) == pytest.approx(1.0)
    assert tv_distance([1, 1], [0, 2]) == pytest.approx(0.5)


def test_tv_zero_sum_and_shape_guards():
    assert tv_distance([0, 0], [0, 0]) == 0.0
    assert tv_distance([0, 0], [1, 0]) == 1.0
    with pytest.raises(ValueError, match="shape"):
        tv_distance([1, 2], [1, 2, 3])


# ---------------------------------------------------------------------------
# AdaptPolicy validation
# ---------------------------------------------------------------------------

def test_policy_threshold_order_enforced():
    with pytest.raises(ValueError, match="replace_threshold"):
        AdaptPolicy(replace_threshold=0.5, replan_threshold=0.2)
    with pytest.raises(ValueError, match="replace_threshold"):
        AdaptPolicy(replace_threshold=0.0)
    with pytest.raises(ValueError, match="≥ 1"):
        AdaptPolicy(patience=0)


# ---------------------------------------------------------------------------
# DriftDetector state machine (synthetic load vectors; driver rebaselines on
# action exactly as the engine does).
# ---------------------------------------------------------------------------

K = 16
POL = AdaptPolicy(replace_threshold=0.05, replan_threshold=0.25,
                  window=4, patience=2, min_batches=2,
                  replace_cooldown=2, replan_cooldown=4)


def _uniform():
    return np.full(K, 100.0)


def _shifted(frac):
    """Move `frac` of the total mass from the first half cells to the last."""
    loads = np.full(K, 100.0)
    move = frac * loads.sum() / (K // 2)
    loads[: K // 2] -= move
    loads[K // 2:] += move
    return loads


def _drive(det, load_seq):
    """Feed loads one batch at a time, acting+rebaselining like the engine."""
    actions = []
    for loads in load_seq:
        det.observe_loads(loads)
        action = det.assess()
        if action != "stable":
            actions.append((det.batches, action))
            det.rebaseline(det.observed_cell_loads(), action=action)
    return actions


def test_stable_load_never_acts():
    det = DriftDetector(_uniform(), POL)
    rng = np.random.default_rng(0)
    seq = [_uniform() + rng.normal(0, 0.5, K) for _ in range(30)]
    assert _drive(det, seq) == []
    assert det.drift() < POL.replace_threshold


def test_gradual_drift_replaces_before_replanning():
    det = DriftDetector(_uniform(), POL)
    # ramp: each batch shifts a little more; crosses the replace threshold
    # long before the replan one
    seq = [_shifted(min(0.02 * i, 0.15)) for i in range(1, 25)]
    actions = _drive(det, seq)
    assert actions, "gradual drift must eventually act"
    assert actions[0][1] == "replace"
    assert all(a == "replace" for _, a in actions)


def test_step_shift_replans_without_thrash():
    det = DriftDetector(_uniform(), POL)
    seq = [_uniform()] * 4 + [_shifted(0.8)] * 30
    actions = _drive(det, seq)
    replans = [b for b, a in actions if a == "replan"]
    # graded escalation: the window dilutes the step at first, so a cheap
    # replace may fire before the replan arm reaches patience — but the
    # replan fires exactly once and the stream then reads stable.
    assert len(replans) == 1, f"expected exactly one replan, got {actions}"
    assert len(actions) <= 3, f"action thrash: {actions}"
    assert det._replan_streak == 0
    assert det.drift() < POL.replace_threshold


def test_moderate_step_heals_with_replaces_only():
    det = DriftDetector(_uniform(), POL)
    seq = [_uniform()] * 4 + [_shifted(0.45)] * 30
    actions = _drive(det, seq)
    assert actions and all(a == "replace" for _, a in actions)
    assert len(actions) <= 3
    assert det.drift() < POL.replace_threshold


def test_oscillating_load_is_ignored_by_patience():
    det = DriftDetector(_uniform(), POL)
    seq = [_shifted(0.45) if i % 2 else _uniform() for i in range(30)]
    # alternating batches never sustain `patience` consecutive drifted
    # WINDOWS: the window mixes both phases, keeping TV below the replan
    # threshold, and any lone replace rebaselines onto the mixture.
    actions = _drive(det, seq)
    assert all(a == "replace" for _, a in actions)
    assert len(actions) <= 2


def test_min_batches_suppresses_early_decisions():
    det = DriftDetector(_uniform(), AdaptPolicy(
        replace_threshold=0.01, replan_threshold=0.5, window=4,
        patience=1, min_batches=3))
    det.observe_loads(_shifted(0.3))
    assert det.assess() == "stable"
    det.observe_loads(_shifted(0.3))
    assert det.assess() == "stable"
    det.observe_loads(_shifted(0.3))
    assert det.assess() == "replace"


def test_cooldown_bounds_action_frequency():
    pol = AdaptPolicy(replace_threshold=0.01, replan_threshold=0.9,
                      window=2, patience=1, min_batches=1,
                      replace_cooldown=5)
    det = DriftDetector(_uniform(), pol)
    acted = []
    for i in range(20):
        det.observe_loads(_shifted(0.2 + 0.02 * (i % 7)))   # keeps drifting
        if det.assess() == "replace":
            acted.append(det.batches)
            # rebaseline to the ORIGINAL expectation so drift persists
            det.rebaseline(_uniform(), action="replace")
    assert acted
    assert all(b - a >= pol.replace_cooldown for a, b in zip(acted, acted[1:]))


def test_new_heavy_hitter_forces_replan_arm():
    pol = AdaptPolicy(replace_threshold=0.05, replan_threshold=0.9,
                      window=4, patience=1, min_batches=1,
                      sketch_counters=32)
    det = DriftDetector(_uniform(), pol, attrs=("B",), hh_frac=0.1,
                        known_hhs={"B": (7,)})
    # loads stay EXACTLY at baseline: TV = 0, so only the HH arm can fire
    det.observe_loads(_uniform())
    det.observe_values({"B": {"R": np.array([7] * 50 + [1, 2, 3])}})
    assert det.assess() == "stable"          # 7 is already known
    det.observe_loads(_uniform())
    det.observe_values({"B": {"R": np.array([9] * 80 + [1, 2])}})
    assert det.new_heavy_hitters()["B"] == (9,)
    assert det.assess() == "replan"
    det.rebaseline(_uniform(), action="replan", known_hhs={"B": (7, 9)})
    assert det.sketches["B"] == {}           # replan resets the sketches
    det.observe_loads(_uniform())
    det.observe_values({"B": {"R": np.array([9] * 80)}})
    assert det.assess() == "stable"          # 9 is known now


def test_observe_loads_accepts_count_matrices():
    det = DriftDetector(_uniform(), POL)
    mats = np.ones((3, K))
    det.observe_loads(mats)
    np.testing.assert_array_equal(det.observed_cell_loads(), np.full(K, 3.0))
    with pytest.raises(ValueError, match="incompatible"):
        det.observe_loads(np.ones(K + 1))


def test_rebaseline_guards():
    det = DriftDetector(_uniform(), POL)
    with pytest.raises(ValueError, match="unknown rebaseline action"):
        det.rebaseline(_uniform(), action="panic")
    with pytest.raises(ValueError, match="size"):
        det.rebaseline(np.ones(K + 2), action="replace")


def test_sketched_hhs_match_exact_detector_on_pinned_stream():
    """With m ≥ distinct values the sketch is exact and the estimate-threshold
    rule reproduces `exact_heavy_hitters` bit-for-bit."""
    q = two_way()
    k = 32
    batch = drifting_join_batch(q, 1024, 128, 100, [3, 4], 20, seed=5)
    det = DriftDetector(np.ones(k), AdaptPolicy(sketch_counters=256),
                        attrs=("B",), hh_frac=1.0 / k)
    det.observe_values(
        {"B": {r.name: batch[r.name][:, r.attrs.index("B")]
               for r in q.relations}})
    exact = exact_heavy_hitters(batch, q, k)
    assert det.sketched_hhs().per_attr == dict(exact.per_attr)


# ---------------------------------------------------------------------------
# End-to-end: the adaptation axis on a live session.
# ---------------------------------------------------------------------------

e2e = pytest.mark.skipif(len(jax.devices()) < 8,
                         reason="needs 8 virtual devices")

N_DEV = 8
N, HH_ROWS, DOM, K_PLAN = 1024, 128, 128, 32
NHOT, BONUS = 6, 24
E2E_POL = AdaptPolicy(replace_threshold=0.02, replan_threshold=0.07,
                      window=4, patience=2, min_batches=2,
                      replace_cooldown=2, replan_cooldown=4,
                      sketch_counters=64)


def _mesh():
    from jax.sharding import AxisType
    return jax.make_mesh((N_DEV,), ("cells",), axis_types=(AxisType.Auto,))


def _hot_sets(plan):
    """Hot tail values grouped by the cell slice they route to, so moving
    the hot set provably moves cell load (hash collisions can otherwise
    cancel the drift)."""
    from collections import defaultdict
    vals = np.arange(2, DOM + 2, dtype=np.int64)
    arr = np.stack([np.zeros_like(vals), vals], axis=1)
    ridx, dest = plan.route_relation("R", arr)
    per_val = defaultdict(set)
    for r, d in zip(ridx, dest):
        per_val[int(vals[r])].add(int(d))
    by_slice = defaultdict(list)
    for v, ds in sorted(per_val.items()):
        by_slice[tuple(sorted(ds))].append(v - 2)
    slices = [vs for _, vs in sorted(by_slice.items())]
    hot_a = [vs[0] for vs in slices[:NHOT]]
    hot_b = [vs[0] for vs in slices[-NHOT:]]
    return hot_a, hot_b


def _setup(adapt=E2E_POL):
    q = two_way()
    base = drifting_join_batch(q, N, HH_ROWS, DOM, [], 0, seed=0)
    plan = plan_skew_join(q, base, K_PLAN)
    assert dict(plan.hhs.per_attr) == {"B": (0,)}
    hot_a, hot_b = _hot_sets(plan)
    data0 = drifting_join_batch(q, N, HH_ROWS, DOM, hot_a, BONUS, seed=1)
    ex = ShardedJoinExecutor(plan_skew_join(q, data0, K_PLAN), _mesh(),
                             config=ExecutorConfig(out_capacity=65536))
    eng = SelfHealingSession(ex, adapt=adapt).prepare(data0)
    return q, eng, ex, hot_a, hot_b


def _run_exact(q, eng, batch):
    res = eng.run_batch(batch)
    got = res["rows"][res["valid"]]
    np.testing.assert_array_equal(canonical(got), reference_join(q, batch))
    return res


@e2e
def test_e2e_stable_stream_never_adapts():
    q, eng, ex, hot_a, _ = _setup()
    for i in range(6):
        _run_exact(q, eng, drifting_join_batch(q, N, HH_ROWS, DOM, hot_a,
                                               BONUS, seed=10 + i))
    st = eng.stats
    assert st["replacements"] == 0 and st["replans"] == 0
    assert st["replace_compiles"] == 0 and st["replan_compiles"] == 0


@e2e
def test_e2e_mild_drift_organic_replacement_zero_compiles():
    q, eng, ex, hot_a, hot_b = _setup()
    for i in range(3):
        _run_exact(q, eng, drifting_join_batch(q, N, HH_ROWS, DOM, hot_a,
                                               BONUS, seed=20 + i))
    warm_compiles = ex.compile_count
    table_before = eng.session.placement.table.copy()
    hot_mild = hot_a[:-2] + hot_b[:2]
    for i in range(6):
        _run_exact(q, eng, drifting_join_batch(q, N, HH_ROWS, DOM, hot_mild,
                                               BONUS, seed=30 + i))
    st = eng.stats
    assert st["replacements"] >= 1, "mild drift must trigger a re-placement"
    assert st["replans"] == 0, "mild drift must NOT re-plan"
    assert st["replace_compiles"] == 0
    assert ex.compile_count == warm_compiles, "re-placement recompiled"
    assert not np.array_equal(eng.session.placement.table, table_before), \
        "re-placement did not change the fold"


@e2e
def test_e2e_step_drift_organic_replan_lands_warm():
    # replan threshold sits below HALF the full step's TV (~0.10): the
    # window dilutes a fresh step by ~2x, and the post-replace residual must
    # still clear the threshold for the replan arm to reach patience.
    pol = AdaptPolicy(replace_threshold=0.015, replan_threshold=0.04,
                      window=4, patience=2, min_batches=2,
                      replace_cooldown=2, replan_cooldown=4,
                      sketch_counters=64)
    q, eng, ex, hot_a, hot_b = _setup(adapt=pol)
    for i in range(3):
        _run_exact(q, eng, drifting_join_batch(q, N, HH_ROWS, DOM, hot_a,
                                               BONUS, seed=40 + i))
    warm_compiles = ex.compile_count
    for i in range(6):
        _run_exact(q, eng, drifting_join_batch(q, N, HH_ROWS, DOM, hot_b,
                                               BONUS, seed=50 + i))
    st = eng.stats
    assert st["replans"] >= 1, "step drift must trigger a re-plan"
    assert st["replan_compiles"] == 0, "same-structure re-plan recompiled"
    assert eng.executor is ex, "plan cache missed on identical structure"
    assert ex.compile_count == warm_compiles
    assert st["replans"] <= 2, f"replan thrash: {eng.detector.history}"
    assert st["batches"] == 9                 # retired counters folded in


@e2e
def test_e2e_new_heavy_hitter_cold_replan_is_honest_and_exact():
    q, eng, ex, hot_a, hot_b = _setup()
    for i in range(3):
        _run_exact(q, eng, drifting_join_batch(q, N, HH_ROWS, DOM, hot_a,
                                               BONUS, seed=60 + i))
    # value 1 becomes a genuine second heavy hitter (sketch-provable)
    for i in range(4):
        _run_exact(q, eng, drifting_join_batch(
            q, N, HH_ROWS, DOM, hot_a, BONUS, seed=70 + i,
            extra_hh={"B": 256}))
        if eng.replans:
            break
    st = eng.stats
    assert st["replans"] >= 1, "provable new HH must force a re-plan"
    assert eng.executor is not ex, "new HH set must build a new plan"
    assert "1" in str(
        {a: eng.executor.plan.hhs.values(a) for a in ("B",)}), \
        f"new plan missed the promoted HH: {eng.executor.plan.hhs.per_attr}"
    assert st["replan_compiles"] >= 1, \
        "a structurally new plan must count its compile"
    # and the adapted session keeps delivering exact results
    _run_exact(q, eng, drifting_join_batch(q, N, HH_ROWS, DOM, hot_a, BONUS,
                                           seed=80, extra_hh={"B": 256}))


@e2e
def test_e2e_forced_actions_warm_and_stats_cumulative():
    q, eng, ex, hot_a, _ = _setup()
    for i in range(2):
        _run_exact(q, eng, drifting_join_batch(q, N, HH_ROWS, DOM, hot_a,
                                               BONUS, seed=90 + i))
    warm_compiles = ex.compile_count
    eng.force_replace()
    _run_exact(q, eng, drifting_join_batch(q, N, HH_ROWS, DOM, hot_a,
                                           BONUS, seed=92))
    eng.force_replan()
    _run_exact(q, eng, drifting_join_batch(q, N, HH_ROWS, DOM, hot_a,
                                           BONUS, seed=93))
    st = eng.stats
    assert st["replacements"] == 1 and st["replans"] == 1
    assert st["replace_compiles"] == 0 and st["replan_compiles"] == 0
    assert ex.compile_count == warm_compiles
    assert eng.executor is ex                 # plan cache hit
    assert st["batches"] == 4                 # merged across the replan


@e2e
@pytest.mark.parametrize("scenario", ["mild_drift", "step_drift"])
def test_e2e_adaptive_makespan_beats_static(scenario):
    """After the hot set moves, the adaptive session's busiest device
    receives fewer rows than a static session's on the same executor
    (makespan: the largest per-device `recv_counts`, averaged over the last
    5 of 10 post-shift batches); every batch of both stays exact.  Mild
    drift heals by re-placement alone, a step shift re-plans."""
    replan = 0.5 if scenario == "mild_drift" else 0.04
    pol = AdaptPolicy(replace_threshold=0.015, replan_threshold=replan,
                      window=4, patience=2, min_batches=2,
                      replace_cooldown=2, replan_cooldown=4)
    q, adaptive, ex, hot_a, hot_b = _setup(adapt=pol)
    static = SelfHealingSession(ex).prepare(
        drifting_join_batch(q, N, HH_ROWS, DOM, hot_a, BONUS, seed=1))
    hot_post = hot_a[:-2] + hot_b[:2] if scenario == "mild_drift" else hot_b
    batches = ([drifting_join_batch(q, N, HH_ROWS, DOM, hot_a, BONUS,
                                    seed=100 + i) for i in range(4)]
               + [drifting_join_batch(q, N, HH_ROWS, DOM, hot_post, BONUS,
                                      seed=200 + i) for i in range(10)])
    span_a, span_s = [], []
    for batch in batches:
        span_a.append(int(_run_exact(q, adaptive, batch)["recv_counts"].max()))
        span_s.append(int(_run_exact(q, static, batch)["recv_counts"].max()))
    st = adaptive.stats
    assert st["replacements"] >= 1
    assert (st["replans"] == 0) if scenario == "mild_drift" \
        else (st["replans"] >= 1)
    assert st["replace_compiles"] == 0 and st["replan_compiles"] == 0
    assert np.mean(span_a[-5:]) < np.mean(span_s[-5:]), (span_a, span_s)

