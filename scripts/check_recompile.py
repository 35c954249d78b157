"""CI recompilation guard (scripts/ci.sh) — the warm-path contract.

A second `ExecutorSession.run_batch` on same-shaped input must NOT trigger a
new jit compile: one step build per (shapes, capacities) signature, one entry
in the traced function's own cache.  Asserted two ways:

  * `ShardedJoinExecutor.compile_count` — step builds (cache misses) stay at 1
    across repeat run_batch calls, including fresh same-shaped chunks and a
    second session over the same executor;
  * the compiled step's `_cache_size()` — jax's traced-call counter for the
    cached executable stays at 1 (no retrace, hence no recompile);
  * retry-within-a-bucket — an overflow-retry escalation ladder the executor
    has already walked (same shapes, same start caps on the capacity-bucket
    grid) compiles ZERO new executables when a second session walks it again
    (the self-healing contract: retries are warm, not recompiles);
  * adapt-warm — a drift-triggered re-placement (placement is a traced
    argument) and a re-plan whose pinned workload hits the executor plan
    cache compile ZERO new steps (the online-adaptation contract:
    `replace_compiles == replan_compiles == 0`).

Exit 1 on any violation.  Usage:  python scripts/check_recompile.py
"""
from __future__ import annotations

import os
import sys

# The executor needs the 8-device virtual mesh; must precede the jax import.
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8").strip()

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "src"))


def main() -> int:
    from repro.core import plan_skew_join, two_way
    from repro.core.executor import ExecutorConfig, ShardedJoinExecutor
    from repro.data import skewed_join_dataset
    import jax
    from jax.sharding import AxisType

    mesh = jax.make_mesh((len(jax.devices()),), ("cells",),
                         axis_types=(AxisType.Auto,))
    q = two_way()
    data = skewed_join_dataset(q, 600, 60, skew={"B": 1.5}, seed=31)
    plan = plan_skew_join(q, data, 8)
    ex = ShardedJoinExecutor(plan, mesh,
                             config=ExecutorConfig(out_capacity=16384))

    session = ex.session().prepare(data)
    session.run_batch()
    failures: list[str] = []
    if ex.compile_count != 1 or len(ex._step_cache) != 1:
        print(f"RECOMPILE GUARD FAILED:\n  first run_batch built "
              f"{ex.compile_count} steps, cached {len(ex._step_cache)} "
              f"(want 1)", file=sys.stderr)
        return 1
    (step,) = ex._step_cache.values()
    # _cache_size is a private jax counter that may not survive upgrades; the
    # public compile_count assertion above is the hard gate either way.
    cache_size = getattr(step, "_cache_size", None)
    cold_traces = cache_size() if cache_size else None

    session.run_batch()                  # warm: prepared device arrays
    session.run_batch(data)              # warm: fresh same-shaped chunks
    ex.session().prepare(data).run_batch()   # second session, same signature
    if ex.compile_count != 1:
        failures.append(
            f"same-shaped run_batch recompiled: {ex.compile_count} step builds")
    if cache_size and (cache_size() != cold_traces or cache_size() != 1):
        failures.append(
            f"traced-fn cache grew: {cold_traces} -> {cache_size()} "
            f"(want a single cached executable)")

    # Retry ladder warmth: two sessions start from the SAME explicit tiny
    # caps (on the bucket grid) and escalate through run_with_retry.  The
    # first walk compiles one step per rung; the second must compile none.
    probe = ex.session().prepare(data)
    tiny = {r.name: max(2, session.caps[r.name] // 8)
            for r in q.relations}

    def walk():
        s = ex.session().prepare(data, caps=dict(tiny),
                                 placement=probe.placement)
        s.run_with_retry()
        return s.stats["retries"]

    retries_first = walk()
    builds_after_first = ex.compile_count
    retries_second = walk()
    if retries_first < 1:
        failures.append("retry-ladder scenario never overflowed "
                        "(tiny caps failed to force a retry)")
    if retries_second != retries_first:
        failures.append(
            f"retry ladder not deterministic: {retries_first} then "
            f"{retries_second} retries from identical start caps")
    if ex.compile_count != builds_after_first:
        failures.append(
            f"retry-within-a-bucket recompiled: second ladder walk built "
            f"{ex.compile_count - builds_after_first} new steps (want 0)")

    # Adapt warmth: forced re-placement swaps the traced placement table, and
    # a forced re-plan over the SAME pinned data hits the plan cache (same
    # HH set, same per-combination counts -> same route specs) — neither may
    # build a step.
    from repro.core.adapt import AdaptPolicy
    from repro.data import drifting_join_batch
    from repro.serve import SelfHealingSession

    adata = drifting_join_batch(q, 512, 64, 64, [3, 7], 16, seed=5)
    aplan = plan_skew_join(q, adata, 16)
    aex = ShardedJoinExecutor(aplan, mesh,
                              config=ExecutorConfig(out_capacity=32768))
    eng = SelfHealingSession(aex, adapt=AdaptPolicy()).prepare(adata)
    eng.run_batch()
    builds_warm = aex.compile_count
    eng.force_replace()
    eng.run_batch()
    eng.force_replan()
    eng.run_batch()
    st = eng.stats
    if st["replacements"] != 1 or st["replans"] != 1:
        failures.append(
            f"adapt scenario did not act: replacements={st['replacements']} "
            f"replans={st['replans']} (want 1 each)")
    if st["replace_compiles"] != 0:
        failures.append(
            f"drift re-placement recompiled: {st['replace_compiles']} step "
            f"builds (placement must be a traced argument)")
    if st["replan_compiles"] != 0:
        failures.append(
            f"same-plan re-plan recompiled: {st['replan_compiles']} step "
            f"builds (the executor plan cache regressed)")
    if aex.compile_count != builds_warm:
        failures.append(
            f"adapt scenario built {aex.compile_count - builds_warm} new "
            f"steps after the warm batch (want 0)")

    if failures:
        print("RECOMPILE GUARD FAILED:", file=sys.stderr)
        for f in failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    traces = cache_size() if cache_size else "untracked"
    print(f"# recompile guard ok (1 step build, {traces} cached trace "
          f"across 4 warm calls; retry ladder of {retries_first} retries "
          f"warm on the second walk; adapt re-place + re-plan warm)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
