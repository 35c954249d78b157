"""Benchmark harness — one function per paper table/claim.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = wall time of the
measured operation; derived = the table's headline quantity).

Tables:
  two_way_cost        Example 1.1 vs 1.2: naive r+ks vs Shares 2√(krs), k sweep
  skew_balance        zipf-α sweep: max reducer load, naive vs SkewShares plan
  residual_decomp     running example (§3/§5): per-residual cost expressions
  moe_dispatch        hot-expert imbalance: classical EP vs SkewShares slots
  executor_e2e        end-to-end distributed join on the virtual mesh
  reduce_scaling      sort-merge vs dense-matrix local join, fragment-size sweep
  shuffle_scaling     radix bucket_pack vs the superseded one-hot/argsort packs
                      over k, plus cold-vs-warm ExecutorSession latency; also
                      emits machine-readable BENCH_shuffle.json at the repo root
  fold_scaling        logical-cell folding: k >> devices plans on the 8-device
                      mesh, LPT vs modulo placement max/mean device load on a
                      zipf-skewed workload; emits BENCH_fold.json
  map_scaling         fused map_pack megakernel vs the staged
                      route->fold->pack path, plus counting mode vs the
                      staged count matrices and the prepare()
                      routes-data-once guarantee; emits BENCH_map.json
  reduce_v2           join_probe radix hash join vs the sort-merge cascade:
                      fragment size x cascade depth (3-way / 4-way chain) x
                      zipf skew, bit-identity asserted against both oracles;
                      emits BENCH_reduce.json
  recover_scaling     self-healing sessions under injected faults (ft/chaos):
                      capacity overflow -> bounded bucket-aligned retry,
                      device loss -> survivor re-fold, straggler -> eviction;
                      recovery must be bit-exact and retries/re-folds must
                      compile zero new executables; emits BENCH_recover.json
  adapt_scaling       online skew adaptation on a drifting stream: mild
                      drift -> drift-triggered re-placement (traced table,
                      zero recompile), step drift -> sketch-driven warm
                      re-plan; adaptive vs static makespan post-shift must
                      improve and stay bit-exact; emits BENCH_adapt.json
  shuffle_overlap     chunked map<->all_to_all pipeline: the SAME plan runs
                      serial (C=1) and overlapped (C in {2,4}); warm batch
                      latency, bit-exactness vs reference_join, zero warm
                      recompiles across chunk counts; emits BENCH_overlap.json
  serve_scaling       multi-tenant join serving: the mixed_workload stream
                      (three structurally distinct queries x two size
                      buckets) through one JoinServingEngine; steady-state
                      queries/sec, p50/p99 latency, cache hit rate, zero
                      recompiles, every request bit-exact; emits
                      BENCH_serve.json
  kernel_throughput   hash_partition / match_counts / segment_histogram
  planner_latency     plan_skew_join wall time vs #HH (control-plane budget)

Run `python benchmarks/run.py --list` for table names and `--only PREFIX`
to run a subset (CI's smoke step does).
"""
import json
import os

# The executor benchmark needs a small multi-device mesh (8, NOT the dry-run's
# 512 — that flag belongs to launch/dryrun.py alone).  Must precede jax import.
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8").strip()

import time

import numpy as np

ROWS: list[tuple[str, float, str]] = []


def row(name: str, us: float, derived: str) -> None:
    ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived}", flush=True)


def _timeit(fn, reps=3):
    fn()  # warmup / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    return (time.perf_counter() - t0) / reps * 1e6, out


# ---------------------------------------------------------------------------

def bench_two_way_cost():
    """Paper Examples 1.1/1.2: the headline communication-cost comparison."""
    from repro.core import (naive_hh_cost, optimize_shares, shares_hh_cost,
                            two_way)
    r, s = 10**7, 10**5
    for k in (16, 64, 256, 1024, 4096):
        q = two_way(r, s)
        us, sol = _timeit(lambda: optimize_shares(q, k, frozen=frozenset({"B"})))
        naive = naive_hh_cost(r, s, k)
        opt = shares_hh_cost(r, s, k)
        row(f"two_way_cost/k={k}", us,
            f"naive={naive:.3e};shares_cont={opt:.3e};"
            f"shares_int={sol.cost:.3e};speedup={naive/sol.cost:.2f}x")


def bench_skew_balance():
    """Max reducer load under zipf skew: plain Shares vs SkewShares."""
    from repro.core import plan_no_skew, plan_skew_join, two_way
    from repro.data import skewed_join_dataset
    k, n = 64, 40_000
    for alpha in (0.0, 0.8, 1.2, 1.6, 2.0):
        q = two_way()
        data = skewed_join_dataset(q, n, 1000, skew={"B": alpha}, seed=1)
        us, plan = _timeit(lambda: plan_skew_join(q, data, k), reps=1)
        l_skew = plan.reducer_loads(data)
        l_flat = plan_no_skew(q, data, k).reducer_loads(data)
        row(f"skew_balance/alpha={alpha}", us,
            f"max_naive={l_flat.max()};max_shares={l_skew.max()};"
            f"imbalance_naive={l_flat.max()/max(l_flat.mean(),1):.1f};"
            f"imbalance_shares={l_skew.max()/max(l_skew.mean(),1):.1f};"
            f"hh={plan.hhs.total()};residuals={len(plan.residuals)}")


def bench_residual_decomp():
    """Running example §3/§5: the six residual joins and their plans."""
    from repro.core import plan_skew_join, running_example
    from repro.data import skewed_join_dataset
    q = running_example()
    data = skewed_join_dataset(q, 20_000, 400, skew={"B": 1.6, "C": 1.3}, seed=2)
    us, plan = _timeit(lambda: plan_skew_join(q, data, 256, max_hh_per_attr=2),
                       reps=1)
    for rp in plan.residuals:
        shares = "x".join(f"{a}:{s}" for a, s in
                          zip(rp.cube.attr_order, rp.cube.shares))
        row(f"residual/{rp.residual.combo}", us / len(plan.residuals),
            f"expr={rp.residual.expr};k_i={rp.k_i};shares={shares or '1'};"
            f"cost={rp.cost:.3e}")
    row("residual/total", us,
        f"total_cost={plan.total_cost:.3e};reducers={plan.reducers_used}")


def bench_moe_dispatch():
    """MoE expert dispatch: classical one-owner EP vs SkewShares replication."""
    from repro.core.moe_shares import dispatch_cost, plan_dispatch
    rng = np.random.default_rng(0)
    E = 64
    for hot_frac in (0.1, 0.3, 0.5):
        loads = rng.uniform(50, 150, E)
        total = loads.sum() / (1 - hot_frac)
        loads[0] = total * hot_frac          # one expert takes hot_frac of tokens
        us, skew = _timeit(lambda: plan_dispatch(loads, int(E * 1.25)))
        classical = plan_dispatch(loads, E)  # no spare slots -> g=1 everywhere
        c = dispatch_cost(loads, classical, weight_cost=1e4)
        s = dispatch_cost(loads, skew, weight_cost=1e4)
        row(f"moe_dispatch/hot={hot_frac}", us,
            f"max_classical={c['max_slot_load']:.0f};"
            f"max_shares={s['max_slot_load']:.0f};"
            f"straggle_reduction={c['max_slot_load']/s['max_slot_load']:.2f}x;"
            f"replicas={int(skew.group_size.max())}")


def bench_executor_e2e():
    """End-to-end distributed skewed join on the virtual device mesh."""
    import jax
    if len(jax.devices()) < 8:
        row("executor_e2e/skipped", 0.0, "needs 8 devices")
        return
    from repro.core import canonical, plan_skew_join, reference_join, two_way
    from repro.core.executor import ExecutorConfig, ShardedJoinExecutor
    from repro.data import skewed_join_dataset
    from jax.sharding import AxisType
    mesh = jax.make_mesh((8,), ("cells",), axis_types=(AxisType.Auto,))
    q = two_way()
    data = skewed_join_dataset(q, 3_000, 3_000, skew={"B": 1.0}, seed=3)
    plan = plan_skew_join(q, data, 8)
    ex = ShardedJoinExecutor(plan, mesh,
                             config=ExecutorConfig(out_capacity=131072))
    us, res = _timeit(lambda: ex.run(data), reps=1)
    got = res["rows"][res["valid"]]
    expect = reference_join(q, data)
    n_out, n_ref = len(got), len(expect)
    # Content exactness, not just row counts — the gate scripts rely on this.
    exact = n_out == n_ref and bool((canonical(got) == expect).all())
    recv = res["recv_counts"].astype(float)
    row("executor_e2e/two_way_3k", us,
        f"out_rows={n_out};ref_rows={n_ref};exact={exact};"
        f"recv_imbalance={recv.max()/max(recv.mean(),1):.2f};"
        f"shuffle_overflow={int(res['shuffle_overflow'].sum())};"
        f"join_overflow={int(res['join_overflow'].sum())}")


def bench_reduce_scaling():
    """Reduce-phase local join: O(n²) dense match matrix vs sort-merge.

    Sweeps per-cell fragment sizes and times both implementations on identical
    fragments; `exact` asserts the sort-merge output is bit-identical to the
    dense baseline.  Sort-merge wins at every swept size and the gap widens
    with n (measured ~34x at 1k rows to ~544x at 16k on the CPU container) —
    the n² -> n·log n claim of the executor rewrite.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import two_way
    from repro.core.executor import _local_join, _local_join_dense
    q = two_way()
    for n in (1024, 4096, 8192, 16384):
        rng = np.random.default_rng(n)
        dom = max(n // 2, 1)                      # ~2 matches per left row
        cap = 8 * n
        frags = {
            "R": jnp.asarray(np.stack(
                [rng.integers(0, 1000, n), rng.integers(0, dom, n),
                 np.zeros(n, np.int64)], axis=1), jnp.int32),
            "S": jnp.asarray(np.stack(
                [rng.integers(0, dom, n), rng.integers(0, 1000, n),
                 np.zeros(n, np.int64)], axis=1), jnp.int32),
        }
        reps = 3 if n <= 4096 else 1
        f_sort = jax.jit(lambda fr: _local_join(fr, q, cap, False))
        f_dense = jax.jit(lambda fr: _local_join_dense(fr, q, cap))
        us_s, out_s = _timeit(lambda: jax.block_until_ready(f_sort(frags)),
                              reps=reps)
        us_d, out_d = _timeit(lambda: jax.block_until_ready(f_dense(frags)),
                              reps=reps)
        exact = (bool((np.asarray(out_s[0]) == np.asarray(out_d[0])).all())
                 and bool((np.asarray(out_s[1]) == np.asarray(out_d[1])).all()))
        row(f"reduce_scaling/n={n}", us_s,
            f"dense_us={us_d:.1f};speedup={us_d / max(us_s, 1e-9):.2f}x;"
            f"out_rows={int(np.asarray(out_s[1]).sum())};exact={exact};"
            f"overflow={int(out_s[2])}")


def bench_shuffle_scaling():
    """Map-phase shuffle pack + session warm-up — the PR-2 headline table.

    Pack throughput vs k: the radix `bucket_pack` hot path against BOTH
    superseded implementations — the O(m·k) one-hot counting sort that was
    `_pack_buckets` (surviving as `bucket_pack_ref`, the kernel's oracle) and
    the O(m log m) argsort fallback it dispatched to at k > 32 — asserting
    bit-identical buffers.  Then cold-vs-warm `ExecutorSession.run_batch`
    latency: cold = prepare + first call (capacity pass + compile), warm =
    same-shaped calls through the cached executable.  Emits
    BENCH_shuffle.json for scripts/check_bench.py."""
    import jax
    import jax.numpy as jnp
    from repro.core.executor import _pack_buckets_argsort
    from repro.kernels import ops as kops
    from repro.kernels.ref import bucket_pack_ref

    report = {"m": 1 << 16, "pack": [], "session": None}
    m, w = report["m"], 4
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.integers(0, 10_000, (m, w)), jnp.int32)

    def best_of(fn, reps=5):
        """Min over reps — the noise-robust estimator this shared container
        needs (the mean-based `_timeit` swings 2-3x under load)."""
        out = fn()     # warmup / compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best * 1e6, out

    for k in (8, 32, 64, 128, 256, 512):
        cap = max(2 * m // k, 4)
        dest = jnp.asarray(rng.integers(-1, k, m), jnp.int32)
        f_new = jax.jit(lambda d, r, k=k, cap=cap: kops.bucket_pack(d, r, k, cap))
        f_arg = jax.jit(lambda d, r, k=k, cap=cap: _pack_buckets_argsort(d, r, k, cap))
        f_hot = jax.jit(lambda d, r, k=k, cap=cap: bucket_pack_ref(d, r, k, cap))
        us_new, out_new = best_of(lambda: jax.block_until_ready(f_new(dest, rows)))
        us_arg, out_arg = best_of(lambda: jax.block_until_ready(f_arg(dest, rows)))
        us_hot, out_hot = best_of(lambda: jax.block_until_ready(f_hot(dest, rows)))
        exact = (bool((np.asarray(out_new[0]) == np.asarray(out_arg[0])).all())
                 and bool((np.asarray(out_new[0]) == np.asarray(out_hot[0])).all())
                 and int(out_new[1]) == int(out_arg[1]) == int(out_hot[1]))
        entry = {"k": k, "radix_us": us_new, "onehot_us": us_hot,
                 "argsort_us": us_arg,
                 "speedup_vs_onehot": us_hot / max(us_new, 1e-9),
                 "speedup_vs_argsort": us_arg / max(us_new, 1e-9),
                 "exact": exact, "overflow": int(out_new[1])}
        report["pack"].append(entry)
        row(f"shuffle_scaling/k={k}", us_new,
            f"onehot_us={us_hot:.1f};argsort_us={us_arg:.1f};"
            f"speedup_onehot={entry['speedup_vs_onehot']:.2f}x;"
            f"speedup_argsort={entry['speedup_vs_argsort']:.2f}x;"
            f"exact={exact};overflow={entry['overflow']}")

    if len(jax.devices()) >= 8:
        from repro.core import (canonical, plan_skew_join, reference_join,
                                two_way)
        from repro.core.executor import ExecutorConfig, ShardedJoinExecutor
        from repro.data import skewed_join_dataset
        from jax.sharding import AxisType
        mesh = jax.make_mesh((8,), ("cells",), axis_types=(AxisType.Auto,))
        q = two_way()
        data = skewed_join_dataset(q, 3_000, 3_000, skew={"B": 1.0}, seed=3)
        plan = plan_skew_join(q, data, 8)
        ex = ShardedJoinExecutor(plan, mesh,
                                 config=ExecutorConfig(out_capacity=131072))
        t0 = time.perf_counter()
        session = ex.session().prepare(data)
        res = session.run_batch()
        cold_us = (time.perf_counter() - t0) * 1e6
        warm_us, res_w = _timeit(lambda: session.run_batch(), reps=3)
        got = res_w["rows"][res_w["valid"]]
        expect = reference_join(q, data)
        exact = len(got) == len(expect) and bool((canonical(got) == expect).all())
        report["session"] = {
            "cold_us": cold_us, "warm_us": warm_us,
            "warm_speedup": cold_us / max(warm_us, 1e-9),
            "exact": exact, "step_builds": ex.compile_count,
            "shuffle_overflow": int(res["shuffle_overflow"].sum()),
        }
        row("shuffle_scaling/session", warm_us,
            f"cold_us={cold_us:.1f};warm_speedup={cold_us/max(warm_us,1e-9):.2f}x;"
            f"exact={exact};step_builds={ex.compile_count};"
            f"shuffle_overflow={report['session']['shuffle_overflow']}")
    else:
        row("shuffle_scaling/session_skipped", 0.0, "needs 8 devices")

    out_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_shuffle.json")
    with open(out_path, "w") as fh:
        json.dump(report, fh, indent=2)
    row("shuffle_scaling/json", 0.0, f"path={out_path}")


def bench_fold_scaling():
    """Logical-cell folding: k >> n_devices plans on the small mesh.

    One zipf-skewed two-way workload; for each k in the fold ladder the SAME
    plan executes under LPT and modulo placement on 8 devices.  Exactness is
    asserted against `reference_join` for every (k, strategy) pair, and the
    headline quantity is the max/mean per-device delivered load
    (`recv_counts`): LPT must never exceed modulo's max on this workload —
    scripts/check_bench.py fails the build if it does, or if anything is
    non-exact or overflows.  Emits BENCH_fold.json (schema in
    scripts/check_bench.py)."""
    import jax
    if len(jax.devices()) < 8:
        row("fold_scaling/skipped", 0.0, "needs 8 devices")
        return
    from repro.core import (canonical, lpt_placement, modulo_placement,
                            plan_skew_join, reference_join, two_way)
    from repro.core.executor import ExecutorConfig, ShardedJoinExecutor
    from repro.data import skewed_join_dataset
    from jax.sharding import AxisType

    n_dev = 8
    mesh = jax.make_mesh((n_dev,), ("cells",), axis_types=(AxisType.Auto,))
    q = two_way()
    data = skewed_join_dataset(q, 4_000, 2_000, skew={"B": 1.3}, seed=11)
    expect = reference_join(q, data)
    report = {"n_devices": n_dev, "workload": {
        "query": str(q), "n_per_relation": 4_000, "domain": 2_000,
        "zipf_B": 1.3, "ref_rows": len(expect)}, "fold": []}

    for k in (8, 64, 256):
        plan = plan_skew_join(q, data, k)
        loads = plan.cell_loads(data)
        ex = ShardedJoinExecutor(plan, mesh,
                                 config=ExecutorConfig(out_capacity=1 << 20))
        entry = {"k": k, "hh": plan.hhs.total(),
                 "residuals": len(plan.residuals)}
        for strategy, placement in (
                ("lpt", lpt_placement(loads, n_dev)),
                ("modulo", modulo_placement(k, n_dev))):
            session = ex.session().prepare(data, placement=placement)
            # _timeit's warmup call is the compile; the timed rep is warm.
            us, res = _timeit(lambda: session.run_batch(), reps=1)
            got = res["rows"][res["valid"]]
            exact = (len(got) == len(expect)
                     and bool((canonical(got) == expect).all()))
            recv = res["recv_counts"].astype(float)
            entry[strategy] = {
                "warm_us": us, "exact": exact,
                "max_load": float(recv.max()),
                "mean_load": float(recv.mean()),
                "imbalance": float(recv.max() / max(recv.mean(), 1)),
                "shuffle_overflow": int(res["shuffle_overflow"].sum()),
                "join_overflow": int(res["join_overflow"].sum()),
            }
        entry["lpt_vs_modulo_max"] = (entry["lpt"]["max_load"]
                                      / max(entry["modulo"]["max_load"], 1))
        report["fold"].append(entry)
        row(f"fold_scaling/k={k}", entry["lpt"]["warm_us"],
            f"strategy=lpt;max_load={entry['lpt']['max_load']:.0f};"
            f"mean_load={entry['lpt']['mean_load']:.0f};"
            f"imbalance={entry['lpt']['imbalance']:.2f};"
            f"modulo_max={entry['modulo']['max_load']:.0f};"
            f"modulo_imbalance={entry['modulo']['imbalance']:.2f};"
            f"exact={entry['lpt']['exact'] and entry['modulo']['exact']};"
            f"shuffle_overflow={entry['lpt']['shuffle_overflow'] + entry['modulo']['shuffle_overflow']};"
            f"join_overflow={entry['lpt']['join_overflow'] + entry['modulo']['join_overflow']}")

    out_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_fold.json")
    with open(out_path, "w") as fh:
        json.dump(report, fh, indent=2)
    row("fold_scaling/json", 0.0, f"path={out_path}")


def bench_map_scaling():
    """Fused map_pack megakernel vs the staged route->fold->pack path.

    One zipf-skewed two-way workload at m = 65536 rows; for each k the SAME
    plan's routes run through (a) the staged composition exactly as the
    executor ran it before the megakernel — `_route_relation` (Pallas
    route_cells) -> `_fold_dests` (fold_cells) -> `_pack_buckets` (radix
    pack), materializing the (m·F, w+1) tagged expansion — and (b) the fused
    `kops.map_pack` streaming pass.  Buffers and overflow counts must be
    bit-identical (best-of-5; scripts/check_bench.py fails on any mismatch).
    The counting-mode leg times the scatter-free (n_devices, k) histogram
    against the staged count-matrix formula, and the prepare leg asserts an
    `ExecutorSession.prepare` routes each relation exactly once
    (`count_passes == 1`).  Emits BENCH_map.json."""
    import jax
    import jax.numpy as jnp
    from repro.core import plan_skew_join, two_way
    from repro.core.executor import (_build_routes, _count_matrix,
                                     _fold_dests, _pack_buckets,
                                     _route_relation, _route_specs)
    from repro.core.placement import lpt_placement
    from repro.data import skewed_join_dataset
    from repro.kernels import ops as kops
    from repro.kernels.map_pack import route_fanout

    m, n_dev = 1 << 16, 8
    q = two_way()
    data = skewed_join_dataset(q, m, 4000, skew={"B": 1.2}, seed=9)
    report = {"m": m, "n_devices": n_dev, "map": [], "count": [],
              "prepare": None}

    def best_of(fn, reps=5):
        out = fn()     # warmup / compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best * 1e6, out

    for k in (64, 256):
        plan = plan_skew_join(q, data, k, max_hh_per_attr=2)
        routes = _build_routes(plan)
        placement = lpt_placement(np.asarray(plan.cell_loads(data), float),
                                  n_dev)
        ptable = jnp.asarray(placement.table)
        fold = np.zeros((k, n_dev), np.int64)
        fold[np.arange(k), placement.table] = 1
        rel = "R"
        rows = jnp.asarray(data[rel], jnp.int32)
        spec = _route_specs(routes[rel])
        # Kernel-level pack of the WHOLE array (no shard_map): capacity must
        # cover each destination device's TOTAL folded cell load.
        counts = np.asarray(kops.map_count(rows, spec, k, n_dev), np.int64)
        cap = int(np.ceil(max((counts.sum(axis=0) @ fold).max(), 1) * 1.25))

        def staged(r, rt=routes[rel], c=cap):
            dest, tagged = _route_relation(r, rt, True)
            phys = _fold_dests(dest, ptable, True)
            return _pack_buckets(phys, tagged, n_dev, c, True)

        f_staged = jax.jit(staged)
        f_fused = jax.jit(lambda r, s=spec, c=cap:
                          kops.map_pack(r, s, ptable, k, n_dev, c))
        us_s, out_s = best_of(lambda: jax.block_until_ready(f_staged(rows)))
        us_f, out_f = best_of(lambda: jax.block_until_ready(f_fused(rows)))
        # exact = buffer bit-identity; overflow parity is its own field.
        exact = bool((np.asarray(out_s[0]) == np.asarray(out_f[0])).all())
        entry = {"k": k, "fanout": route_fanout(spec), "cap": cap,
                 "staged_us": us_s, "fused_us": us_f,
                 "speedup": us_s / max(us_f, 1e-9), "exact": exact,
                 "overflow": int(out_f[1]),
                 "overflow_match": int(out_s[1]) == int(out_f[1])}
        report["map"].append(entry)
        row(f"map_scaling/k={k}", us_f,
            f"staged_us={us_s:.1f};fanout={entry['fanout']};"
            f"speedup={entry['speedup']:.2f}x;exact={exact};"
            f"overflow={entry['overflow']};"
            f"overflow_match={entry['overflow_match']}")

        def staged_count(r, rt=routes[rel]):
            dest, _ = _route_relation(r, rt, True)
            return _count_matrix(dest, r.shape[0], k, n_dev)

        f_sc = jax.jit(staged_count)
        f_fc = jax.jit(lambda r, s=spec: kops.map_count(r, s, k, n_dev))
        us_sc, out_sc = best_of(lambda: jax.block_until_ready(f_sc(rows)))
        us_fc, out_fc = best_of(lambda: jax.block_until_ready(f_fc(rows)))
        c_exact = bool((np.asarray(out_sc) == np.asarray(out_fc)).all())
        report["count"].append({
            "k": k, "staged_us": us_sc, "fused_us": us_fc,
            "speedup": us_sc / max(us_fc, 1e-9), "exact": c_exact})
        row(f"map_scaling/count/k={k}", us_fc,
            f"staged_us={us_sc:.1f};speedup={us_sc/max(us_fc,1e-9):.2f}x;"
            f"exact={c_exact}")

    if len(jax.devices()) >= 8:
        from repro.core import canonical, reference_join
        from repro.core.executor import ExecutorConfig, ShardedJoinExecutor
        from jax.sharding import AxisType
        small = skewed_join_dataset(q, 3_000, 3_000, skew={"B": 1.0}, seed=3)
        plan = plan_skew_join(q, small, 64)
        mesh = jax.make_mesh((8,), ("cells",), axis_types=(AxisType.Auto,))
        ex = ShardedJoinExecutor(plan, mesh,
                                 config=ExecutorConfig(out_capacity=131072))
        t0 = time.perf_counter()
        session = ex.session().prepare(small)
        prep_us = (time.perf_counter() - t0) * 1e6
        res = session.run_batch()
        got = res["rows"][res["valid"]]
        expect = reference_join(q, small)
        exact = (len(got) == len(expect)
                 and bool((canonical(got) == expect).all()))
        report["prepare"] = {"prepare_us": prep_us,
                             "count_passes": session.count_passes,
                             "exact": exact}
        row("map_scaling/prepare", prep_us,
            f"count_passes={session.count_passes};exact={exact}")
    else:
        row("map_scaling/prepare_skipped", 0.0, "needs 8 devices")

    out_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_map.json")
    with open(out_path, "w") as fh:
        json.dump(report, fh, indent=2)
    row("map_scaling/json", 0.0, f"path={out_path}")


def bench_reduce_v2():
    """Reduce-phase radix hash join vs the sort-merge cascade — the PR-5
    headline table.

    Sweeps fragment size × cascade depth (3-way and 4-way chain queries) ×
    zipf key skew; for each point the SAME per-cell fragments (tagged with 4
    logical cell ids) run through `_local_join` in hash mode (the
    `join_probe` host twins — the CPU production path) and in sort-merge
    mode (the retained oracle on its fast jnp path), asserting bit-identical
    (rows, valid, overflow) — and bit-identity against the dense-matrix
    ground oracle at n ≤ 4096, where the O(n²) match matrix is still
    tractable.  `cap_out` is sized from the EXACT cascade intermediate sizes
    (reference `join_two` on host), so overflow must be zero.  Emits
    BENCH_reduce.json; scripts/check_bench.py fails the build on any
    non-exactness, overflow, or the hash path losing to sort-merge at
    n ≥ 4096."""
    import jax
    import jax.numpy as jnp
    from repro.core import JoinQuery, Relation, running_example
    from repro.core.executor import _local_join, _local_join_dense
    from repro.core.reference import join_two
    from repro.data.synthetic import zipf_column

    queries = {
        "three_way": running_example(),
        "four_way_chain": JoinQuery((
            Relation("R", ("A", "B")), Relation("S", ("B", "C")),
            Relation("T", ("C", "D")), Relation("U", ("D", "E")))),
    }
    n_cells = 4
    report = {"n_cells": n_cells, "sweep": []}

    def best_of(fn, reps):
        out = fn()     # warmup / compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best * 1e6, out

    for qname, q in queries.items():
        shared = {a for r in q.relations for a in r.attrs
                  if sum(a in r2.attrs for r2 in q.relations) > 1}
        for n in (1024, 4096, 16384):
            for alpha in (0.0, 0.8):
                rng = np.random.default_rng(n + int(10 * alpha)
                                            + len(q.relations))
                frags = {}
                for rel in q.relations:
                    cols = [zipf_column(rng, n, 2 * n if a in shared else 1000,
                                        alpha if a in shared else 0.0)
                            for a in rel.attrs]
                    cols.append(rng.integers(0, n_cells, n))   # logical cell
                    frags[rel.name] = np.stack(cols, axis=1).astype(np.int32)
                # Exact cascade sizes -> a tight cap that cannot overflow.
                acc = frags[q.relations[0].name].astype(np.int64)
                attrs = tuple(q.relations[0].attrs) + ("__cell__",)
                sizes = []
                for rel in q.relations[1:]:
                    acc, attrs = join_two(acc, attrs,
                                          frags[rel.name].astype(np.int64),
                                          tuple(rel.attrs) + ("__cell__",))
                    sizes.append(len(acc))
                cap = max(1024, int(1.25 * max(sizes)))
                jfrags = {k: jnp.asarray(v) for k, v in frags.items()}
                f_hash = jax.jit(
                    lambda fr, c=cap: _local_join(fr, q, c, True, True))
                f_sort = jax.jit(
                    lambda fr, c=cap: _local_join(fr, q, c, False, False))
                # Best-of reps: noise robustness where the win margin is
                # thinnest (small outputs), fewer reps only where a single
                # rep costs seconds (the giant zipf expansions).
                reps = 5 if cap <= (1 << 18) else 3
                us_h, out_h = best_of(
                    lambda: jax.block_until_ready(f_hash(jfrags)), reps)
                us_s, out_s = best_of(
                    lambda: jax.block_until_ready(f_sort(jfrags)), reps)
                exact = (bool((np.asarray(out_h[0])
                               == np.asarray(out_s[0])).all())
                         and bool((np.asarray(out_h[1])
                                   == np.asarray(out_s[1])).all()))
                if n <= 4096:
                    out_d = _local_join_dense(jfrags, q, cap)
                    exact = (exact
                             and bool((np.asarray(out_h[0])
                                       == np.asarray(out_d[0])).all())
                             and bool((np.asarray(out_h[1])
                                       == np.asarray(out_d[1])).all())
                             and int(out_h[2]) == int(out_d[2]))
                entry = {
                    "query": qname, "relations": len(q.relations), "n": n,
                    "alpha": alpha, "cap": cap,
                    "out_rows": int(np.asarray(out_h[1]).sum()),
                    "hash_us": us_h, "sort_us": us_s,
                    "speedup": us_s / max(us_h, 1e-9), "exact": exact,
                    "overflow": int(out_h[2]),
                    "overflow_match": int(out_h[2]) == int(out_s[2]),
                }
                report["sweep"].append(entry)
                row(f"reduce_v2/{qname}/n={n}/alpha={alpha}", us_h,
                    f"sort_us={us_s:.1f};speedup={entry['speedup']:.2f}x;"
                    f"out_rows={entry['out_rows']};cap={cap};exact={exact};"
                    f"overflow={entry['overflow']};"
                    f"overflow_match={entry['overflow_match']}")

    out_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_reduce.json")
    with open(out_path, "w") as fh:
        json.dump(report, fh, indent=2)
    row("reduce_v2/json", 0.0, f"path={out_path}")


def bench_recover_scaling():
    """Self-healing sessions under injected faults — the robustness table.

    Three deterministic chaos scenarios (ft/chaos.py) against the fault-free
    `reference_join` oracle; the gate (scripts/check_bench.py) fails the
    build on any non-exact recovery, a retry count above the policy bound,
    or a retry/re-fold that compiled a new executable:

      overflow_retry   caps squeezed to 30%: `run_with_retry` escalates on
                       the capacity-bucket grid until clean; a second
                       session walking the SAME ladder must compile nothing;
      device_loss      one device stops heartbeating: the virtual clock ages
                       it to FAILED, it is evicted, cells re-fold over the 7
                       survivors (traced placement: zero recompile), it
                       receives zero rows, output bit-exact;
      straggler_evict  one device reports 30 s steps: two strikes and the
                       watchdog evicts it through the same re-fold path.

    Emits BENCH_recover.json."""
    import jax
    if len(jax.devices()) < 8:
        row("recover_scaling/skipped", 0.0, "needs 8 devices")
        return
    from repro.core import canonical, plan_skew_join, reference_join, two_way
    from repro.core.executor import (ExecutorConfig, RetryPolicy,
                                     ShardedJoinExecutor)
    from repro.data import skewed_join_dataset
    from repro.ft import ChaosInjector
    from jax.sharding import AxisType
    from repro.serve import SelfHealingSession

    n_dev = 8
    mesh = jax.make_mesh((n_dev,), ("cells",), axis_types=(AxisType.Auto,))
    q = two_way()
    data = skewed_join_dataset(q, 3_000, 1_500, skew={"B": 1.2}, seed=41)
    expect = reference_join(q, data)
    policy = RetryPolicy()
    report = {"n_devices": n_dev, "workload": {
        "query": str(q), "n_per_relation": 3_000, "domain": 1_500,
        "zipf_B": 1.2, "ref_rows": len(expect)}, "scenarios": {}}

    def _exact(res):
        got = res["rows"][res["valid"]]
        return (len(got) == len(expect)
                and bool((canonical(got) == expect).all()))

    def _executor():
        plan = plan_skew_join(q, data, 32)
        return ShardedJoinExecutor(plan, mesh,
                                   config=ExecutorConfig(out_capacity=1 << 18))

    # -- overflow_retry ------------------------------------------------------
    ex = _executor()

    def healed_walk():
        chaos = ChaosInjector(n_dev, seed=0)
        chaos.squeeze_caps(0.3)
        eng = SelfHealingSession(ex, retry=policy, chaos=chaos).prepare(data)
        t0 = time.perf_counter()
        res = eng.run_batch()
        return (time.perf_counter() - t0) * 1e6, eng, res

    us_first, eng, res = healed_walk()
    builds_after_first = ex.compile_count
    us_second, eng2, res2 = healed_walk()
    baseline = ex.session().prepare(data)       # fault-free caps
    baseline.run_batch()
    us_clean, _ = _timeit(lambda: baseline.run_batch(), reps=3)
    entry = {
        "retries": eng.stats["retries"],
        "retry_bound": policy.max_retries,
        "escalations": eng.stats["escalations"],
        "exact": _exact(res) and _exact(res2),
        "residual_overflow": int(res["shuffle_overflow"].sum()
                                 + res["join_overflow"].sum()),
        "new_compiles_on_retry": ex.compile_count - builds_after_first,
        "healed_us": us_second, "clean_warm_us": us_clean,
        "healing_overhead": us_second / max(us_clean, 1e-9),
    }
    report["scenarios"]["overflow_retry"] = entry
    row("recover_scaling/overflow_retry", us_second,
        f"retries={entry['retries']};bound={entry['retry_bound']};"
        f"exact={entry['exact']};overflow={entry['residual_overflow']};"
        f"new_compiles_on_retry={entry['new_compiles_on_retry']};"
        f"overhead_vs_clean={entry['healing_overhead']:.2f}x")

    # -- device_loss ---------------------------------------------------------
    ex = _executor()
    dead = 3
    chaos = ChaosInjector(n_dev, seed=0)
    chaos.drop_heartbeats(dead)
    eng = SelfHealingSession(ex, chaos=chaos, heartbeat_timeout_s=2.5,
                             suspect_timeout_s=1.5).prepare(data)
    exact = _exact(eng.run_batch())
    batches_to_evict = 1
    while eng.evicted == [] and batches_to_evict < 16:
        exact = exact and _exact(eng.run_batch())
        batches_to_evict += 1
    compiles_before = ex.compile_count
    t0 = time.perf_counter()
    res = eng.run_batch()                       # first degraded-mode batch
    degraded_us = (time.perf_counter() - t0) * 1e6
    entry = {
        "evicted": list(eng.evicted),
        "batches_to_evict": batches_to_evict,
        "refolds": eng.refolds,
        "refold_compiles": eng.refold_compiles,
        "degraded_compiles": ex.compile_count - compiles_before,
        "recv_on_evicted": int(res["recv_counts"][dead]),
        "exact": exact and _exact(res),
        "degraded_us": degraded_us,
    }
    report["scenarios"]["device_loss"] = entry
    row("recover_scaling/device_loss", degraded_us,
        f"evicted={entry['evicted']};batches_to_evict={batches_to_evict};"
        f"refold_compiles={entry['refold_compiles']};"
        f"degraded_compiles={entry['degraded_compiles']};"
        f"recv_on_evicted={entry['recv_on_evicted']};exact={entry['exact']}")

    # -- straggler_evict -----------------------------------------------------
    ex = _executor()
    slow = 5
    chaos = ChaosInjector(n_dev, seed=0)
    chaos.delay_device(slow, 30.0)
    eng = SelfHealingSession(ex, chaos=chaos, straggler_threshold=1.5,
                             evict_after=2).prepare(data)
    exact = True
    batches_to_evict = 0
    while eng.evicted == [] and batches_to_evict < 8:
        exact = exact and _exact(eng.run_batch())
        batches_to_evict += 1
    res = eng.run_batch()
    entry = {
        "evicted": list(eng.evicted),
        "batches_to_evict": batches_to_evict,
        "refolds": eng.refolds,
        "refold_compiles": eng.refold_compiles,
        "recv_on_evicted": int(res["recv_counts"][slow]),
        "exact": exact and _exact(res),
    }
    report["scenarios"]["straggler_evict"] = entry
    row("recover_scaling/straggler_evict", 0.0,
        f"evicted={entry['evicted']};batches_to_evict={batches_to_evict};"
        f"refold_compiles={entry['refold_compiles']};"
        f"recv_on_evicted={entry['recv_on_evicted']};exact={entry['exact']}")

    out_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_recover.json")
    with open(out_path, "w") as fh:
        json.dump(report, fh, indent=2)
    row("recover_scaling/json", 0.0, f"path={out_path}")


def bench_adapt_scaling():
    """Online skew adaptation — the drift table.

    A deterministic drifting stream (data/synthetic.drifting_join_batch: the
    hot tail values move between cell slices mid-stream while the HH set and
    per-combination row counts stay pinned) is run through two sessions over
    the SAME executor: a static `SelfHealingSession` that keeps its phase-A
    LPT placement, and an adaptive one whose `DriftDetector` watches the
    per-batch cell loads.  The gate (scripts/check_bench.py) fails the build
    on any non-exact batch, on an adaptive post-shift makespan that does not
    beat the static session's, or on a warm re-placement / re-plan that
    compiled a new executable:

      mild_drift   hot set shifts partially: TV drift crosses the replace
                   threshold only -> `lpt_placement` re-run on observed
                   loads, traced table swapped (zero recompile), no replan;
      step_drift   hot set jumps slices entirely: graded thresholds escalate
                   to a re-plan from the sketched HH set; the pinned combos
                   make the residual plan byte-identical, so the plan cache
                   and warm step cache serve it with zero new compiles.

    Makespan = max over devices of rows received (recv_counts), averaged over
    the final post-shift batches.  Emits BENCH_adapt.json."""
    import jax
    if len(jax.devices()) < 8:
        row("adapt_scaling/skipped", 0.0, "needs 8 devices")
        return
    from collections import defaultdict

    from repro.core import canonical, plan_skew_join, reference_join, two_way
    from repro.core.adapt import AdaptPolicy
    from repro.core.executor import ExecutorConfig, ShardedJoinExecutor
    from repro.data import drifting_join_batch
    from jax.sharding import AxisType
    from repro.serve import SelfHealingSession

    n_dev, n, hh_rows, dom, nhot, bonus, k = 8, 1024, 128, 128, 6, 24, 32
    mesh = jax.make_mesh((n_dev,), ("cells",), axis_types=(AxisType.Auto,))
    q = two_way()

    # Group tail values by which cell slice the plan routes them to, so a
    # "hot set" concentrates load on few cells and moving it is real drift
    # (hash collisions between slices would otherwise cancel the signal).
    base = drifting_join_batch(q, n, hh_rows, dom, [], 0, seed=0)
    plan0 = plan_skew_join(q, base, k)
    vals = np.arange(2, dom + 2, dtype=np.int64)
    arr = np.stack([np.zeros_like(vals), vals], axis=1)
    ridx, dest = plan0.route_relation("R", arr)
    per_val = defaultdict(set)
    for r, d in zip(ridx, dest):
        per_val[int(vals[r])].add(int(d))
    by_slice = defaultdict(list)
    for v, ds in sorted(per_val.items()):
        by_slice[tuple(sorted(ds))].append(v - 2)
    slices = [vs for _, vs in sorted(by_slice.items())]
    hot_a = [vs[0] for vs in slices[:nhot]]
    hot_b = [vs[0] for vs in slices[-nhot:]]

    report = {"n_devices": n_dev, "k": k, "workload": {
        "query": str(q), "n_per_relation": n, "hh_rows": hh_rows,
        "tail_domain": dom, "hot_values": nhot, "hot_bonus": bonus,
        "pre_shift_batches": 4, "post_shift_batches": 10,
        "makespan_window": 5}, "scenarios": {}}

    def _scenario(name, policy, hot_post):
        data0 = drifting_join_batch(q, n, hh_rows, dom, hot_a, bonus, seed=1)
        ex = ShardedJoinExecutor(plan_skew_join(q, data0, k), mesh,
                                 config=ExecutorConfig(out_capacity=1 << 16))
        adaptive = SelfHealingSession(ex, adapt=policy).prepare(data0)
        static = SelfHealingSession(ex).prepare(data0)
        batches = ([drifting_join_batch(q, n, hh_rows, dom, hot_a, bonus,
                                        seed=100 + i) for i in range(4)] +
                   [drifting_join_batch(q, n, hh_rows, dom, hot_post, bonus,
                                        seed=200 + i) for i in range(10)])
        exact, ms_a, ms_s, t_us = True, [], [], 0.0
        for b in batches:
            expect = reference_join(q, b)
            t0 = time.perf_counter()
            res_a = adaptive.run_batch(b)
            t_us += (time.perf_counter() - t0) * 1e6
            res_s = static.run_batch(b)
            for res in (res_a, res_s):
                got = res["rows"][res["valid"]]
                exact = exact and (len(got) == len(expect)
                                   and bool((canonical(got) == expect).all()))
            ms_a.append(int(res_a["recv_counts"].max()))
            ms_s.append(int(res_s["recv_counts"].max()))
        st = adaptive.stats
        win = report["workload"]["makespan_window"]
        entry = {
            "replacements": st["replacements"],
            "replace_compiles": st["replace_compiles"],
            "replans": st["replans"],
            "replan_compiles": st["replan_compiles"],
            "actions": [(i, act, round(tv, 4))
                        for i, act, tv in adaptive.detector.history],
            "exact": exact,
            "adaptive_makespan": float(np.mean(ms_a[-win:])),
            "static_makespan": float(np.mean(ms_s[-win:])),
            "makespan_ratio": float(np.mean(ms_a[-win:])
                                    / max(np.mean(ms_s[-win:]), 1e-9)),
            "adaptive_us_per_batch": t_us / len(batches),
        }
        report["scenarios"][name] = entry
        row(f"adapt_scaling/{name}", entry["adaptive_us_per_batch"],
            f"replacements={entry['replacements']};replans={entry['replans']};"
            f"replace_compiles={entry['replace_compiles']};"
            f"replan_compiles={entry['replan_compiles']};"
            f"exact={entry['exact']};"
            f"makespan={entry['adaptive_makespan']:.0f}"
            f"_vs_static={entry['static_makespan']:.0f}"
            f"({entry['makespan_ratio']:.2f}x)")

    # mild: replan threshold far above any observable TV -> replace only.
    _scenario("mild_drift",
              AdaptPolicy(replace_threshold=0.015, replan_threshold=0.5,
                          window=4, patience=2, min_batches=2,
                          replace_cooldown=2, replan_cooldown=4),
              hot_a[:-2] + hot_b[:2])
    # step: thresholds below half the step TV (window dilution halves the
    # observed distance while old batches age out) -> graded replan fires.
    _scenario("step_drift",
              AdaptPolicy(replace_threshold=0.015, replan_threshold=0.04,
                          window=4, patience=2, min_batches=2,
                          replace_cooldown=2, replan_cooldown=4),
              hot_b)

    out_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_adapt.json")
    with open(out_path, "w") as fh:
        json.dump(report, fh, indent=2)
    row("adapt_scaling/json", 0.0, f"path={out_path}")


def bench_shuffle_overlap():
    """Chunked map<->all_to_all pipeline vs the serial one-shot shuffle.

    The SAME skewed plan executes with `overlap_shuffle` C = 1 (serial
    oracle: pack everything, one all_to_all per relation) and C in {2, 4}
    (the tile pipeline: per-chunk caps are the serial cap ceil-divided by C,
    so total shuffle-buffer rows stay ~constant, and pack(tile i+1) has no
    data dependency on all_to_all(tile i), so a parallel runtime overlaps
    them).  All C sessions stay live and the timing loop INTERLEAVES them —
    one batch each per round, per-C minimum over the rounds — so container
    load drift hits every chunk count equally.  Per (m, k) point and C:
    warm `run_batch` latency blocked on the device-resident output buffer,
    bit-exactness vs `reference_join`, overflow counts, and `compile_count`
    growth across the warm rounds (C is baked into the step recipe — warm
    batches must compile NOTHING).

    Honest-measurement note: this container exposes ONE physical core
    (`cores` in the artifact), so there is no parallelism for the pipeline
    to exploit — pack and exchange serialize either way, and the expected
    result is latency-NEUTRAL (the ~1-3% chunk dispatch/concat overhead
    disappears into join-phase noise).  The gate therefore requires the
    overlapped path to stay within OVERLAP_TOL of serial at the largest
    swept size (enabling the pipeline must be free), not to beat it; the
    overlap's wall-clock win needs a multi-core host (XLA:CPU thunk
    executor) or a real TPU interconnect.  Emits BENCH_overlap.json."""
    import jax
    if len(jax.devices()) < 8:
        row("shuffle_overlap/skipped", 0.0, "needs 8 devices")
        return
    from repro.core import canonical, plan_skew_join, reference_join, two_way
    from repro.core.executor import ExecutorConfig, ShardedJoinExecutor
    from repro.data import skewed_join_dataset
    from jax.sharding import AxisType

    n_dev = 8
    mesh = jax.make_mesh((n_dev,), ("cells",), axis_types=(AxisType.Auto,))
    q = two_way()
    chunk_counts = (1, 2, 4)
    report = {"n_devices": n_dev, "cores": os.cpu_count(),
              "chunk_counts": list(chunk_counts), "rounds": 7, "sweep": []}

    for m, k in ((1 << 16, 32), (1 << 17, 64)):
        data = skewed_join_dataset(q, m, m, skew={"B": 0.5}, seed=13)
        plan = plan_skew_join(q, data, k)
        expect = reference_join(q, data)
        cap_out = 1 << max(int(np.ceil(np.log2(max(len(expect), 1) * 1.5))),
                           14)
        entry = {"m": m, "k": k, "ref_rows": len(expect), "chunks": []}
        sessions, builds_cold = {}, {}
        for C in chunk_counts:
            ex = ShardedJoinExecutor(plan, mesh, config=ExecutorConfig(
                out_capacity=cap_out, overlap_shuffle=C))
            session = ex.session().prepare(data)
            session.run_batch()                     # compile
            sessions[C] = (ex, session)
            builds_cold[C] = ex.compile_count
        best = {C: float("inf") for C in chunk_counts}
        for _ in range(report["rounds"]):
            for C, (_ex, session) in sessions.items():
                # Block on the device-resident output buffer, NOT a host
                # transfer — the (n_dev, cap_out, w) copy-out would swamp
                # the shuffle-phase difference this table measures.
                t0 = time.perf_counter()
                jax.block_until_ready(session.run_batch()._out)
                best[C] = min(best[C], time.perf_counter() - t0)
        for C, (ex, session) in sessions.items():
            res = session.run_batch()
            got = res["rows"][res["valid"]]
            exact = (len(got) == len(expect)
                     and bool((canonical(got) == expect).all()))
            c_entry = {
                "C": C, "warm_us": best[C] * 1e6, "exact": exact,
                "shuffle_overflow": int(res["shuffle_overflow"].sum()),
                "join_overflow": int(res["join_overflow"].sum()),
                "warm_builds": ex.compile_count - builds_cold[C],
                "step_builds": ex.compile_count,
            }
            entry["chunks"].append(c_entry)
            row(f"shuffle_overlap/m={m}/k={k}/C={C}", c_entry["warm_us"],
                f"exact={exact};"
                f"shuffle_overflow={c_entry['shuffle_overflow']};"
                f"join_overflow={c_entry['join_overflow']};"
                f"warm_builds={c_entry['warm_builds']}")
        serial_us = entry["chunks"][0]["warm_us"]
        best_c = min(entry["chunks"][1:], key=lambda e: e["warm_us"])
        entry["serial_us"] = serial_us
        entry["best_overlap_us"] = best_c["warm_us"]
        entry["best_C"] = best_c["C"]
        entry["overlap_vs_serial"] = best_c["warm_us"] / max(serial_us, 1e-9)
        report["sweep"].append(entry)
        row(f"shuffle_overlap/m={m}/k={k}/best", best_c["warm_us"],
            f"serial_us={serial_us:.1f};best_C={best_c['C']};"
            f"overlap_vs_serial={entry['overlap_vs_serial']:.3f}")

    out_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_overlap.json")
    with open(out_path, "w") as fh:
        json.dump(report, fh, indent=2)
    row("shuffle_overlap/json", 0.0, f"path={out_path}")


def bench_serve_scaling():
    """Multi-tenant join serving — the continuous-batching table.

    The deterministic `mixed_workload` stream (three tenants with
    structurally DISTINCT queries — 2-way, the paper's 3-way running
    example, a 4-way chain — each cycling through two row-count buckets)
    drives one `JoinServingEngine` on the 8-device mesh in two phases:

      warmup   two full size cycles per tenant: every (structure, shape
               bucket) signature is prepared and compiled, including any
               overflow-escalation ladder rungs;
      steady   a longer replay with fresh data (new seeds, same shapes):
               every request must land on a cached session (engine cache
               hit rate ≥ 0.9 is the gate floor; this run hits 1.0) and
               the engine-level compile count must not move — ZERO
               recompiles at steady state, the serving contract.

    Every request (warmup and steady) is checked bit-exact against
    `reference_join`.  Headline numbers: sustained queries/sec over the
    steady phase and per-request p50/p99 latency (request wall time
    including admission, padding, execute, and materializing the valid
    rows).  Emits BENCH_serve.json (schema in scripts/check_bench.py)."""
    import jax
    if len(jax.devices()) < 8:
        row("serve_scaling/skipped", 0.0, "needs 8 devices")
        return
    from repro.core import canonical, reference_join
    from repro.data import mixed_workload
    from jax.sharding import AxisType
    from repro.serve import JoinServingEngine

    n_dev, warm_n, steady_n = 8, 12, 24
    mesh = jax.make_mesh((n_dev,), ("cells",), axis_types=(AxisType.Auto,))
    eng = JoinServingEngine(mesh, k=n_dev)

    def _run_phase(n_requests, seed):
        reqs = [(eng.submit(tenant, q, data), q, data)
                for tenant, q, data in mixed_workload(n_requests, seed=seed)]
        t0 = time.perf_counter()
        eng.run()
        wall = time.perf_counter() - t0
        exact = True
        for req, q, data in reqs:
            got = canonical(req.rows)
            expect = canonical(reference_join(q, data))
            exact = exact and (got.shape == expect.shape
                               and bool((got == expect).all()))
        return wall, exact, [r.finished_at - r.started_at
                             for r, _, _ in reqs]

    warm_wall, warm_exact, _ = _run_phase(warm_n, seed=0)
    warm_compiles = eng.cache.compile_count()
    h0, m0 = eng.cache.hits, eng.cache.misses
    steady_wall, steady_exact, lat = _run_phase(steady_n, seed=1)
    recompiles = eng.cache.compile_count() - warm_compiles
    s_hits, s_misses = eng.cache.hits - h0, eng.cache.misses - m0
    hit_rate = s_hits / max(s_hits + s_misses, 1)
    lat_ms = np.asarray(lat) * 1e3
    queries = sorted({str(q) for _, q, _ in mixed_workload(3, seed=0)})
    report = {
        "n_devices": n_dev,
        "workload": {"queries": queries,
                     "distinct_queries": len(queries)},
        "warmup": {"requests": warm_n, "wall_s": warm_wall,
                   "compiles": warm_compiles, "exact": warm_exact},
        "steady": {"requests": steady_n, "wall_s": steady_wall,
                   "qps": steady_n / max(steady_wall, 1e-9),
                   "p50_ms": float(np.percentile(lat_ms, 50)),
                   "p99_ms": float(np.percentile(lat_ms, 99)),
                   "recompiles": recompiles,
                   "hits": s_hits, "misses": s_misses,
                   "cache_hit_rate": hit_rate, "exact": steady_exact},
        "cache": eng.cache.stats,
        "per_tenant": {name: dict(t.stats)
                       for name, t in eng.tenants.items()},
        "exact": warm_exact and steady_exact,
    }
    row("serve_scaling/warmup", warm_wall / max(warm_n, 1) * 1e6,
        f"requests={warm_n};compiles={warm_compiles};exact={warm_exact}")
    row("serve_scaling/steady", steady_wall / max(steady_n, 1) * 1e6,
        f"requests={steady_n};qps={report['steady']['qps']:.2f};"
        f"p50_ms={report['steady']['p50_ms']:.1f};"
        f"p99_ms={report['steady']['p99_ms']:.1f};"
        f"recompiles={recompiles};hit_rate={hit_rate:.2f};"
        f"exact={steady_exact}")

    out_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_serve.json")
    with open(out_path, "w") as fh:
        json.dump(report, fh, indent=2)
    row("serve_scaling/json", 0.0, f"path={out_path}")


def bench_kernel_throughput():
    """Kernel wrappers (jit'd ref path on CPU; Pallas compiles on TPU)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    n = 1 << 20
    keys = jnp.asarray(np.random.default_rng(0).integers(0, 1 << 30, n),
                       jnp.int32)
    f1 = jax.jit(lambda k: ref.hash_partition_ref(k, 0x9E3779B1, 256))
    us, _ = _timeit(lambda: jax.block_until_ready(f1(keys)), reps=5)
    row("kernel/hash_partition_1M", us, f"keys_per_s={n/(us/1e6):.3e}")
    probe = keys[:1 << 14]
    build = keys[:1 << 12]
    f2 = jax.jit(ref.match_counts_ref)
    us, _ = _timeit(lambda: jax.block_until_ready(f2(probe, build)), reps=5)
    row("kernel/match_counts_16kx4k", us,
        f"cmp_per_s={(probe.size*build.size)/(us/1e6):.3e}")
    vals = keys % 384
    f3 = jax.jit(lambda v: ref.segment_histogram_ref(v, 384))
    us, _ = _timeit(lambda: jax.block_until_ready(f3(vals)), reps=5)
    row("kernel/segment_histogram_1M", us, f"vals_per_s={n/(us/1e6):.3e}")


def bench_planner_latency():
    """Control-plane budget: plan_skew_join latency vs #HH."""
    from repro.core import plan_skew_join, two_way
    from repro.data import skewed_join_dataset
    q = two_way()
    for max_hh in (1, 4, 16, 64):
        data = skewed_join_dataset(q, 50_000, 200, skew={"B": 1.4}, seed=4)
        us, plan = _timeit(
            lambda: plan_skew_join(q, data, 256, max_hh_per_attr=max_hh),
            reps=1)
        row(f"planner/max_hh={max_hh}", us,
            f"hh={plan.hhs.total()};residuals={len(plan.residuals)};"
            f"cost={plan.total_cost:.3e}")


# Registry for `--only` / `--list` selection; insertion order is run order.
TABLES = {
    "two_way_cost": bench_two_way_cost,
    "skew_balance": bench_skew_balance,
    "residual_decomp": bench_residual_decomp,
    "moe_dispatch": bench_moe_dispatch,
    "executor_e2e": bench_executor_e2e,
    "reduce_scaling": bench_reduce_scaling,
    "shuffle_scaling": bench_shuffle_scaling,
    "fold_scaling": bench_fold_scaling,
    "map_scaling": bench_map_scaling,
    "reduce_v2": bench_reduce_v2,
    "recover_scaling": bench_recover_scaling,
    "adapt_scaling": bench_adapt_scaling,
    "shuffle_overlap": bench_shuffle_overlap,
    "serve_scaling": bench_serve_scaling,
    "kernel_throughput": bench_kernel_throughput,
    "planner_latency": bench_planner_latency,
}


def main(argv=None) -> None:
    import argparse
    p = argparse.ArgumentParser(
        description="Run benchmark tables (all by default).")
    p.add_argument("--only", metavar="PREFIX", default=None,
                   help="run only tables whose name starts with PREFIX")
    p.add_argument("--list", action="store_true", dest="list_tables",
                   help="list table names and exit")
    args = p.parse_args(argv)
    if args.list_tables:
        for name in TABLES:
            print(name)
        return
    selected = (list(TABLES.items()) if args.only is None
                else [(n, f) for n, f in TABLES.items()
                      if n.startswith(args.only)])
    if not selected:
        raise SystemExit(
            f"--only {args.only!r} matches no table; try --list")
    print("name,us_per_call,derived")
    for _, fn in selected:
        fn()
    print(f"# {len(ROWS)} rows")


if __name__ == "__main__":
    main()
