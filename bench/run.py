"""One run of one cell of the chip benchmark.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration file and its metrics are found by name in
`BENCHMARK.json` at the root of the checkout; the traffic mix is
`bench/traffic/<traffic>.json`, the configuration's rows are made by
`bench/generators/<generator>.py` and each metric is read by
`bench/metrics/<metric>.py`.  A run makes its requests from the seed,
serves each request of its pool once as a warm-up, through
`JoinServingEngine.submit` and `step_round` on a mesh of the cell's chips,
then drives the same engine for `--seconds`: one client sends the pool's
next request when the last returns.
With `--trace 1` the window runs under the profiler and the result carries
the per-layer metrics, read from the trace and the run record, in place of
the end-to-end ones.

Once the window has closed, every request served in it is compared with
the plain reference (`check.py`).  The last line of standard output is one
JSON object: `correct`, `attempted`, `failed`, `metrics`, `device` and, with
`--trace 1`, `breakdown`; its last key, `checks`, gives each compared number
beside its limit, as do the last lines of standard error.  The run exits 1
and prints no result where JAX finds no TPU or fewer chips than the cell
asks for.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Mapping  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import check, gen  # noqa: E402
from bench.record import Run, Served  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
DRAIN_S = 60.0      # how long past the window a request is waited for


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, flush=True)


# -- the benchmark's files, found by name ------------------------------------

def load_cell(workload: str, root: Path = ROOT) -> tuple[dict, dict, dict,
                                                          dict]:
    """(spec, cell, configuration, traffic) of `workload`."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    gen.check_config(config, root / "bench")
    gen.check_traffic(traffic, config)
    return spec, cell, config, traffic


def cell_metrics(spec: Mapping, cell: str, traced: bool) -> list[dict]:
    """The metrics a run of `cell` reports: its end-to-end ones, or with
    the trace its per-layer ones."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not traced:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in moved
                             else [])]


def metric_reader(name: str, bench: Path = BENCH) -> Callable:
    """`read(run)` of `metrics/<name>.py`."""
    return gen.load_module(bench / "metrics" / f"{name}.py").read


def device_peaks(kind: str, bench: Path = BENCH) -> dict:
    """Peaks of a device kind from `peaks.json`; an unknown kind is an
    error, never a default."""
    table = json.loads((bench / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json "
                       f"(have {sorted(table)})")
    return table[kind]


def require_chips(chips: int) -> list:
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found "
                     f"{len(devices)}")
    return devices[:chips]


# -- the run -----------------------------------------------------------------

class _Counter:
    """Counts JAX compiles (and persistent-cache misses) as they happen."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event: str, _dur: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event == CACHE_MISS_EVENT:
            self.cache_misses += 1


def _query(relations):
    from repro.core.plan import JoinQuery, Relation
    return JoinQuery(tuple(Relation(n, tuple(a)) for n, a in relations))


def out_capacity(config: Mapping, chips: int) -> int:
    """Per-device join output capacity by the configuration's rule: the
    largest cascade prefix of each tenant's largest request drawn from seed
    0, times `slack`, over the chips.  It is a static shape of the step, so
    it must not move with the run's seed."""
    rule = config["out_capacity"]
    if rule["rule"] != "max_prefix":
        raise ValueError(f"unknown out_capacity rule {rule['rule']!r}")
    top = max(max(check.prefix_sizes(
        gen.relations(config, t),
        gen.tenant_data(config, t, config["rows"], (0, 3))))
        for t in config["tenants"])
    return int(math.ceil(top * float(rule["slack"]) / chips))


def run_cell(cell: Mapping, config: Mapping, traffic: Mapping, seed: int,
             seconds: float, devices: list, peaks: Mapping,
             traced: bool = False, control: str | None = None
             ) -> tuple[Run, dict]:
    """Warm up, measure, check.  Returns the run record, the fullest
    chip's peak memory and the checks."""
    import jax
    from jax.sharding import AxisType
    from repro.core.executor import ExecutorConfig
    from repro.kernels.ops import TPU_IMPL
    from repro.serve.join_engine import JoinServingEngine

    chips = len(devices)
    counter = _Counter()
    run = Run(cell["name"], chips, dict(peaks))
    tenants = list(config["tenants"])
    queries = {t: _query(gen.relations(config, t)) for t in tenants}
    run.widths = {t: {name: len(attrs) for name, attrs
                      in gen.relations(config, t)} for t in tenants}
    run.out_width = {t: len(gen.attributes(gen.relations(config, t)))
                     for t in tenants}
    log("phases on TPU: " + ", ".join(f"{p}={i}" for p, i in TPU_IMPL.items()))

    specs = gen.request_specs(config, traffic, seed)
    t = time.perf_counter()
    data = {s.index: gen.tenant_data(config, s.tenant, s.rows, s.data_seed)
            for s in specs}
    log(f"generated a pool of {len(specs)} requests in "
        f"{time.perf_counter() - t:.2f} s")

    k = int(config["k_per_chip"]) * chips
    cap_out = out_capacity(config, chips)
    mesh = jax.make_mesh((chips,), ("cells",), devices=devices,
                         axis_types=(AxisType.Auto,))
    engine = JoinServingEngine(mesh, config=ExecutorConfig(
        out_capacity=cap_out), k=k)
    log(f"engine: k={k} logical cells on {chips} chip(s), "
        f"out_capacity={cap_out}")

    # The pool itself: every request the window sends is served once.
    t = time.perf_counter()
    for s in specs:
        req = engine.submit(s.tenant, queries[s.tenant], data[s.index])
        engine.step_round()
        if not req.done:
            raise RuntimeError(f"warm-up request {s} was not served")
    st = engine.stats
    log(f"warm-up: {time.perf_counter() - t:.2f} s; step compiles="
        f"{st['compiles']} jax compiles={counter.compiles} "
        f"persistent-cache misses={counter.cache_misses}")
    compiles0, misses0, steps0 = (counter.compiles, counter.cache_misses,
                                  st["compiles"])

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    run.setup_s = time.perf_counter() - PROCESS_START
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        _closed_loop(engine, queries, specs, data, t0, seconds, run)
        t1 = max([t0] + [s.done for s in run.completed()])
    run.window_s = t1 - t0
    if traced:
        jax.profiler.stop_trace()
    window_compiles = counter.compiles - compiles0
    counter.close()
    st = engine.stats
    log(f"window: {run.window_s:.3f} s, {len(run.completed())}/"
        f"{len(run.served)} requests served; compiles in the window: "
        f"jax={window_compiles} step={st['compiles'] - steps0} "
        f"persistent-cache misses={counter.cache_misses - misses0}")
    log(f"engine.stats: requests={st['requests']} rounds={st['rounds']} "
        f"compiles={st['compiles']} cache={json.dumps(st['cache'])}")
    for name, ts in st["tenants"].items():
        log(f"tenant {name}: " + " ".join(f"{k}={v}" for k, v in ts.items()))
    recv = np.array([s.recv for s in run.completed()], np.int64)
    if len(recv):
        log(f"recv_counts per device, mean over requests: "
            f"{recv.mean(axis=0).round(1).tolist()}; first request: "
            f"{recv[0].tolist()}")

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    texts = _step_programs(engine, peak) if traced else []
    del engine
    if traced:
        from bench import tracereduce
        run.trace = tracereduce.load_dir(
            trace_dir, len(devices), tracereduce.op_names_from_hlo(texts))
        shutil.rmtree(trace_dir, ignore_errors=True)
    return run, {"peak_bytes": peak,
                 "checks": check_run(config, run, data, control, seed)}


def _closed_loop(engine, queries, specs, data, t0, seconds, run) -> None:
    """One client, requests back to back until `seconds` pass; the request
    in flight at the deadline is finished and counted.  One that has not
    come back a minute past the deadline is recorded as never served."""
    import jax
    i = 0
    while True:
        with jax.profiler.TraceAnnotation("bench.pool"):
            spec = specs[i % len(specs)]
            rel = data[spec.index]
        s = Served(spec, {r: len(a) for r, a in rel.items()})
        with jax.profiler.TraceAnnotation("bench.submit"):
            req = engine.submit(spec.tenant, queries[spec.tenant], rel)
        run.served.append(s)
        while not req.done and time.perf_counter() - t0 < seconds + DRAIN_S:
            with jax.profiler.TraceAnnotation("bench.step_round"):
                engine.step_round()
        if not req.done:
            return
        _finish(s, req, time.perf_counter())
        i += 1
        if s.done - t0 >= seconds:
            return


def _finish(s: Served, req, now: float) -> None:
    s.done = now
    s.rows = req.rows
    s.out_rows = len(req.rows)
    s.recv = [int(x) for x in np.asarray(req.recv_counts).reshape(-1)]


def _step_programs(engine, peak: int) -> list[str]:
    """Log the allocator's peak beside each warm step's compiled memory
    analysis (persistent-cache hits, after the window); returns the steps'
    HLO texts, which name the `op_name` of each operation the trace shows.
    It reads the engine's private session state, so a program that renames
    it loses these lines and the phases, not the run."""
    log(f"peak_bytes_in_use (fullest chip): {peak}")
    texts = []
    try:
        for ses in list(engine.cache._sessions.values()):
            f = ses.executor._compiled_step(ses._shapes, ses.caps,
                                            ses.cap_out)
            c = f.lower(ses._ptable_dev, *ses._device_args).compile()
            ma = c.memory_analysis()
            log(f"step {ses._shapes} caps={ses.caps} cap_out={ses.cap_out}: "
                f"temp={ma.temp_size_in_bytes} "
                f"argument={ma.argument_size_in_bytes} "
                f"output={ma.output_size_in_bytes} bytes per device")
            texts.append(c.as_text())
    except AttributeError as e:
        log(f"step programs unavailable: {e}")
    return texts


def check_run(config: Mapping, run: Run, data: Mapping, control: str | None,
              seed: int) -> dict:
    """Compare every served request with the reference (each distinct
    request's reference computed once, its fingerprint keys drawn from the
    run's seed).  With `control`, the control's answers stand in for the
    program's."""
    t = time.perf_counter()
    refs: dict[int, check.Reference] = {}
    controls: dict[int, np.ndarray] = {}
    wrong = unserved = bad_requests = 0
    for s in run.served:
        if math.isnan(s.done):
            unserved += 1
            continue
        rels = gen.relations(config, s.spec.tenant)
        i = s.spec.index
        if i not in refs:
            refs[i] = check.Reference(rels, data[i], (seed, 2, i))
        got = s.rows
        if control == "hash_match":
            if i not in controls:
                controls[i] = check.hash_match_join(rels, data[i])
            got = controls[i]
        elif control is not None:
            raise ValueError(f"unknown control {control!r}")
        n = refs[i].rows_wrong(got)
        wrong += n
        bad_requests += n > 0
    exact = sum(r.exact is not None for r in refs.values())
    log(f"check: {time.perf_counter() - t:.2f} s for "
        f"{len(run.served) - unserved} answers against {len(refs)} "
        f"references, {exact} built in full")
    return {"rows_wrong": {"value": wrong, "limit": 0},
            "requests_unserved": {"value": unserved, "limit": 0},
            "requests_wrong": {"value": bad_requests, "limit": 0}}


def result_line(spec: Mapping, cell: str, run: Run, out: dict, devices,
                traced: bool) -> dict:
    checks = out["checks"]
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    metrics = {}
    for m in cell_metrics(spec, cell, traced):
        value = metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": out["peak_bytes"]}
    line = {"correct": correct, "attempted": len(run.served),
            "failed": checks["requests_unserved"]["value"]
            + checks["requests_wrong"]["value"],
            "metrics": metrics, "device": device}
    if traced and run.trace is not None:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s()
        line["breakdown"] = {"device_ops": run.trace.top_ops(10),
                             "idle_gaps": run.trace.idle_gaps(10)}
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("hash_match",), default=None,
                    help="compare the control's answers in place of the "
                         "program's (proves the check can fail)")
    args = ap.parse_args(argv)
    spec, cell, config, traffic = load_cell(args.workload)
    try:
        devices = require_chips(int(cell["chips"]))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    kind = devices[0].device_kind
    peaks = device_peaks(kind)
    log(f"device: {devices[0].platform} {kind} x{len(devices)}")
    from repro.launch.cache import enable_compile_cache
    import jax
    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    seed = args.seed % (1 << 63)
    run, out = run_cell(cell, config, traffic, seed, args.seconds, devices,
                        peaks, traced=bool(args.trace), control=args.control)
    line = result_line(spec, args.workload, run, out, devices,
                       bool(args.trace))
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
