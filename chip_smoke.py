"""Smoke run of the skewed-join serving path on the TPU.

One process drives the main path the way a user does: a `JoinServingEngine`
over a mesh of the chips, two tenants submitting skewed multiway joins
(the paper's 3-way running example and a 3-relation chain), served by
`engine.run()`.  Every request is checked row for row against the numpy
oracle `reference_join`.  Each relation has 2^21 rows whose heavy hitters
are pinned by `pinned_skew_batch`, so the plan holds heavy hitters on both
join attributes and the output sizes are known before the run.

    python chip_smoke.py [--seed S]      # one chip, both tenants
    python chip_smoke.py --chips 4       # four chips, the 3-way join only

The last line of standard output is one JSON object,
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
The script exits non-zero, without that line, when JAX finds no TPU, when a
request's rows differ from the oracle, when overflow is not healed by the
engine's retries, or on any exception.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import (canonical, cascade_sizes, reference_join,  # noqa: E402
                        running_example)
from repro.core.executor import ExecutorConfig  # noqa: E402
from repro.data import chain_query, pinned_skew_batch  # noqa: E402
from repro.kernels.ops import TPU_IMPL  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.serve.join_engine import JoinServingEngine  # noqa: E402

ROWS = 1 << 21            # rows per relation
HEAVY_SHARE = 4           # 1/4 of its heavy relation's rows hold value 0
LIGHT_ROWS = 4            # rows of the heavy value in every other relation
# Logical cells per chip.  A value is heavy at ≥ ROWS / k rows, so k must
# reach HEAVY_SHARE: the engine's default k (one cell per device) would find
# no heavy hitter on one chip.
CELLS_PER_CHIP = 4

TENANTS = {
    "paper3": (running_example(), {"B": "R", "C": "T"}),
    "chain3": (chain_query(3), {"X1": "R0", "X2": "R2"}),
}


def make_requests(chips: int, seed: int) -> list[tuple[str, int]]:
    """(tenant, data seed) per request, in submission order.  paper3 comes
    twice with fresh data at the same size: one shape bucket, so the second
    is served from the engine's session cache."""
    if chips > 1:
        return [("paper3", seed)]
    return [("paper3", seed), ("chain3", seed + 1), ("paper3", seed + 2)]


def tenant_batch(tenant: str, rows: int, seed: int):
    """(query, data) of one request of `tenant`: value 0 is heavy in one
    relation per join attribute and light in the others."""
    query, heavy = TENANTS[tenant]
    hh_rows = rows // HEAVY_SHARE
    return query, pinned_skew_batch(query, rows, heavy, hh_rows, LIGHT_ROWS,
                                    rows - hh_rows, seed=seed)


def log(msg: str) -> None:
    print(msg, flush=True)


def smoke(devices, seed: int, rows: int = ROWS) -> None:
    """Serve the requests over `devices` and check them; raises on any
    mismatch or unhealed overflow."""
    chips = len(devices)
    k = CELLS_PER_CHIP * chips
    mesh = jax.make_mesh((chips,), ("cells",), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,))
    reqs = []
    for tenant, data_seed in make_requests(chips, seed):
        query, data = tenant_batch(tenant, rows, data_seed)
        reqs.append((tenant, query, data, cascade_sizes(query, data)))
    # Each device's cascade must hold every intermediate: bound it by the
    # whole join's largest prefix, so no retry walks up from the default.
    out_capacity = max(max(sz[1:]) for *_, sz in reqs)
    engine = JoinServingEngine(mesh, config=ExecutorConfig(
        out_capacity=out_capacity), k=k)
    log(f"rows per relation: {rows}; logical cells k={k} on {chips} chip(s); "
        f"out_capacity={out_capacity}")
    log("phases on TPU: " + ", ".join(f"{p}={i}" for p, i in
                                      TPU_IMPL.items()))
    handles = [engine.submit(t, q, d) for t, q, d, _ in reqs]
    t0 = time.perf_counter()
    engine.run()
    log(f"engine.run: {time.perf_counter() - t0:.1f} s host wall clock, "
        f"compiles included")
    for (tenant, query, data, sizes), req in zip(reqs, handles):
        plan = engine.tenants[tenant].plan
        if not req.done:
            raise RuntimeError(f"request {req.rid} ({tenant}) not served")
        hhs = {a: plan.hhs.values(a) for a in plan.hhs.attrs_with_hh()}
        if not hhs:
            raise RuntimeError(f"{tenant}: plan found no heavy hitters")
        expect = reference_join(query, data)
        got = canonical(req.rows)
        exact = got.shape == expect.shape and np.array_equal(got, expect)
        recv = req.recv_counts.tolist()
        log(f"request {req.rid} {tenant}: {query}; heavy hitters {hhs}; "
            f"{len(plan.residuals)} residual joins; cascade sizes {sizes}; "
            f"output rows {len(got)}; received per device {recv}; "
            f"served in {req.finished_at - req.started_at:.1f} s; "
            f"{'row-exact' if exact else 'MISMATCH'} vs reference_join")
        if not exact:
            raise RuntimeError(f"request {req.rid} ({tenant}) differs from "
                               f"reference_join: {len(got)} vs "
                               f"{len(expect)} rows")
        if chips > 1 and min(recv) <= 0:
            raise RuntimeError(f"request {req.rid}: a device received no "
                               f"rows: {recv}")
    st = engine.stats
    for name, t in st["tenants"].items():
        log(f"tenant {name}: requests={t['requests']} "
            f"retries={t['retries']} overflow={t['overflow']} "
            f"compiles={t['compiles']} prepares={t['prepares']}")
    log(f"engine: compiles={st['compiles']} session cache "
        f"hits={st['cache']['hits']} misses={st['cache']['misses']}")
    for d in devices:
        peak = (d.memory_stats() or {}).get("peak_bytes_in_use")
        log(f"device {d.id} peak_bytes_in_use: {peak}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated relations")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="chips in the mesh; 4 runs only the 3-way join")
    args = ap.parse_args(argv)
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    devices = devices[:args.chips]
    log(f"device: {platform} {devices[0].device_kind} x{len(devices)}")
    log(f"compile cache: {enable_compile_cache()}")
    smoke(devices, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
