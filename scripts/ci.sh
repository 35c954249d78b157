#!/usr/bin/env bash
# Tier-1 verify + benchmark smoke check + example smoke runs (see ROADMAP.md).
set -euo pipefail
cd "$(dirname "$0")/.."
# CI runs on the CPU (8 virtual devices); the chip has chip_smoke.py.
export JAX_PLATFORMS=cpu

PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q
# Chaos smoke: every injected-fault scenario (overflow retry, device loss,
# straggler eviction, corrupted rows) must recover bit-exact.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q tests/test_chaos.py
# Benchmark table selection must keep working (benchmarks/run.py --list /
# --only): the smoke runs one cheap host-side table end-to-end.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python benchmarks/run.py --list \
    | grep -qx serve_scaling
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python benchmarks/run.py \
    --only two_way_cost
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python scripts/check_bench.py
# check_bench regenerates every BENCH_*.json (map_scaling, reduce_v2,
# recover_scaling, adapt_scaling and serve_scaling included) and fails on
# non-exact/overflow/hash-path, self-healing (unbounded retry /
# recompile-on-retry), adaptation (static beats adaptive / warm re-plan
# recompiled) or serving (steady recompiles / cold cache / p99 cliff)
# regressions; the artifacts must exist afterwards.
test -f BENCH_shuffle.json -a -f BENCH_fold.json -a -f BENCH_map.json \
     -a -f BENCH_reduce.json -a -f BENCH_recover.json -a -f BENCH_adapt.json \
     -a -f BENCH_overlap.json -a -f BENCH_serve.json
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python scripts/check_recompile.py
# Structural lowering guard: the scatter-assemble map phase and the one-hot
# reduce expansion must lower with ZERO XLA gather ops (and the counter's
# teeth must still bite on the superseded gather paths).
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python scripts/check_hlo.py

# The documented entry points must not rot: each example asserts its own
# exactness (quickstart runs a k=256 plan folded onto 8 devices; the demo a
# k=64 three-way join) and exits non-zero on mismatch.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python examples/quickstart.py
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python examples/skewed_join_demo.py
