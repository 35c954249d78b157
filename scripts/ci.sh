#!/usr/bin/env bash
# Tier-1 verify + example smoke runs (see ROADMAP.md).
set -euo pipefail
cd "$(dirname "$0")/.."
# CI runs on the CPU (8 virtual devices); the chip has bench/run.py.
export JAX_PLATFORMS=cpu

# The tests include the structural lowering pins (tests/test_scatter_pack.py)
# and the warm-path, self-healing, adaptation and serving gates.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q
# Chaos smoke: every injected-fault scenario (overflow retry, device loss,
# straggler eviction, corrupted rows) must recover bit-exact.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q tests/test_chaos.py

# The documented entry points must not rot: each example asserts its own
# exactness (quickstart runs a k=256 plan folded onto 8 devices; the demo a
# k=64 three-way join) and exits non-zero on mismatch.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python examples/quickstart.py
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python examples/skewed_join_demo.py
