"""End-to-end driver: a distributed multiway join under heavy skew.

This is the paper-native "production job": plan (HH detection + residual
decomposition + Shares) then execute (hash -> placement fold ->
capacity-bounded all_to_all -> local joins) on a device mesh, validated
against the single-machine oracle.  The plan allocates k=64 LOGICAL reducer
cells — 8x more than the 8 physical devices — and the executor folds them
onto the mesh with skew-aware LPT placement (core/placement.py), exactly how
a data-sized plan runs on fixed hardware.

Run:  PYTHONPATH=src python examples/skewed_join_demo.py
The mesh spans every device JAX finds: the chips on a TPU host, or 8
virtual devices on the CPU (requested below).
"""
import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8").strip()

import time

import jax
import numpy as np

from repro.core import (canonical, plan_skew_join, reference_join,
                        running_example)
from repro.core.executor import ExecutorConfig, ShardedJoinExecutor
from repro.data import skewed_join_dataset
from repro.launch.cache import enable_compile_cache
from jax.sharding import AxisType


def main():
    enable_compile_cache()
    mesh = jax.make_mesh((len(jax.devices()),), ("cells",),
                         axis_types=(AxisType.Auto,))
    # The paper's running 3-way example: R(A,B) ⋈ S(B,E,C) ⋈ T(C,D),
    # with heavy hitters on both B and C.
    query = running_example()
    data = skewed_join_dataset(query, n_per_relation=120, domain=60,
                               skew={"B": 1.6, "C": 1.3}, seed=7)
    print(f"query: {query}")
    print(f"mesh: {dict(mesh.shape)} ({len(jax.devices())} devices)\n")

    # k = 64 logical cells folded onto the devices (8x on 8 devices).
    plan = plan_skew_join(query, data, k=64, max_hh_per_attr=3)
    print(f"HHs: B={plan.hhs.values('B')} C={plan.hhs.values('C')}")
    print(f"{len(plan.residuals)} residual joins, k={plan.k} logical cells, "
          f"total planned communication {plan.total_cost:.0f} tuples\n")

    ex = ShardedJoinExecutor(plan, mesh,
                             config=ExecutorConfig(out_capacity=32768))
    t0 = time.time()
    session = ex.session().prepare(data)
    result = session.run_batch()
    dt = time.time() - t0

    p = session.placement
    fold = np.bincount(p.table, minlength=p.n_devices)
    print(f"placement: {p.strategy}, {p.k} logical cells -> {p.n_devices} "
          f"devices ({fold.min()}-{fold.max()} cells each)")

    rows = result["rows"][result["valid"]]
    expect = reference_join(query, data)
    ok = np.array_equal(canonical(rows), expect)
    recv = result["recv_counts"].astype(float)
    print(f"executed in {dt:.2f}s ({'exact match' if ok else 'MISMATCH'} "
          f"vs oracle: {len(rows)} joined rows)")
    print(f"shuffle overflow: {int(result['shuffle_overflow'].sum())}, "
          f"join overflow: {int(result['join_overflow'].sum())}")
    print(f"per-device received tuples: min={recv.min():.0f} "
          f"mean={recv.mean():.0f} max={recv.max():.0f} "
          f"(imbalance {recv.max()/max(recv.mean(),1):.2f})")
    assert ok, "distributed result != oracle"


if __name__ == "__main__":
    main()
