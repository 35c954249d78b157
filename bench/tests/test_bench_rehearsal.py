"""A tiny CPU rehearsal of each cell's path: the traffic generator, the
engine, the check and the trace reduction run; no device metric comes out
of a CPU."""
import json

import pytest

from bench import run as harness
from bench.tests.rehearse import rehearse

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
DEVICE_METRICS = {m["name"] for k in ("end_to_end", "per_layer")
                  for m in SPEC[k] if m["source"] == "device_trace"}


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
@pytest.mark.parametrize("traced", [False, True], ids=["e2e", "traced"])
def test_cpu_rehearsal(cell, traced):
    spec, run, line = rehearse(cell, traced=traced)
    assert line["correct"] and line["failed"] == 0
    assert line["attempted"] == len(run.served) > 0
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(
        line)
    want = {m["name"] for m in harness.cell_metrics(spec, cell, traced)}
    assert set(line["metrics"]) == want - DEVICE_METRICS
    assert line["device"]["platform"] == "cpu"
    if traced:
        assert line["device"]["busy_s"] == 0
        assert line["breakdown"] == {"device_ops": [], "idle_gaps": []}
    else:
        assert "breakdown" not in line and line["metrics"]["setup_s"][
            "value"] > 0
