"""Drive a cell's whole run on the CPU at a tiny size, its configuration's
`rehearsal` keys in place of its own: the harness's look for a chip is
skipped, everything after it runs."""
import json

import jax

from bench import run as harness

SEED = 2**31 + 7


def rehearse(cell: str, seconds: float = 1.0, traced: bool = False,
             control=None, rows=None):
    spec, w, config, traffic = harness.load_cell(cell)
    config = {**config, **config["rehearsal"]}
    if rows:
        config["rows"] = rows
    devices = jax.devices()[:w["chips"]]
    run, out = harness.run_cell(w, config, traffic, SEED, seconds, devices,
                                harness.device_peaks("TPU v5 lite"),
                                traced=traced, control=control)
    return spec, run, harness.result_line(spec, cell, run, out, devices,
                                          traced)


def rehearse_four_chips(rows: int = 8192, seconds: float = 1.0):
    """`paper3-2m` folded onto four CPU devices, as a four-chip cell would
    run it (k = 16, each chip's output capacity twice its share, since the
    chips' shares of the result differ); returns the checks."""
    bench = harness.BENCH
    config = json.loads((bench / "configs/paper3-2m.json").read_text())
    config.update(rows=rows, out_capacity={"rule": "max_prefix", "slack": 2.0})
    traffic = json.loads((bench / "traffic/closed1-pool2.json").read_text())
    _, out = harness.run_cell({"name": "paper3x4", "chips": 4},
                              config, traffic, SEED,
                              seconds, jax.devices()[:4],
                              harness.device_peaks("TPU v5 lite"))
    return out["checks"]
