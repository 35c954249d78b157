"""Production meshes.

`make_production_mesh` is a FUNCTION (never a module-level constant) so that
importing this module touches no jax device state — the dry-run must set its
XLA_FLAGS before the first jax device query.

  single-pod:  (16, 16)      axes ("data", "model")         = 256 chips (v5e pod)
  multi-pod:   (2, 16, 16)   axes ("pod", "data", "model")  = 512 chips

DP runs over ("pod","data"); the pod axis carries only the cross-pod gradient
all-reduce (DCN), which the multi-pod dry-run proves shardable.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(devices: int | None = None, model: int = 4):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = devices or len(jax.devices())
    model = min(model, n)
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


# TPU v5e hardware constants used by the roofline analysis (per chip).
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW_PER_LINK = 50e9            # B/s per link (~4 links/chip on v5e)
