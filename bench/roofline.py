"""Bytes that the map and the reduce of one request must move, from shapes.

Both phases are sorts, scatters and gathers of int32 rows: they are bound by
memory, so their roofline is bytes over the chip's HBM bandwidth and no
operation count is given.  A routed copy carries its relation's columns and
the logical cell id.  The program reports copies received per device over
all relations together, so a copy is counted at the narrowest relation's
width: a lower bound that keeps a share of the roofline from passing 100%.
Intermediates of the join cascade are left out, so the count stays the same
whatever implements the join.
"""
from __future__ import annotations

from typing import Mapping

WORD = 4    # bytes of an int32 value


def map_bytes(rows: Mapping[str, int], widths: Mapping[str, int],
              copies: int) -> int:
    """Read every input row once; write every routed copy once."""
    read = sum(rows[r] * widths[r] for r in rows) * WORD
    return read + copies * (min(widths.values()) + 1) * WORD


def reduce_bytes(widths: Mapping[str, int], copies: int, out_rows: int,
                 out_width: int) -> int:
    """Read every received copy once; write every result row once."""
    return (copies * (min(widths.values()) + 1) + out_rows * out_width) * WORD


def roofline_pct(total_bytes: float, device_s: float,
                 bytes_per_s: float) -> float | None:
    """Share of the bandwidth roofline, in %: the least time the bytes
    need over the device time taken.  None when no device time was seen."""
    if device_s <= 0:
        return None
    return 100.0 * total_bytes / bytes_per_s / device_s

