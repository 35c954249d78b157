"""The record of one run that the metric readers in `metrics/` are given."""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np

from .gen import RequestSpec


@dataclass
class Served:
    """One request of the window as the client saw it, on the host clock."""
    spec: RequestSpec
    rows_in: dict[str, int]     # rows per relation
    done: float = math.nan      # when step_round returned it
    out_rows: int = 0
    recv: list[int] = field(default_factory=list)   # copies per device
    rows: np.ndarray | None = None


@dataclass
class Run:
    cell: str
    chips: int
    peaks: dict                 # this device kind's row of peaks.json
    setup_s: float = math.nan   # process start to window start
    window_s: float = math.nan  # window start to the last completion
    served: list[Served] = field(default_factory=list)
    widths: dict[str, dict[str, int]] = field(default_factory=dict)
    out_width: dict[str, int] = field(default_factory=dict)   # per tenant
    trace: object = None        # tracereduce.Trace of a traced run

    def completed(self) -> list[Served]:
        return [s for s in self.served if not math.isnan(s.done)]
