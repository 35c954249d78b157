"""Readings of the program's host spans (`join.*`, opened by the serving
engine and the executor session) in a traced run.  A span the trace does
not hold reads as missing, never as zero."""
from __future__ import annotations

import numpy as np

REQUEST = "join.request"    # encloses the others: covers no idle of its own


def _intervals(trace, keep) -> np.ndarray:
    """(start, end) rows of the host spans whose name passes `keep`,
    clipped to the window."""
    lo, hi = trace.window
    iv = np.array([(s.start_ns, s.start_ns + s.dur_ns) for s in trace.host
                   if keep(s.name)], np.float64).reshape(-1, 2)
    return np.clip(iv, lo, hi)


def span_ms(run, name: str) -> float | None:
    """Milliseconds inside the window's spans named `name`, per completed
    request."""
    n, t = len(run.completed()), run.trace
    if t is None or not n:
        return None
    iv = _intervals(t, lambda nm: nm == name)
    if not len(iv):
        return None
    return float(np.sum(iv[:, 1] - iv[:, 0])) / n / 1e6


def idle_unspanned_ms(run) -> float | None:
    """Milliseconds of device 0's idle time that no `join.*` span other than
    `join.request` covers, per completed request."""
    n, t = len(run.completed()), run.trace
    if t is None or not n or not t.devices:
        return None
    iv = _intervals(t, lambda nm: nm.startswith("join.") and nm != REQUEST)
    if not len(iv):
        return None
    gaps = t.gaps()
    idle = float(np.sum(gaps[:, 1] - gaps[:, 0]))
    # Spans nest, so merge them before taking their overlap with the gaps.
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    covered, reach = 0.0, -np.inf
    for s0, s1 in iv:
        s0 = max(s0, reach)
        if s1 > s0:
            covered += float(np.sum(np.clip(np.minimum(gaps[:, 1], s1)
                                             - np.maximum(gaps[:, 0], s0),
                                             0, None)))
        reach = max(reach, s1)
    return (idle - covered) / n / 1e6
