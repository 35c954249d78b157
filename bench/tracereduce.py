"""From a profiler trace to device busy time, phases and idle gaps.

`load_dir` reads the `.xplane.pb` that `jax.profiler` wrote and keeps: the
window (the host span `bench.window` that the harness opened around its
measured loop); each TPU's operations, from the `XLA Ops` line of a
`/device:TPU:<n>` plane, with the HLO module that ran each (the `XLA
Modules` event around it) and its JAX `op_name` path, which the TPU trace
does not carry and the compiled HLO text does (`op_names_from_hlo`); the
`Async XLA Ops` line, for collectives in flight; and the host's spans.
Everything else is reduced from those:

- busy time: the union of a device's operation intervals inside the
  window, averaged over the devices.  Operations nest (a `while` holds its
  body's), so each operation is credited only with the busy time it adds
  to those that started before it: the credits sum to the busy time;
- a phase's time: the credits of the operations whose `op_name` path holds
  a segment that starts with one of the phase's names, so that a kernel and
  its jitted twin (`scatter_pack`, `scatter_pack_host`) count alike;
  operations of the step under none of them are `step_other`;
- collective time: the union of the intervals, synchronous or in flight,
  of operations whose HLO name holds the collective's name;
- idle gaps: the holes in device 0's busy time, each named after the host
  span that covers half of it (the shortest such, else the one overlapping
  most), inside the harness's innermost `bench.*` span.

A trace with no TPU plane gives no device numbers: on a CPU every reader of
a device metric returns None.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass
from typing import Mapping

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW = "bench.window"
OP_NAME_STATS = ("tf_op", "long_name")
MODULE_STATS = ("hlo_module",)
SHORT_GAP_NS = 50_000       # gaps shorter than this are pooled unnamed
PHASES = (("map", ("scatter_pack",)), ("reduce", ("join_probe", "expand_rows")))
_HLO_LINE = re.compile(
    r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=.*?metadata=\{[^}]*?op_name="([^"]*)"')


@dataclass(frozen=True)
class Op:
    start_ns: float
    dur_ns: float
    name: str           # HLO instruction
    module: str         # HLO module
    op_name: str        # JAX's op_name path (jit(step)/.../jit(f)/sort)


@dataclass(frozen=True)
class Span:
    start_ns: float
    dur_ns: float
    name: str


def op_names_from_hlo(texts) -> dict[str, str]:
    """Instruction name -> `op_name` over compiled HLO texts; a name that
    two programs give different paths maps to ''."""
    out: dict[str, str] = {}
    for text in texts:
        for line in text.splitlines():
            m = _HLO_LINE.match(line)
            if m:
                name, path = m.groups()
                out[name] = path if out.get(name, path) == path else ""
    return out


def _stat(stats: dict, keys) -> str:
    for k in keys:
        if k in stats and stats[k] not in (None, ""):
            return str(stats[k])
    return ""


def _stats(event) -> dict:
    try:
        return {k: v for k, v in event.stats}
    except (TypeError, ValueError):
        return {}


def _device_ops(plane, op_names: Mapping[str, str]
                ) -> tuple[list[Op], list[Op]]:
    """(operations, operations in flight) of one device plane."""
    lines = {line.name: list(line.events) for line in plane.lines}
    mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                   ev.name.split("(")[0]) for ev in lines.get(MODULES_LINE, []))
    starts = [m[0] for m in mods]

    def module_of(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        return mods[i][2] if i >= 0 and t <= mods[i][1] else ""

    def ops(line_name: str) -> list[Op]:
        out = []
        for ev in lines.get(line_name, []):
            st = _stats(ev)
            name = ev.name.split(" = ", 1)[0].lstrip("%")
            out.append(Op(ev.start_ns, ev.duration_ns, name,
                          _stat(st, MODULE_STATS) or module_of(ev.start_ns),
                          _stat(st, OP_NAME_STATS) or op_names.get(name, "")))
        return out

    return ops(OPS_LINE), ops(ASYNC_LINE)


def from_profile(pd, n_devices: int | None = None,
                 op_names: Mapping[str, str] | None = None) -> "Trace":
    """A `Trace` from a `jax.profiler.ProfileData`."""
    window, host, devices = None, [], {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = _device_ops(plane, op_names or {})
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.duration_ns > 0:
                        host.append(Span(ev.start_ns, ev.duration_ns,
                                         ev.name))
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    ids = sorted(devices)[:n_devices] if n_devices else sorted(devices)
    return Trace(window, [devices[i][0] for i in ids], host,
                 [devices[i][1] for i in ids])


def load_dir(trace_dir: str, n_devices: int | None = None,
             op_names: Mapping[str, str] | None = None) -> "Trace":
    """The trace that `jax.profiler.start_trace(trace_dir)` wrote."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(paths)}")
    return from_profile(ProfileData.from_file(paths[0]), n_devices, op_names)


def _union(intervals: np.ndarray) -> np.ndarray:
    """Disjoint sorted (start, end) rows covering the given rows."""
    if not len(intervals):
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > reach[:-1]
    starts = iv[new, 0]
    ends = reach[np.r_[np.nonzero(new)[0][1:] - 1, len(iv) - 1]]
    return np.stack([starts, ends], axis=1)


def _segments(op_name: str) -> list[str]:
    """`jit(step)/shard_map/jit(scatter_pack_host)/sort` ->
    ['step', 'shard_map', 'scatter_pack_host', 'sort']."""
    out = []
    for seg in op_name.split("/"):
        m = re.fullmatch(r"\w+\((.*)\)", seg)
        out.append(m.group(1) if m else seg)
    return out


class Trace:
    """The reduction of one traced window (see the module docstring)."""

    def __init__(self, window: tuple[float, float], devices: list[list[Op]],
                 host: list[Span], in_flight: list[list[Op]] | None = None):
        self.window = window
        lo, hi = window

        def inside(ops):
            return [o for o in ops
                    if o.start_ns < hi and o.start_ns + o.dur_ns > lo]

        self.devices = [inside(ops) for ops in devices]
        self.in_flight = [inside(ops) for ops in (in_flight or
                                                  [[] for _ in devices])]
        self.host = [s for s in host if s.start_ns < hi
                     and s.start_ns + s.dur_ns > lo]
        self.credit = [self._credit(ops) for ops in self.devices]

    def _credit(self, ops: list[Op]) -> np.ndarray:
        """Busy nanoseconds each operation adds to those that started
        before it, inside the window."""
        lo, hi = self.window
        order = sorted(range(len(ops)), key=lambda i: ops[i].start_ns)
        credit = np.zeros(len(ops))
        reach = lo
        for i in order:
            o = ops[i]
            end = min(o.start_ns + o.dur_ns, hi)
            credit[i] = max(0.0, end - max(o.start_ns, reach))
            reach = max(reach, end)
        return credit

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices;
        0 where the trace holds no device."""
        if not self.devices:
            return 0.0
        return float(np.mean([c.sum() for c in self.credit])) / 1e9

    def _mean_s(self, keep) -> float:
        if not self.devices:
            return 0.0
        return float(np.mean([
            sum(c for o, c in zip(ops, cr) if keep(o))
            for ops, cr in zip(self.devices, self.credit)])) / 1e9

    def module_s(self, prefix: str) -> float:
        """Busy seconds of the modules named `prefix*`, per device."""
        return self._mean_s(lambda o: o.module.startswith(prefix))

    def phase_s(self, prefixes) -> float:
        """Busy seconds of the operations under a jitted function whose
        name starts with one of `prefixes`, per device."""
        prefixes = tuple(prefixes)
        return self._mean_s(lambda o: any(
            s.startswith(prefixes) for s in _segments(o.op_name)))

    def collective_s(self, kind: str) -> float:
        """Seconds in which an operation whose HLO name holds `kind` (such
        as `all-to-all`) ran or was in flight, per device."""
        if not self.devices:
            return 0.0
        lo, hi = self.window
        tot = []
        for ops, fl in zip(self.devices, self.in_flight):
            iv = np.array([(o.start_ns, o.start_ns + o.dur_ns)
                           for o in ops + fl if kind in o.name],
                          np.float64).reshape(-1, 2)
            u = _union(np.clip(iv, lo, hi))
            tot.append(np.sum(u[:, 1] - u[:, 0]))
        return float(np.mean(tot)) / 1e9

    @staticmethod
    def label(op: Op) -> str:
        """`<phase>/<op_name below it>`: map, reduce or step_other within
        the step, else the module; the HLO op kind where no op_name is
        known."""
        segs = _segments(op.op_name)
        for phase, prefixes in PHASES:
            for i, s in enumerate(segs):
                if s.startswith(prefixes):
                    return "/".join([phase] + segs[i:])
        kind = re.sub(r"[.\d]+$", "", op.name) or op.name
        inner = [s for s in segs if s not in ("step", "shard_map", "")]
        where = ("step_other" if op.module.startswith("jit_step")
                 else op.module or "unknown")
        return "/".join([where] + (inner or [kind]))

    def top_ops(self, n: int = 10) -> list[list]:
        """The `n` labels with the most busy seconds, per device."""
        tot: dict[str, float] = {}
        for ops, cr in zip(self.devices, self.credit):
            for o, c in zip(ops, cr):
                lab = self.label(o)
                tot[lab] = tot.get(lab, 0.0) + c
        k = max(len(self.devices), 1)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[lab, float(ns) / k / 1e9] for lab, ns in top]

    def gaps(self) -> np.ndarray:
        """(start, end) rows of device 0's idle time inside the window."""
        if not self.devices:
            return np.zeros((0, 2))
        lo, hi = self.window
        iv = np.array([(o.start_ns, o.start_ns + o.dur_ns)
                       for o in self.devices[0]], np.float64).reshape(-1, 2)
        u = _union(np.clip(iv, lo, hi))
        edges = np.concatenate([[lo], u.ravel(), [hi]]).reshape(-1, 2)
        return edges[edges[:, 1] > edges[:, 0]]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """Device 0's idle seconds by what the host was doing: the `n`
        names with the most, each summed over its gaps."""
        gaps = self.gaps()
        if not len(gaps):
            return []
        starts = np.array([s.start_ns for s in self.host])
        ends = starts + np.array([s.dur_ns for s in self.host])
        names = [s.name for s in self.host]
        bench = np.array([nm.startswith("bench.") for nm in names], bool)
        tot: dict[str, float] = {}
        for g0, g1 in gaps:
            if g1 - g0 < SHORT_GAP_NS:
                lab = f"gaps under {SHORT_GAP_NS // 1000} us"
            else:
                lab = self._gap_label(g0, g1, starts, ends, names, bench)
            tot[lab] = tot.get(lab, 0.0) + (g1 - g0)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[lab, float(ns) / 1e9] for lab, ns in top]

    @staticmethod
    def _gap_label(g0, g1, starts, ends, names, bench) -> str:
        if not len(starts):
            return "no host span"
        over = np.minimum(ends, g1) - np.maximum(starts, g0)
        mid = (g0 + g1) / 2
        outer = np.nonzero(bench & (starts <= mid) & (ends >= mid))[0]
        where = (names[outer[np.argmin(ends[outer] - starts[outer])]]
                 if len(outer) else "outside bench spans")
        inner = np.nonzero(~bench & (over > 0))[0]
        if not len(inner):
            return where
        # The shortest span that covers half the gap, else the one that
        # covers most of it.
        cover = inner[over[inner] >= (g1 - g0) / 2]
        pick = (cover[np.argmin(ends[cover] - starts[cover])] if len(cover)
                else inner[np.argmax(over[inner])])
        return f"{where} > {names[pick]}"
