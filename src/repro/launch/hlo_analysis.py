"""Roofline terms from a compiled XLA executable's HLO text.

XLA's built-in `cost_analysis()` counts a while-loop body ONCE regardless of
trip count, so a scanned-layers model under-reports FLOPs/bytes by ~n_layers
(verified in EXPERIMENTS.md §Dry-run against an unrolled compile).  This
module re-derives the three roofline terms from the post-SPMD, post-fusion
HLO text with trip-count weighting:

  * per computation, build a symbol table (op name -> shape) since scheduled
    HLO prints operands by name only;
  * FLOPs: every `dot` contributes 2 · prod(output dims) · prod(rhs
    contracting dims) — MXU work (elementwise is negligible for these models);
  * HBM bytes: operands + result of every *memory-moving* top-level op
    (fusions, dots, copies, slices, collectives); fusion boundaries are
    exactly the HBM round trips, so fusion-body internals are skipped;
  * collective bytes: operand bytes of all-reduce / all-gather /
    reduce-scatter / all-to-all / collective-permute, with iota-format
    replica-group parsing to split in-pod vs cross-pod traffic;
  * call graph: `while` bodies weighted by backend_config
    known_trip_count, fusions/calls by 1.

This is a structural model, not a simulator: its job is comparing sharding /
fusion / schedule variants in §Perf (relative accuracy), and its absolute
FLOPs cross-check against XLA's cost_analysis on an unrolled compile
(scripts/validate_hlo_parser.py).
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass, field

import numpy as np

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_HDR_RE = re.compile(r"^(ENTRY\s+)?%([\w\.\-]+)\s+\(")
_OPLINE_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.*)$")


def _split_type_kind(rhs: str) -> tuple[str, str, str]:
    """Split 'TYPE kind(args)...' -> (type, kind, args).

    The split point is the first space outside (), {}, [] — this handles tuple
    types like '(s32[], f32[4,64]{1,0}) while(%t), ...' whose parens would
    otherwise be mistaken for the argument list (variadic all-reduce bug)."""
    depth = 0
    for i, ch in enumerate(rhs):
        if ch in "({[":
            depth += 1
        elif ch in ")}]":
            depth -= 1
        elif ch == " " and depth == 0:
            type_part = rhs[:i]
            rest = rhs[i + 1:]
            m = re.match(r"([a-z][\w\-]*)\(", rest)
            if not m:
                return type_part, "", ""
            # args: balanced-paren scan from after 'kind('
            astart = m.end()
            d = 1
            for j in range(astart, len(rest)):
                if rest[j] == "(":
                    d += 1
                elif rest[j] == ")":
                    d -= 1
                    if d == 0:
                        return type_part, m.group(1), rest[astart:j]
            return type_part, m.group(1), rest[astart:]
    return rhs, "", ""
_WHILE_RE = re.compile(r"condition=%([\w\.\-]+),\s*body=%([\w\.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count[\\"]*:\s*\{[\\"]*n[\\"]*:[\\"]*(\d+)')
_CALLS_RE = re.compile(r"(?:calls|to_apply)=%([\w\.\-]+)")
_RHS_CONTRACT_RE = re.compile(r"rhs_contracting_dims=\{([0-9,]*)\}")
_OPERANDS_RE = re.compile(r"%([\w\.\-]+)")
_RG_LIST_RE = re.compile(r"replica_groups=\{(\{[0-9,\}\{]*\})\}")
_RG_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
# Ops that move HBM on TPU.  Deliberately EXCLUDES ops the TPU compiler fuses
# into consumers (reshape/bitcast/transpose/broadcast/iota/convert/select/pad/
# slice) — the CPU backend materializes those, and counting them makes the
# memory term ~2x pessimistic vs a real TPU executable.
_HBM_OPS = {"fusion", "dot", "convolution", "copy", "dynamic-slice",
            "dynamic-update-slice", "scatter", "gather", "sort", "reduce",
            "concatenate", "rng-bit-generator",
            *COLLECTIVES, *(f"{c}-start" for c in COLLECTIVES)}
_FREE_OPS = {"bitcast", "get-tuple-element", "tuple", "parameter", "constant",
             "after-all", "add-dependency"}


def _shape_dims(text: str) -> list[tuple[str, list[int]]]:
    out = []
    for dt, dims in _SHAPE_RE.findall(text):
        if dt in _DTYPE_BYTES:
            out.append((dt, [int(d) for d in dims.split(",") if d]))
    return out


def _bytes_of(shapes: list[tuple[str, list[int]]]) -> float:
    return float(sum(_DTYPE_BYTES[dt] * math.prod(dims or [1])
                     for dt, dims in shapes))


@dataclass
class _Op:
    name: str
    kind: str
    out_shapes: list
    rhs: str
    args: str


@dataclass
class _Comp:
    name: str
    ops: dict[str, _Op] = field(default_factory=dict)
    order: list[str] = field(default_factory=list)


def _split_computations(text: str) -> tuple[dict[str, _Comp], str | None]:
    comps: dict[str, _Comp] = {}
    entry = None
    cur: _Comp | None = None
    for line in text.splitlines():
        raw = line.rstrip()
        s = raw.strip()
        if cur is None:
            m = _HDR_RE.match(s)
            if m and s.endswith("{") and "->" in s:
                cur = _Comp(m.group(2))
                comps[cur.name] = cur
                if m.group(1):
                    entry = cur.name
            continue
        if s == "}":
            cur = None
            continue
        m = _OPLINE_RE.match(s)
        if not m:
            continue
        name, rhs = m.groups()
        type_part, kind, args = _split_type_kind(rhs)
        cur.ops[name] = _Op(name, kind, _shape_dims(type_part), rhs, args)
        cur.order.append(name)
    return comps, entry


def _iota_groups(g: int, s: int, dims: list[int], perm: list[int] | None
                 ) -> np.ndarray:
    n = math.prod(dims)
    arr = np.arange(n).reshape(dims)
    if perm:
        arr = arr.transpose(perm)
    return arr.reshape(g, s)


def _groups_of(line: str) -> np.ndarray | None:
    m = _RG_IOTA_RE.search(line)
    if m:
        g, s = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        perm = [int(x) for x in m.group(4).split(",")] if m.group(4) else None
        return _iota_groups(g, s, dims, perm)
    m = _RG_LIST_RE.search(line)
    if m:
        rows = re.findall(r"\{([0-9,]+)\}", m.group(1))
        groups = [[int(x) for x in r.split(",")] for r in rows]
        width = max(len(r) for r in groups)
        return np.array([r + r[-1:] * (width - len(r)) for r in groups])
    return None


def count_ops(text: str, op_name: str) -> int:
    """Number of `op_name` ops in an HLO module, across ALL computations —
    fusion bodies, while bodies, and called computations included, so an op
    the compiler fused out of the entry computation still counts.

    `op_name` is the HLO opcode as printed (e.g. "gather", "scatter",
    "dynamic-slice", "all-to-all"); matching is exact on the parsed op kind,
    so "gather" never matches "all-gather".  This is the structural gate
    the lowering pins in tests/test_scatter_pack.py build on: the map's sort
    assemble must lower with count_ops(hlo, "gather") == 0."""
    comps, _ = _split_computations(text)
    return sum(1 for comp in comps.values()
               for op in comp.ops.values() if op.kind == op_name)


@dataclass
class RooflineTerms:
    flops: float
    hbm_bytes: float
    coll_bytes: dict[str, float]
    coll_bytes_total: float
    coll_bytes_crosspod: float
    coll_counts: dict[str, int]

    def seconds(self, *, peak_flops: float, hbm_bw: float, ici_bw: float
                ) -> dict[str, float]:
        """Per-device roofline terms in seconds (HLO is the per-device SPMD
        program, so each term divides by per-chip rates)."""
        return {"compute_s": self.flops / peak_flops,
                "memory_s": self.hbm_bytes / hbm_bw,
                "collective_s": self.coll_bytes_total / ici_bw}


def analyze(text: str, pod_size: int | None = None) -> RooflineTerms:
    comps, entry = _split_computations(text)
    # Fusion-called computations: internals are not HBM traffic.
    fusion_bodies: set[str] = set()
    for comp in comps.values():
        for op in comp.ops.values():
            if op.kind == "fusion":
                cm = _CALLS_RE.search(op.rhs)
                if cm:
                    fusion_bodies.add(cm.group(1))

    def op_flops(comp: _Comp, op: _Op) -> float:
        if op.kind != "dot":
            return 0.0
        out_n = math.prod((op.out_shapes[0][1] or [1])) if op.out_shapes else 0
        cm = _RHS_CONTRACT_RE.search(op.rhs)
        if not cm:
            return 0.0
        # rhs operand = second %ref of the argument list
        refs = _OPERANDS_RE.findall(op.args)
        if len(refs) < 2:
            return 0.0
        rhs_op = comp.ops.get(refs[1])
        if rhs_op is None or not rhs_op.out_shapes:
            return 0.0
        rdims = rhs_op.out_shapes[0][1]
        contract = 1
        for ci in cm.group(1).split(","):
            if ci and int(ci) < len(rdims):
                contract *= rdims[int(ci)]
        return 2.0 * out_n * contract

    def operand_bytes(comp: _Comp, op: _Op) -> float:
        total = 0.0
        for ref in _OPERANDS_RE.findall(op.args):
            producer = comp.ops.get(ref)
            if producer is not None:
                total += _bytes_of(producer.out_shapes)
        return total

    # Per-computation raw stats + call edges.
    raw: dict[str, dict] = {}
    for comp in comps.values():
        st = {"flops": 0.0, "hbm": 0.0, "coll": {}, "coll_x": 0.0, "calls": []}
        count_hbm = comp.name not in fusion_bodies
        for name in comp.order:
            op = comp.ops[name]
            st["flops"] += op_flops(comp, op)
            if op.kind == "while":
                wm = _WHILE_RE.search(op.rhs)
                trips = 1.0
                tm = _TRIP_RE.search(op.rhs)
                if tm:
                    trips = float(tm.group(1))
                if wm:
                    st["calls"].append((wm.group(2), trips))
                    st["calls"].append((wm.group(1), trips))
                continue
            cm = _CALLS_RE.search(op.rhs)
            if cm and op.kind in ("fusion", "call", "map", "reduce", "sort",
                                  "scatter", "all-reduce", "reduce-scatter"):
                # to_apply bodies are tiny scalar fns except call/fusion.
                if op.kind in ("fusion", "call"):
                    st["calls"].append((cm.group(1), 1.0))
            base_kind = op.kind.removesuffix("-start")
            if base_kind in COLLECTIVES and not op.kind.endswith("-done"):
                b = operand_bytes(comp, op)
                st["coll"][base_kind] = st["coll"].get(base_kind, 0.0) + b
                if pod_size:
                    g = _groups_of(op.rhs)
                    if g is not None and ((g // pod_size).max(axis=1)
                                          != (g // pod_size).min(axis=1)).any():
                        st["coll_x"] += b
            if count_hbm and op.kind in _HBM_OPS:
                st["hbm"] += operand_bytes(comp, op) + _bytes_of(op.out_shapes)
        raw[comp.name] = st

    if entry is None:
        called = {c for st in raw.values() for c, _ in st["calls"]}
        entries = [n for n in raw if n not in called]
        entry = entries[0] if entries else next(iter(raw))

    memo: dict[str, tuple] = {}

    def walk(name: str, depth=0):
        if name in memo:
            return memo[name]
        if name not in raw or depth > 128:
            return (0.0, 0.0, {}, 0.0)
        st = raw[name]
        fl, hb, cb, cx = st["flops"], st["hbm"], dict(st["coll"]), st["coll_x"]
        for callee, mult in st["calls"]:
            f2, h2, c2, x2 = walk(callee, depth + 1)
            fl += mult * f2
            hb += mult * h2
            for k, v in c2.items():
                cb[k] = cb.get(k, 0.0) + mult * v
            cx += mult * x2
        memo[name] = (fl, hb, cb, cx)
        return memo[name]

    fl, hb, cb, cx = walk(entry)
    counts = {c: len(re.findall(rf"= [^=]*\b{c}(?:-start)?\(", text))
              for c in COLLECTIVES}
    return RooflineTerms(flops=fl, hbm_bytes=hb, coll_bytes=cb,
                         coll_bytes_total=sum(cb.values()),
                         coll_bytes_crosspod=cx, coll_counts=counts)
