"""Distributed SkewShares execution engine: map -> shuffle -> reduce in JAX.

The MapReduce round of the paper as a `shard_map` over a 1-D device axis.
The full design narrative — what each phase runs, the logical-cell /
physical-device fold, capacity derivation, session caching — lives in
docs/architecture.md; this docstring keeps only the invariants the code
relies on.

  map     `kernels.scatter_pack.scatter_pack_host`, per relation: route
          every row through all of its residual routes (fused multiply-shift
          hashes, type constraints, replication offsets), fold each wrapped
          logical cell onto its device through the placement table, and
          assemble the (n_devices, cap, w+1) shuffle buffer with ONE stable
          sort keyed on the destination — no rank pass, no scatter; each
          device's rows are a slice of the sorted copies.
  shuffle each relation's buffer goes through one `all_to_all` — or, with
          `overlap_shuffle = C ≥ 2`, through C chunked all_to_alls
          interleaved with the next chunk's pack (each chunk's send buffer
          is final the moment it is packed — the paper's one-round structure
          is what makes the overlap legal; the serial path stays the
          bit-exactness oracle up to fragment arrival order).
  reduce  `_local_join`: a cascade of joins matching only within equal
          logical cell ids.  Each step probes with
          `kernels.join_probe.join_probe_host` (an exact-key merge sort of
          both sides, then scans: no hash, loop, gather or scatter) and
          expands the (counts, lo, perm) result into `cap_out` rows with
          `kernels.scatter_pack.expand_rows_host` (each slot's left row
          located by a merge, then gathered).  `_local_join_dense`, the
          O(n_l·n_r) match-matrix cascade, is the ground oracle.

Invariants:
  * Logical cells of every residual join live in one flat id space
    (Hypercube.offset, cumulative), wrapped modulo plan.k; a `CellPlacement`
    (core/placement.py) maps the k wrapped ids onto n_devices physical
    devices — LPT bin-packing on observed per-cell loads by default,
    modulo as the oblivious fallback, identity when k == n_devices.
  * Every routed tuple copy carries its UNWRAPPED logical cell id as a hidden
    last column and the local join matches only within equal ids, so cells
    sharing a device — wrapped blocks or folded placements, even every cell
    on one device — can never produce cross-residual or cross-cell
    duplicates.  Placement moves load, never correctness.
  * The placement table is a runtime argument of the compiled step, not a
    constant: re-placing cells never recompiles.

Conventions: attribute values are int32 ≥ 0; -1 marks invalid/padding rows.
`plan.k` is the LOGICAL cell count — any power of two ≥ the mesh axis size
executes (k < n_devices or non-power-of-two k raise at construction).
Sessions (`ExecutorSession.prepare`/`run_batch`) upload once and stream warm;
`ShardedJoinExecutor.run` is the one-shot wrapper.
"""
from __future__ import annotations

import collections.abc
import warnings
from dataclasses import dataclass
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels import ops as kops
from .hypercube import hash_seed
from .placement import (CellPlacement, check_fold, modulo_placement,
                        place_cells)
from .plan import JoinQuery
from .skewjoin import SkewJoinPlan

INVALID = -1


def span(name: str, rid: int | None = None) -> jax.profiler.TraceAnnotation:
    """A host span on the profiler's clock, carrying the request id as its
    `rid` stat.  It costs about a microsecond when no trace is active and
    never synchronizes with the device (docs/architecture.md, "Tracing")."""
    if rid is None:
        return jax.profiler.TraceAnnotation(name)
    return jax.profiler.TraceAnnotation(name, rid=rid)


# ---------------------------------------------------------------------------
# Error taxonomy (the fault-tolerance layer's structured failures)
# ---------------------------------------------------------------------------

class ExecutorError(RuntimeError):
    """Base of the executor's structured failures (all are RuntimeErrors so
    pre-taxonomy callers catching RuntimeError keep working)."""


class InputValidationError(ExecutorError):
    """A relation's tuples violate the data-plane contract (integer 2-D,
    values ≥ -1, int32-representable).  Raised BEFORE upload — corrupted
    rows must never reach the routing kernels, whose -1 sentinel they would
    alias."""


class CapacityOverflowError(ExecutorError):
    """A static capacity was exceeded and rows were dropped.

    Carries the full per-device, per-phase breakdown: `shuffle_by_rel` is the
    (n_devices, n_relations) dropped-copy count of the shuffle pack,
    `join_overflow` the (n_devices,) dropped-result count of the reduce
    cascade, `relations` the column labels of `shuffle_by_rel`.  The message
    renders the non-zero entries so the failing (device, phase, relation)
    coordinates are visible without a debugger."""

    def __init__(self, msg: str, shuffle_by_rel: np.ndarray,
                 join_overflow: np.ndarray, relations: tuple[str, ...]):
        super().__init__(msg)
        self.shuffle_by_rel = shuffle_by_rel
        self.join_overflow = join_overflow
        self.relations = relations

    @classmethod
    def from_result(cls, result: Mapping[str, np.ndarray],
                    relations: tuple[str, ...],
                    hint: str = "raise capacity_factor/out_capacity or "
                                "retry via run_with_retry()"
                    ) -> "CapacityOverflowError":
        sh = np.asarray(result["shuffle_overflow_by_rel"], np.int64)
        jo = np.asarray(result["join_overflow"], np.int64)
        lines = []
        for dev in range(sh.shape[0]):
            parts = [f"shuffle[{rel}]={int(sh[dev, r])}"
                     for r, rel in enumerate(relations) if sh[dev, r]]
            if jo[dev]:
                parts.append(f"join={int(jo[dev])}")
            if parts:
                lines.append(f"  dev {dev}: " + ", ".join(parts))
        msg = (f"capacity overflow: shuffle={int(sh.sum())} "
               f"join={int(jo.sum())}; per-device breakdown:\n"
               + "\n".join(lines) + f"\n{hint}")
        return cls(msg, sh, jo, relations)


class RetryBudgetExceededError(CapacityOverflowError):
    """Bounded retry exhausted its budget and the last attempt still
    overflowed — capacities escalated `attempts` times without absorbing the
    load (the data plane refuses to loop forever)."""

    def __init__(self, msg: str, shuffle_by_rel: np.ndarray,
                 join_overflow: np.ndarray, relations: tuple[str, ...],
                 attempts: int):
        super().__init__(msg, shuffle_by_rel, join_overflow, relations)
        self.attempts = attempts


class DeviceLossError(ExecutorError):
    """Degraded mode cannot shrink further (no surviving device to re-fold
    onto, or an eviction target is unknown)."""


# ---------------------------------------------------------------------------
# Capacity bucketing + retry policy
# ---------------------------------------------------------------------------

def quantize_capacity(cap: int, ratio: float = 2.0) -> int:
    """Round a capacity UP to the geometric grid {1, ⌈r⌉, ⌈⌈r⌉·r⌉, ...}.

    Compiled steps are keyed on capacities, so every distinct derived cap is
    a cold compile; quantizing to a coarse geometric grid makes
    heterogeneous-but-similar chunks and geometrically escalated retries
    land on ALREADY-COMPILED signatures (the warm step cache) instead of
    recompiling.  ratio ≤ 1 disables (identity); ratio 2 is the power-of-two
    grid.  Never rounds down — a bucketed cap can only add slack."""
    cap = int(cap)
    if ratio <= 1.0 or cap <= 1:
        return max(cap, 1)
    b = 1
    while b < cap:
        b = max(int(np.ceil(b * ratio)), b + 1)
    return b


@dataclass(frozen=True)
class RetryPolicy:
    """Bounded overflow-retry policy: escalate the overflowing capacities by
    `escalation` (quantized to the session's bucket grid, so ladder rungs are
    shared executables) and re-run the SAME chunk, at most `max_retries`
    times; then raise `RetryBudgetExceededError`.  `escalation` should match
    the config's `cap_bucket` ratio — then every retry moves exactly one grid
    point and a previously-walked ladder recompiles nothing."""
    max_retries: int = 4
    escalation: float = 2.0


@dataclass(frozen=True)
class ExecutorConfig:
    capacity_factor: float = 2.0       # shuffle slack over the max observed load
    out_capacity: int = 4096           # per-cell join output rows (static)
    cap_bucket: float = 2.0            # geometric grid DERIVED capacities are
                                       # quantized to (≤ 1 disables); aligns
                                       # retries + similar chunks on warm
                                       # executables (explicit caps= are
                                       # respected verbatim)
    overlap_shuffle: int = 0           # C ≥ 2: split each relation's map
                                       # pass into C tiles and interleave
                                       # pack(i+1) with all_to_all(i) on
                                       # per-chunk send buffers (caps are
                                       # per chunk; remainder tiles pad to
                                       # the warm shapes).  ≤ 1: the serial
                                       # map -> one all_to_all oracle path
    max_cached_steps: int = 32         # compiled-step LRU bound per executor:
                                       # every retained executable pins real
                                       # device memory, so a long-lived
                                       # multi-tenant process must evict
                                       # (generous default — a steady
                                       # workload's working set is a handful
                                       # of (shapes, caps) signatures;
                                       # `evicted_steps` counts evictions)


@dataclass(frozen=True)
class _Route:
    """Static routing recipe for one (residual, relation) pair."""
    rel: str
    hashed: tuple[tuple[int, int, int, int], ...]  # (col, seed, share, stride)
    rep_strides: tuple[int, ...]                   # flattened replication offsets
    offset: int
    # Type constraints (paper Example 3.2): which rows participate.
    eq_constraints: tuple[tuple[int, int], ...]    # (col, value) must equal
    notin_constraints: tuple[tuple[int, tuple[int, ...]], ...]  # (col, hh_values)


def _route_specs(routes: list[_Route]) -> tuple:
    """Flatten `_Route`s to the static nested-tuple `RouteSpec` the map and
    the count pass compile in (k rides separately)."""
    return tuple((r.hashed, r.rep_strides, r.offset, r.eq_constraints,
                  r.notin_constraints) for r in routes)


def _build_routes(plan: SkewJoinPlan) -> dict[str, list[_Route]]:
    """Per relation: one `_Route` per residual join (static, host-side)."""
    routes: dict[str, list[_Route]] = {r.name: [] for r in plan.query.relations}
    for rp in plan.residuals:
        cube = rp.cube
        strides = cube.strides()
        assign = rp.residual.combo.as_dict
        for rel in plan.query.relations:
            hashed, wild = [], []
            for ax, (attr, share) in enumerate(zip(cube.attr_order, cube.shares)):
                if attr in rel.attrs:
                    hashed.append((rel.attrs.index(attr),
                                   hash_seed(attr, cube.salt), share, strides[ax]))
                else:
                    wild.append((strides[ax], share))
            # Flattened replication offsets (static fanout).
            reps = np.zeros(1, dtype=np.int64)
            for stride, share in wild:
                reps = (reps[:, None] + np.arange(share) * stride).ravel()
            eqs, notins = [], []
            for i, attr in enumerate(rel.attrs):
                hh_vals = plan.hhs.values(attr)
                if not hh_vals:
                    continue
                if attr in assign:
                    eqs.append((i, int(assign[attr])))
                else:
                    notins.append((i, tuple(int(v) for v in hh_vals)))
            routes[rel.name].append(_Route(
                rel.name, tuple(hashed), tuple(int(x) for x in reps),
                cube.offset, tuple(eqs), tuple(notins)))
    return routes


def _validate_relation(name: str, arr: np.ndarray, width: int | None = None
                       ) -> np.ndarray:
    """Host-side input validation before anything is uploaded.

    The data-plane contract (module docstring): integer 2-D arrays, attribute
    values ≥ 0, with -1 reserved for the executor's own padding sentinel.
    Corrupted rows (negative garbage, values outside int32) would alias the
    sentinel or wrap in the int32 cast — silently wrong joins — so they are
    rejected HERE with the relation name and offending row, never routed."""
    a = np.asarray(arr)
    if a.ndim != 2:
        raise InputValidationError(
            f"relation {name!r}: expected a 2-D (rows, attrs) array, got "
            f"shape {a.shape}")
    if width is not None and a.shape[1] != width:
        raise InputValidationError(
            f"relation {name!r}: {a.shape[1]} columns != {width} declared "
            f"attributes")
    if not np.issubdtype(a.dtype, np.integer):
        raise InputValidationError(
            f"relation {name!r}: dtype {a.dtype} is not integer (attribute "
            f"values are int32 ≥ 0)")
    if a.size:
        lo, hi = int(a.min()), int(a.max())
        if lo < INVALID:
            bad = np.nonzero((a < INVALID).any(axis=1))[0]
            raise InputValidationError(
                f"relation {name!r}: {bad.size} corrupted rows with values "
                f"< {INVALID} (first at row {int(bad[0])}); -1 is the "
                f"reserved padding sentinel and attribute values must be "
                f"≥ 0")
        if hi > np.iinfo(np.int32).max:
            raise InputValidationError(
                f"relation {name!r}: max value {hi} exceeds int32 range")
    return a


def _check_placement_compat(placement: CellPlacement, k: int, n_dev: int
                            ) -> None:
    """A placement must map exactly the plan's k cells onto exactly the
    mesh's devices (shared by executor construction and session prepare)."""
    if placement.k != k or placement.n_devices != n_dev:
        raise ValueError(
            f"placement maps {placement.k} cells -> {placement.n_devices} "
            f"devices; plan/mesh need {k} -> {n_dev}")


# ---------------------------------------------------------------------------
# Reduce phase
# ---------------------------------------------------------------------------

def _local_join(frags: dict[str, jnp.ndarray], query: JoinQuery, cap_out: int
                ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Cascade natural join of one cell's fragments.

    Every fragment row carries its LOGICAL cell id as the last column; each
    cascade step joins on (shared named attributes AND equal logical cell), so
    a physical cell hosting several logical cells computes each logical cell's
    join independently — structural exactness for wrapped residual blocks.

    One step, with left = accumulator (n_l rows) and right = next fragment:
      1. a probe pass over the shared key columns (incl. `__cell__`) yields
         per-left-row match counts, group-start offsets, and a grouped
         right-side permutation whose groups are contiguous and internally in
         ARRIVAL order — `kops.join_probe`, an exact-key merge sort;
      2. expand to the static `cap_out` shape from the exclusive prefix sum
         of per-left-row counts — `kops.expand_rows`, output order (left
         row, right arrival order), bit-identical to the dense-matrix ground
         oracle `_local_join_dense`.  Output columns are carved out of the
         expanded (left ++ right) rows with STATIC slices — no column
         gather.

    Returns (rows (cap_out, n_attrs), valid (cap_out,), overflow ())."""
    rels = list(query.relations)
    acc = frags[rels[0].name]                      # columns: attrs + [cell]
    acc_attrs = list(rels[0].attrs) + ["__cell__"]
    acc_valid = acc[:, -1] != INVALID
    overflow = jnp.int32(0)
    for rel in rels[1:]:
        right = frags[rel.name]
        right_attrs = list(rel.attrs) + ["__cell__"]
        r_valid = right[:, -1] != INVALID
        shared = [(acc_attrs.index(a), right_attrs.index(a))
                  for a in right_attrs if a in acc_attrs]   # incl. __cell__
        lk = acc[:, jnp.asarray([l for l, _ in shared])]
        rk = right[:, jnp.asarray([r for _, r in shared])]
        counts, lo, perm = kops.join_probe(lk, acc_valid, rk, r_valid)
        n_match = counts.sum()
        overflow = overflow + jnp.maximum(0, n_match - cap_out)
        exp, valid_out = kops.expand_rows(acc, right, counts, lo, perm,
                                          cap_out)
        wa = acc.shape[1]
        extra_names = [a for a in rel.attrs if a not in acc_attrs]
        extra_cols = [right_attrs.index(a) for a in extra_names]
        # Column layout: acc named attrs, new named attrs, __cell__ last —
        # static slices of the expanded (acc ++ right) rows.
        pieces = [exp[:, :wa - 1]]
        pieces.extend(exp[:, wa + c:wa + c + 1] for c in extra_cols)
        pieces.append(exp[:, wa - 1:wa])           # the (equal) cell id
        new_rows = jnp.concatenate(pieces, axis=1)
        acc_valid = valid_out
        acc = jnp.where(acc_valid[:, None], new_rows, INVALID)
        acc_attrs = acc_attrs[:-1] + extra_names + ["__cell__"]
    order = [acc_attrs.index(a) for a in query.attributes]
    return acc[:, jnp.asarray(order)], acc_valid, overflow


def _local_join_dense(frags: dict[str, jnp.ndarray], query: JoinQuery,
                      cap_out: int
                      ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """O(n_l·n_r) match-matrix cascade — the superseded reduce phase, kept as
    the exactness oracle of the tests.

    Output (rows, valid, overflow) is bit-identical to `_local_join`."""
    rels = list(query.relations)
    acc = frags[rels[0].name]
    acc_attrs = list(rels[0].attrs) + ["__cell__"]
    acc_valid = acc[:, -1] != INVALID
    overflow = jnp.int32(0)
    for rel in rels[1:]:
        right = frags[rel.name]
        right_attrs = list(rel.attrs) + ["__cell__"]
        r_valid = right[:, -1] != INVALID
        shared = [(acc_attrs.index(a), right_attrs.index(a))
                  for a in right_attrs if a in acc_attrs]
        match = acc_valid[:, None] & r_valid[None, :]
        for la, ra in shared:
            match &= acc[:, la][:, None] == right[:, ra][None, :]
        n_match = match.sum()
        overflow = overflow + jnp.maximum(0, n_match - cap_out)
        flat = jnp.nonzero(match.reshape(-1), size=cap_out, fill_value=0)[0]
        li, ri = flat // right.shape[0], flat % right.shape[0]
        extra_names = [a for a in rel.attrs if a not in acc_attrs]
        extra_cols = [right_attrs.index(a) for a in extra_names]
        pieces = [acc[li][:, :-1]]
        if extra_cols:
            pieces.append(right[ri][:, jnp.asarray(extra_cols)])
        pieces.append(acc[li][:, -1:])
        new_rows = jnp.concatenate(pieces, axis=1)
        acc_valid = jnp.arange(cap_out) < n_match
        acc = jnp.where(acc_valid[:, None], new_rows, INVALID)
        acc_attrs = acc_attrs[:-1] + extra_names + ["__cell__"]
    order = [acc_attrs.index(a) for a in query.attributes]
    return acc[:, jnp.asarray(order)], acc_valid, overflow


class ShardedJoinExecutor:
    """Runs a SkewJoinPlan on a 1-D mesh of any size ≤ plan.k.

    plan.k LOGICAL cells fold onto the mesh's n_devices physical devices
    through a `CellPlacement` table (identity modulo when k == n_devices,
    skew-aware LPT from observed cell loads otherwise; pass `placement` or
    `placement_strategy` to override).  Holds everything static: the routing
    recipes, the jitted counting pass, and a cache of compiled steps keyed on
    (input shapes, capacities) — the placement table is a runtime argument,
    so re-placing never recompiles.  All data movement lives in
    `ExecutorSession` (see `session()`); `run` is the one-shot wrapper."""

    def __init__(self, plan: SkewJoinPlan, mesh: Mesh, axis: str = "cells",
                 config: ExecutorConfig = ExecutorConfig(),
                 placement: CellPlacement | None = None,
                 placement_strategy: str = "lpt"):
        n_dev = mesh.shape[axis]
        check_fold(plan.k, n_dev)
        if placement is not None:
            _check_placement_compat(placement, plan.k, n_dev)
        self.plan, self.mesh, self.axis, self.config = plan, mesh, axis, config
        self.n_devices = n_dev
        self.placement = placement            # None -> per-session default
        self.placement_strategy = placement_strategy
        self.route_specs = {name: _route_specs(rs)
                            for name, rs in _build_routes(plan).items()}
        self._step_cache: dict[tuple, object] = {}
        self._count_fn = None
        self.compile_count = 0          # step builds (one per distinct key)
        self.step_hits = 0              # warm step lookups (no build)
        self.evicted_steps = 0          # steps dropped by the LRU bound

    # -- control plane ------------------------------------------------------
    def _shard(self, arr: np.ndarray) -> np.ndarray:
        """Pad rows to a device-divisible count with INVALID rows."""
        n_dev = self.n_devices
        n = len(arr)
        n_pad = -n % n_dev
        pad = np.full((n_pad, arr.shape[1]), INVALID, arr.dtype)
        return np.concatenate([arr, pad]).astype(np.int32)

    def _upload(self, sharded: np.ndarray) -> jnp.ndarray:
        """Place a host-sharded array on the mesh, split along the axis."""
        return jax.device_put(
            sharded, NamedSharding(self.mesh, P(self.axis)))

    def _upload_table(self, placement: CellPlacement) -> jnp.ndarray:
        """Replicate a placement table to every device on the mesh."""
        return jax.device_put(placement.table.astype(np.int32),
                              NamedSharding(self.mesh, P()))

    def _count_pass(self):
        """Jitted routing/histogram pass shared by every session.

        One call routes ALL relations on device — `kops.map_count`, the
        map's routing in scatter-free COUNTING mode (so placement,
        capacities, and the step all see identical destinations) — and
        returns each relation's
        (n_devices, k) count matrix of routed copies per (source device,
        wrapped LOGICAL cell).  The session folds these tiny matrices on
        host: column-sums are the per-cell loads LPT placement bin-packs,
        and folding columns through a placement table yields the
        per-(source, destination device) counts that set shuffle capacities.
        This is the ONLY routing of the data prepare() performs.  Each
        device counts its own shard, which is one source row of the matrix:
        a Pallas kernel cannot be partitioned by XLA, so the pass runs under
        `shard_map` like the step."""
        if self._count_fn is None:
            k, query, specs = self.plan.k, self.plan.query, self.route_specs

            def count_matrices(*arrs):
                return tuple(kops.map_count(a, specs[rel.name], k, 1)
                             for rel, a in zip(query.relations, arrs))

            n_rel = len(query.relations)
            self._count_fn = jax.jit(jax.shard_map(
                count_matrices, mesh=self.mesh,
                in_specs=(P(self.axis),) * n_rel,
                out_specs=(P(self.axis),) * n_rel, check_vma=False))
        return self._count_fn

    def _compiled_step(self, shapes: tuple, caps: Mapping[str, int],
                       cap_out: int | None = None):
        """Compiled map→shuffle→reduce step for one (shapes, caps, cap_out)
        signature.

        The placement table is the step's FIRST argument (replicated, traced)
        — sessions with different placements share the same executable.
        `cap_out` (the join output capacity, default the config's) is part of
        the cache key so retry escalation of the reduce phase gets its own
        executable without rebuilding the executor."""
        query, cfg = self.plan.query, self.config
        n_dev = self.n_devices
        cap_out = cfg.out_capacity if cap_out is None else int(cap_out)
        key = (shapes, tuple(caps[r.name] for r in query.relations), cap_out)
        f = self._step_cache.pop(key, None)
        if f is not None:
            self._step_cache[key] = f     # re-insert: LRU, not FIFO, eviction
            self.step_hits += 1
            return f
        specs, k = self.route_specs, self.plan.k

        C = max(int(cfg.overlap_shuffle), 1)

        def step(ptable, *arrs):
            # Each phase runs under a named scope (`phase_map`,
            # `phase_exchange`, `phase_reduce`) that the compiled operations'
            # `op_name` carries, so a profile credits them to their phase
            # whatever their kernels are called.
            local = {r.name: a for r, a in zip(query.relations, arrs)}
            frags, overs = {}, []
            recv_count = jnp.int32(0)

            def pack_one(rows_in, rel_name):
                with jax.named_scope("phase_map"):
                    # Route -> fold -> sort assemble: the send buffer.
                    return kops.scatter_pack(rows_in, specs[rel_name], ptable,
                                             k, n_dev, caps[rel_name])

            def exchange(buf):
                with jax.named_scope("phase_exchange"):
                    recv = jax.lax.all_to_all(buf, self.axis, split_axis=0,
                                              concat_axis=0, tiled=True)
                    return recv.reshape(-1, recv.shape[-1])

            for rel in query.relations:
                rows_loc = local[rel.name]
                if C <= 1:
                    buf, over = pack_one(rows_loc, rel.name)
                    frag = exchange(buf)
                else:
                    # Chunked overlap: C tile-sized packs, each followed by
                    # its own all_to_all.  pack(i+1) has no data dependency
                    # on all_to_all(i), so the runtime overlaps the next
                    # chunk's pack with the in-flight exchange (each chunk's
                    # send buffer is final the moment its tiles are packed —
                    # the one-round structure makes the pipeline legal).
                    # The last tile is padded with INVALID rows up to the
                    # uniform tile shape, so every chunk shares one compiled
                    # pack signature.
                    n_loc = rows_loc.shape[0]
                    tile = -(-n_loc // C)
                    pad = C * tile - n_loc
                    if pad:
                        rows_loc = jnp.concatenate(
                            [rows_loc,
                             jnp.full((pad, rows_loc.shape[1]), INVALID,
                                      rows_loc.dtype)], axis=0)
                    parts, chunk_overs = [], []
                    for ci in range(C):
                        cbuf, cover = pack_one(
                            jax.lax.slice_in_dim(rows_loc, ci * tile,
                                                 (ci + 1) * tile, axis=0),
                            rel.name)
                        parts.append(exchange(cbuf))
                        chunk_overs.append(cover)
                    over = jnp.stack(chunk_overs).sum()
                    with jax.named_scope("phase_exchange"):
                        frag = jnp.concatenate(parts, axis=0)
                overs.append(over)
                with jax.named_scope("phase_exchange"):
                    recv_count = recv_count + (frag[:, -1] != INVALID).sum()
                frags[rel.name] = frag
            # Per-relation overflow vector: the per-(device, phase, relation)
            # coordinates CapacityOverflowError and targeted retry need.
            sh_over = jnp.stack(overs)
            with jax.named_scope("phase_reduce"):
                out, valid, j_over = _local_join(frags, query, cap_out)
            return (out[None], valid[None], sh_over[None], j_over[None],
                    recv_count[None])

        specs_in = (P(),) + tuple(P(self.axis) for _ in query.relations)
        specs_out = (P(self.axis),) * 5
        f = jax.jit(jax.shard_map(step, mesh=self.mesh, in_specs=specs_in,
                                  out_specs=specs_out, check_vma=False))
        # Bounded: one-shot run()s over ever-changing data derive fresh caps
        # each time, and each retained executable pins real memory — evict
        # least-recently-used so a long-lived executor can't grow without
        # limit (the pop/re-insert above keeps insertion order = recency).
        while len(self._step_cache) >= max(int(cfg.max_cached_steps), 1):
            self._step_cache.pop(next(iter(self._step_cache)))
            self.evicted_steps += 1
        self._step_cache[key] = f
        self.compile_count += 1
        return f

    # -- data plane ----------------------------------------------------------
    def session(self) -> "ExecutorSession":
        """New device-resident session (upload + capacities once, run many)."""
        return ExecutorSession(self)

    def run(self, data: Mapping[str, np.ndarray]) -> dict[str, np.ndarray]:
        """One-shot execute; returns {'rows', 'valid', 'shuffle_overflow',
        'join_overflow', 'recv_counts'} gathered to host."""
        return self.session().prepare(data).run_batch()

    def result_rows(self, data: Mapping[str, np.ndarray]) -> np.ndarray:
        res = self.run(data)
        if res["shuffle_overflow"].sum() or res["join_overflow"].sum():
            raise CapacityOverflowError.from_result(
                res, tuple(r.name for r in self.plan.query.relations))
        return res["rows"][res["valid"]]


class BatchResult(collections.abc.Mapping):
    """Lazily-materialized result of one `run_batch`.

    A read-only Mapping with the six keys `run_batch` has always returned
    ('rows', 'valid', 'shuffle_overflow', 'shuffle_overflow_by_rel',
    'join_overflow', 'recv_counts').  Each value is fetched from device and
    converted on FIRST access, then cached — a warm streaming loop that
    never reads a key never pays its device->host transfer, so back-to-back
    `run_batch` calls stay fully asynchronous (no host block between
    dispatches).  Reading any key still yields exactly what the old eager
    dict held, bit for bit."""

    _KEYS = ("rows", "valid", "shuffle_overflow", "shuffle_overflow_by_rel",
             "join_overflow", "recv_counts")

    def __init__(self, out, valid, sh_over, j_over, recv):
        self._out, self._valid = out, valid
        self._sh_over, self._j_over, self._recv = sh_over, j_over, recv
        self._cache: dict = {}

    def __getitem__(self, key):
        if key not in self._KEYS:
            raise KeyError(key)
        if key not in self._cache:
            if key == "rows":
                self._cache[key] = np.asarray(self._out).reshape(
                    -1, self._out.shape[-1])
            elif key == "valid":
                self._cache[key] = np.asarray(self._valid).reshape(-1)
            elif key == "shuffle_overflow_by_rel":
                self._cache[key] = np.asarray(self._sh_over, np.int64)
            elif key == "shuffle_overflow":
                self._cache[key] = self["shuffle_overflow_by_rel"].sum(axis=1)
            elif key == "join_overflow":
                self._cache[key] = np.asarray(self._j_over, np.int64)
            else:   # recv_counts
                self._cache[key] = np.asarray(self._recv)
        return self._cache[key]

    def __iter__(self):
        return iter(self._KEYS)

    def __len__(self):
        return len(self._KEYS)


class ExecutorSession:
    """Device-resident executor session: upload once, run warm many times.

    `prepare(data)` shards and uploads the relations a single time, runs ONE
    jitted routing/histogram pass (no host-side numpy re-route) whose
    (n_devices, k) per-relation count matrices drive BOTH control decisions:
    the cell placement (LPT bin-packing of per-logical-cell loads when
    k > n_devices, identity modulo otherwise — or whatever `placement=` says)
    and the per-relation shuffle capacities (worst per-(source, destination
    device) routed-copy count after folding through that placement, times
    `capacity_factor`).  The compiled step is fetched from the executor's
    cache keyed on (shapes, capacities) — the placement table is a traced
    argument, so it never forces a rebuild — and every subsequent `run_batch`
    on same-shaped input reuses the warm executable with no recompilation and
    no host round-trips.  `run_batch(chunks)` streams new tuple chunks
    through that executable: chunks smaller than the prepared shapes are
    padded up to them (staying on the warm path); a chunk LARGER than the
    prepared shapes cannot — it compiles a fresh executable for the new
    shape (a `UserWarning` flags it, `executor.compile_count` counts it)
    while keeping the prepare-time capacities, which that bigger batch may
    well overflow.  The escape hatch is to re-prepare: call
    `session.prepare(big_data)` (or a fresh `executor.session()`) so shapes,
    placement, and capacities are re-derived for the new size.  Capacities
    and placement stay frozen at prepare-time values otherwise — the
    overflow counters report when a later batch exceeds them (raise
    `capacity_factor` or re-prepare).  `count_passes` records how many
    routing/histogram passes prepare() ran — exactly one per prepared
    session (zero when both `caps` and `placement` are supplied)."""

    def __init__(self, executor: ShardedJoinExecutor):
        self.executor = executor
        self.caps: dict[str, int] = {}
        self.cap_out: int = int(executor.config.out_capacity)
        self.placement: CellPlacement | None = None
        self.count_passes = 0           # routing passes run (prepare + adapt)
        self._device_args: list[jnp.ndarray] | None = None
        self._last_args: list[jnp.ndarray] | None = None   # last executed batch
        self._ptable_dev: jnp.ndarray | None = None
        self._shapes: tuple | None = None
        self._count_mats: list[np.ndarray] | None = None
        n_rel = len(executor.plan.query.relations)
        # Cumulative fault counters over the SESSION lifetime: every attempt
        # of every chunk is counted exactly once, so retried chunks keep the
        # overflow their failed attempts saw (the delivered result's own
        # counters are zero after a successful retry).  The overflow arrays
        # accumulate LAZILY: run_batch parks each batch's (tiny) device-side
        # overflow vectors in `_pending` and the `stats` property drains them
        # on access — a warm streaming loop never blocks on device->host
        # sync just to keep counters current.
        self._stats: dict = {
            "batches": 0,               # run_batch calls (attempts included)
            "retries": 0,               # re-runs forced by overflow
            "escalations": 0,           # capacity bumps applied by retries
            "shuffle_overflow": np.zeros((executor.n_devices, n_rel),
                                         np.int64),
            "join_overflow": np.zeros(executor.n_devices, np.int64),
        }
        self._pending: list[tuple] = []     # undrained (sh_over, j_over)

    # Bound on undrained per-batch overflow vectors before run_batch folds
    # them in itself (each is two small device arrays; the bound keeps an
    # unread streaming session from pinning thousands of buffers).
    _PENDING_MAX = 64

    def _drain_stats(self) -> None:
        pending, self._pending = self._pending, []
        for sh_over, j_over in pending:
            self._stats["shuffle_overflow"] += np.asarray(sh_over, np.int64)
            self._stats["join_overflow"] += np.asarray(j_over, np.int64)

    @property
    def stats(self) -> dict:
        """Session-lifetime fault counters (see __init__); draining any
        pending per-batch overflow vectors on access."""
        self._drain_stats()
        return self._stats

    def prepare(self, data: Mapping[str, np.ndarray],
                caps: Mapping[str, int] | None = None,
                placement: CellPlacement | None = None) -> "ExecutorSession":
        """Shard + upload `data`; derive (or accept) placement + capacities.

        Derived capacities are quantized to the config's `cap_bucket`
        geometric grid (see `quantize_capacity`) so similar chunk mixes and
        escalated retries share compiled steps; explicit `caps=` are
        respected verbatim (they are the tests' and the chaos harness's
        forced-tiny-caps hook)."""
        ex = self.executor
        plan, n_dev = ex.plan, ex.n_devices
        if placement is None:
            placement = ex.placement
        if placement is not None:
            _check_placement_compat(placement, plan.k, n_dev)
        self.cap_out = int(ex.config.out_capacity)
        if not plan.residuals:
            # Provably empty join (some relation contributes zero tuples).
            # Still expose a (trivial) placement so callers reading
            # `session.placement` after prepare never see None.
            self.placement = placement or modulo_placement(plan.k, n_dev)
            self._device_args, self._shapes = [], ()
            return self
        sharded = [ex._shard(_validate_relation(r.name, data[r.name],
                                                len(r.attrs)))
                   for r in plan.query.relations]
        self._device_args = [ex._upload(s) for s in sharded]
        self._shapes = tuple(s.shape for s in sharded)
        self._count_mats = None
        counts = None
        if placement is None:
            if plan.k == n_dev:
                placement = modulo_placement(plan.k, n_dev)   # identity
            else:
                counts = self._counts()
                cell_loads = np.sum([c.sum(axis=0) for c in counts], axis=0)
                placement = place_cells(cell_loads, plan.k, n_dev,
                                        ex.placement_strategy)
        self.placement = placement
        self._ptable_dev = ex._upload_table(placement)
        if caps is None:
            counts = counts if counts is not None else self._counts()
            caps = self._derive_caps(counts, placement)
        self.caps = dict(caps)
        self._count_mats = counts       # None when caps+placement were given
        return self

    def _counts(self, args: list[jnp.ndarray] | None = None
                ) -> list[np.ndarray]:
        """Per-relation (n_devices, k) routed-copy count matrices (host)."""
        self.count_passes += 1
        args = self._device_args if args is None else args
        return [np.asarray(c, np.int64)
                for c in self.executor._count_pass()(*args)]

    def count_batch(self) -> list[np.ndarray]:
        """Count matrices of the LAST executed batch (the prepared relations
        until a chunked `run_batch` ran).  One extra scatter-free counting
        pass over the already-resident device arrays — the adaptive loop's
        per-batch observation hook (core/adapt.py): column sums are the
        observed per-cell loads a drift detector windows, and folding the
        matrices through a candidate placement re-derives capacities for a
        drift-triggered re-placement.  Increments `count_passes` (prepare's
        routes-data-once guarantee is about prepare, which still runs exactly
        one)."""
        if self._shapes is None:
            raise RuntimeError("ExecutorSession.count_batch before prepare()")
        if not self.executor.plan.residuals:
            return []
        return self._counts(self._last_args)

    def _derive_caps(self, counts: list[np.ndarray],
                     placement: CellPlacement) -> dict[str, int]:
        """Bucketed shuffle capacities: worst per-(source, destination
        device) routed-copy count after folding the count matrices through
        `placement`, times `capacity_factor`, quantized to the cap grid.

        With `overlap_shuffle = C ≥ 2` capacities are PER CHUNK: the serial
        quantized cap ceil-divided by C, so the C chunked send buffers hold
        the same total rows (and the reduce sees the same fragment shape) as
        the serial buffer would — the slack factor, not the chunking, is
        what absorbs per-chunk imbalance."""
        ex = self.executor
        plan, n_dev = ex.plan, ex.n_devices
        factor = ex.config.capacity_factor
        C = max(int(ex.config.overlap_shuffle), 1)
        # Fold logical columns onto devices: worst (source, dest) count.
        fold = np.zeros((plan.k, n_dev), np.int64)
        fold[np.arange(plan.k), placement.table] = 1
        caps = {}
        for r, c in zip(plan.query.relations, counts):
            serial = quantize_capacity(
                int(np.ceil(max(int((c @ fold).max()), 1) * factor)),
                ex.config.cap_bucket)
            caps[r.name] = -(-serial // C) if C > 1 else serial
        return caps

    def cell_loads(self) -> np.ndarray:
        """Per-logical-cell routed-copy loads (k,) from the prepare-time
        count matrices — the LPT input for degraded-mode re-folds.  Runs one
        count pass if prepare() was handed everything and never counted."""
        if self._shapes is None:
            raise RuntimeError("ExecutorSession.cell_loads before prepare()")
        if self._count_mats is None:
            self._count_mats = self._counts()
        return np.sum([c.sum(axis=0) for c in self._count_mats], axis=0)

    def refold(self, placement: CellPlacement,
               counts: list[np.ndarray] | None = None) -> "ExecutorSession":
        """Re-place logical cells WITHOUT touching shapes or resident data.

        Uploads the new table (a traced step argument — re-placing never
        recompiles) and re-derives bucketed capacities from the prepare-time
        count matrices folded through it; when the re-derived caps land in
        the already-compiled bucket (the common case — `capacity_factor`
        headroom absorbs a single device loss), the next run_batch is warm.
        This is the degraded-mode core: evicting a failed or persistently
        straggling device is `refold(lpt_placement(session.cell_loads(),
        n_devices, devices=survivors))` — the dead device keeps its mesh
        slot (SPMD collectives need it) but receives zero cells, and outputs
        stay bit-exact because correctness never depends on placement.

        `counts` overrides the capacity source with OBSERVED count matrices
        (e.g. `count_batch()` of a drifted batch) so a drift-triggered
        re-placement sizes capacities for the traffic it is adapting to; the
        prepare-time matrices stay cached for later default refolds."""
        ex = self.executor
        if self._shapes is None:
            raise RuntimeError("ExecutorSession.refold before prepare()")
        _check_placement_compat(placement, ex.plan.k, ex.n_devices)
        self.placement = placement
        if not ex.plan.residuals:
            return self
        self._ptable_dev = ex._upload_table(placement)
        if counts is None:
            if self._count_mats is None:
                self._count_mats = self._counts()
            counts = self._count_mats
        self.caps = self._derive_caps(counts, placement)
        return self

    def run_batch(self, chunks: Mapping[str, np.ndarray] | None = None,
                  rid: int | None = None) -> dict[str, np.ndarray]:
        """Execute one batch through the warm step.

        `chunks=None` re-runs the prepared relations; otherwise `chunks` maps
        every relation to a fresh tuple array (a streamed batch), padded up to
        the session shapes when smaller so the cached executable is reused.
        Returns a `BatchResult` — a Mapping with the usual six keys whose
        values materialize on first access, so the call itself never blocks
        on a device->host transfer (per-batch overflow vectors are folded
        into `session.stats` lazily too, on stats access).  `rid` tags the
        `join.ingest` and `join.dispatch` spans with the serving request."""
        if self._shapes is None:
            raise RuntimeError("ExecutorSession.run_batch before prepare()")
        ex = self.executor
        plan, query = ex.plan, ex.plan.query
        n_dev, n_rel = ex.n_devices, len(query.relations)
        if not plan.residuals:
            w = len(query.attributes)
            self._stats["batches"] += 1
            return {"rows": np.zeros((0, w), np.int32),
                    "valid": np.zeros((0,), bool),
                    "shuffle_overflow": np.zeros(n_dev, np.int64),
                    "shuffle_overflow_by_rel": np.zeros((n_dev, n_rel),
                                                        np.int64),
                    "join_overflow": np.zeros(n_dev, np.int64),
                    "recv_counts": np.zeros(n_dev, np.int64)}
        if chunks is None:
            args = self._device_args
        else:
            with span("join.ingest", rid):
                args = []
                for rel, target in zip(query.relations, self._shapes):
                    sh = ex._shard(_validate_relation(
                        rel.name, chunks[rel.name], len(rel.attrs)))
                    if sh.shape[0] < target[0]:
                        pad = np.full((target[0] - sh.shape[0], sh.shape[1]),
                                      INVALID, sh.dtype)
                        sh = np.concatenate([sh, pad])
                    args.append(ex._upload(sh))
        self._last_args = args          # count_batch()'s observation target
        shapes = tuple(a.shape for a in args)
        if shapes != self._shapes:
            # A chunk larger than the prepared shapes cannot pad down: it
            # runs off the warm path with the frozen prepare-time capacities
            # the bigger batch may overflow, compiling a new executable if
            # this shape is new.  Surface it every time — the escape hatch is
            # session.prepare(new_data) (see class docstring).
            warnings.warn(
                f"run_batch chunk shapes {shapes} exceed the prepared "
                f"{self._shapes}: running with frozen prepare-time "
                f"capacities (compiles a new step for a new shape); "
                f"re-prepare() to re-derive shapes/placement/capacities",
                UserWarning, stacklevel=2)
        with span("join.dispatch", rid):
            f = ex._compiled_step(shapes, self.caps, self.cap_out)
            out, valid, sh_over, j_over, recv = f(self._ptable_dev, *args)
        self._stats["batches"] += 1
        self._pending.append((sh_over, j_over))
        if len(self._pending) >= self._PENDING_MAX:
            self._drain_stats()
        return BatchResult(out, valid, sh_over, j_over, recv)

    def run_with_retry(self, chunks: Mapping[str, np.ndarray] | None = None,
                       policy: RetryPolicy | None = None,
                       rid: int | None = None) -> dict[str, np.ndarray]:
        """Execute one batch, healing capacity overflow by bounded retry.

        Each overflowing attempt escalates EXACTLY the failing capacities —
        the shuffle cap of each relation that dropped copies, the join
        output cap when the reduce cascade dropped results — by the policy's
        `escalation` factor, quantized to the config's `cap_bucket` grid,
        and re-runs the SAME chunk.  Grid alignment is what keeps retries
        cheap: an escalation ladder any previous chunk (or session of this
        executor) has walked hits the warm step cache and compiles nothing.
        After `policy.max_retries` escalations a still-overflowing result
        raises `RetryBudgetExceededError` with the full per-device,
        per-phase breakdown; the delivered result of a successful retry has
        zero overflow (every failed attempt's counters stay visible in
        `session.stats`).  Each attempt's overflow read, where the host
        waits for the step, is a `join.wait` span; each escalation and its
        re-run is a `join.retry` span; all are tagged with `rid`."""
        policy = policy or RetryPolicy()
        ex = self.executor
        rels = tuple(r.name for r in ex.plan.query.relations)

        def overflowed(res) -> bool:
            with span("join.wait", rid):
                return bool(res["shuffle_overflow"].sum()
                            or res["join_overflow"].sum())

        res = self.run_batch(chunks, rid)
        over = overflowed(res)
        attempt = 1
        while over:
            if attempt > policy.max_retries:
                base = CapacityOverflowError.from_result(res, rels)
                raise RetryBudgetExceededError(
                    f"retry budget exhausted: {attempt} attempts "
                    f"({policy.max_retries} retries) and the last still "
                    f"overflowed — {base}", base.shuffle_by_rel,
                    base.join_overflow, rels, attempt)
            with span("join.retry", rid):
                per_rel = res["shuffle_overflow_by_rel"].sum(axis=0)
                for i, rel in enumerate(rels):
                    if per_rel[i]:
                        self.caps[rel] = quantize_capacity(
                            int(np.ceil(self.caps[rel] * policy.escalation)),
                            ex.config.cap_bucket)
                        self.stats["escalations"] += 1
                if res["join_overflow"].sum():
                    self.cap_out = quantize_capacity(
                        int(np.ceil(self.cap_out * policy.escalation)),
                        ex.config.cap_bucket)
                    self.stats["escalations"] += 1
                self.stats["retries"] += 1
                res = self.run_batch(chunks, rid)
                over = overflowed(res)
            attempt += 1
        return res
