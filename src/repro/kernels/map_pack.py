"""Pallas megakernel: the whole map phase — route → fold → pack — in one pass.

The staged map phase paid for the routed expansion three times: route_cells
materialized a fanout-expanded ``(n·F, w+1)`` tagged-rows buffer in HBM,
`fold_cells` re-read every destination for the placement lookup, and
`bucket_pack` streamed the expansion a third time to rank and scatter it.
This kernel fuses all of it per input tile of rows:

  member   §3 type constraints (eq / not-in against the HH values) and the
           INVALID-padding mask, per residual route;
  route    multiply-shift hash of every hashed attribute, mixed-radix
           combine, static replication offsets — the unwrapped LOGICAL cell
           id per (row, copy), wrapped modulo k for the destination;
  fold     placement-table lookup (one-hot contraction over the small k
           axis, the `fold_cells` idiom) — wrapped cell -> physical device;
  rank     the carried-histogram trick of `bucket_pack`: TPU grids iterate
           sequentially, so a revisited ``(n_devices + 1,)`` output block
           accumulates the per-device histogram and each copy reads its
           stable within-bucket rank as carry + strict-lower-triangular
           local count.

The copies never leave VMEM as wide rows: the kernel emits three int32
streams per copy (physical device, unwrapped logical tag, rank) plus the
histogram, and `_assemble_tagged` scatters an int32 inverse permutation and
gathers the ORIGINAL (n, w) rows straight into the ``(n_devices, cap, w+1)``
shuffle buffer — the ``(n·F, w+1)`` expansion is never materialized, and the
three kernel launches of the staged path become one streaming pass.  Output
is bit-identical to route_cells + fold_cells + bucket_pack (the staged path
survives in core.executor as the exactness oracle).

`map_count` is the same pass in scatter-free COUNTING mode: it accumulates
only the ``(n_src, k)`` histogram of routed copies per (source device,
wrapped logical cell) — the control-plane matrix `ExecutorSession.prepare`
needs for LPT cell loads and shuffle capacities.  Prepare therefore routes
each relation's data exactly once, with no placement table and no scatter.

`map_pack_host` / `map_count_host` are the bit-identical vectorized-XLA
twins used off-TPU (the same split as `bucket_rank_host`); the route recipe
is a static nested tuple (see `RouteSpec`), so it compiles into the kernel
body — shares are powers of two and the HH constraint sets are tiny, so
constraints unroll into scalar compares.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .bucket_pack import DEFAULT_HOST_BLOCK, bucket_rank_host
from .ref import MULT

# Copies (row × route-rep) per Pallas tile: the (copies, copies) triangular
# rank matrix and the (copies, k) fold one-hot must both fit VMEM.
DEFAULT_BLOCK_COPIES = 256
# Rows per `map_count` tile: 1-D int32 operands live in tiles of 1024 on TPU.
COUNT_BLOCK = 1024
INVALID = -1

# One route = (hashed, rep_strides, offset, eq_constraints, notin_constraints)
# with hashed = ((col, seed, share, stride), ...) — the static recipe of
# core.executor._Route, flattened to hashable tuples so it can be a jit
# static argument.  All routes of a relation share the wrap modulus k.
RouteSpec = tuple


def route_fanout(routes: RouteSpec) -> int:
    """Total copies per input row over every residual route."""
    return sum(len(reps) for _, reps, _, _, _ in routes)


def _route_cols(cols, routes: RouteSpec, k: int
                ) -> tuple[list[jnp.ndarray], list[jnp.ndarray]]:
    """Per copy, (logical int32, member bool) over the rows of `cols`.

    `cols[c]` holds column c of a block of rows, in any shape shared by all
    columns; every output has that shape.  Shared by the kernel bodies and
    the host twins: pure jnp, constraints and hashes unrolled from the static
    recipe, logical ids masked to INVALID on non-members.  The copy order
    (routes concatenated, reps in rep_strides order) reproduces the staged
    `_route_relation` order.
    """
    member_base = cols[0] != INVALID
    logical_cols, valid_cols = [], []
    for hashed, reps, offset, eqs, notins in routes:
        member = member_base
        for col, val in eqs:
            member &= cols[col] == val
        for col, vals in notins:
            for v in vals:                      # tiny static HH set: unroll
                member &= cols[col] != v
        base = jnp.zeros_like(cols[0])
        for col, seed, share, stride in hashed:
            if share == 1:
                continue
            b = share.bit_length() - 1
            h = (cols[col].astype(jnp.uint32) * jnp.uint32(seed)) \
                * jnp.uint32(MULT)
            base = base + (h >> jnp.uint32(32 - b)).astype(jnp.int32) * stride
        for r in reps:
            logical_cols.append(
                jnp.where(member, base + (r + offset), INVALID))
            valid_cols.append(member)
    return logical_cols, valid_cols


def _route_block(rows: jnp.ndarray, routes: RouteSpec, k: int
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(logical (n, F) int32, valid (n, F) bool) for a block of rows: the
    copies of `_route_cols` stacked along axis 1, so flattening axis 1 gives
    the staged copy order."""
    logical, valid = _route_cols([rows[:, c] for c in range(rows.shape[1])],
                                 routes, k)
    return jnp.stack(logical, axis=1), jnp.stack(valid, axis=1)


def _assemble_tagged(rows: jnp.ndarray, tag: jnp.ndarray, d: jnp.ndarray,
                     rank: jnp.ndarray, hist: jnp.ndarray, n_dev: int,
                     cap: int, fanout: int
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(buf (n_dev, cap, w+1), overflow) from per-copy streams — the final
    gather.  The inverse permutation is scattered as int32 copy indices; the
    wide values then move ONCE, straight from the original (n, w) rows
    (src row = copy // fanout — the expansion is never materialized) with the
    unwrapped logical tag appended as the hidden last column."""
    n, w = rows.shape
    m = n * fanout
    overflow = jnp.maximum(hist - cap, 0).sum()
    slot = jnp.where((d < n_dev) & (rank < cap), d * cap + rank, n_dev * cap)
    inv = jnp.full((n_dev * cap + 1,), m, jnp.int32).at[slot].set(
        jnp.arange(m, dtype=jnp.int32), mode="drop")[:n_dev * cap]
    rows_pad = jnp.concatenate(
        [rows, jnp.full((1, w), INVALID, rows.dtype)], axis=0)
    tag_pad = jnp.concatenate(
        [tag.astype(rows.dtype), jnp.full((1,), INVALID, rows.dtype)])
    vals = rows_pad[inv // fanout]        # sentinel m // fanout == n: padding
    buf = jnp.concatenate([vals, tag_pad[inv][:, None]], axis=1)
    return buf.reshape(n_dev, cap, w + 1), overflow


def _empty_pack(w: int, n_dev: int, cap: int, dtype
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    return (jnp.full((n_dev, cap, w + 1), INVALID, dtype), jnp.int32(0))


def count_scatter(dest: jnp.ndarray, n: int, k: int, n_src: int
                  ) -> jnp.ndarray:
    """(n_src, k) scatter-add histogram of flat per-copy destinations.

    The counting mode's semantic contract, shared by `map_count_host` and
    the executor's staged `_count_matrix` oracle: `dest` holds the wrapped
    cell ids of the n·F copies of n rows in row-major copy order; row i is
    source i // (n // n_src); dest < 0 copies (and sources beyond n_src on
    non-divisible n, via scatter OOB-drop) count toward nothing.
    """
    fan = dest.shape[0] // max(n, 1)
    src = jnp.repeat(jnp.arange(n, dtype=jnp.int32) // max(n // n_src, 1),
                     fan)
    idx = jnp.where(dest >= 0, src * k + dest, n_src * k)
    counts = jnp.zeros((n_src * k + 1,), jnp.int32).at[idx].add(1)
    return counts[:n_src * k].reshape(n_src, k)


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _map_pack_kernel(rows_ref, table_ref, d_ref, tag_ref, rank_ref, hist_ref,
                     *, routes, k, n_dev, block):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    rows = rows_ref[...]                                    # (block, w)
    logical, valid = _route_block(rows, routes, k)          # (block, F)
    c = logical.shape[0] * logical.shape[1]                 # copies this tile
    vflat = valid.reshape(c)
    wrapped = jnp.where(vflat, logical.reshape(c) % k, 0)
    # Placement fold: one-hot contraction over the small k axis (VPU
    # compare+select, the fold_cells idiom) instead of a vector gather.
    table = table_ref[...]                                  # (k,) whole table
    oh_k = wrapped[:, None] == jax.lax.broadcasted_iota(jnp.int32, (c, k), 1)
    phys = jnp.sum(jnp.where(oh_k, table[None, :], 0), axis=1,
                   dtype=jnp.int32)
    d = jnp.where(vflat, phys, jnp.int32(n_dev))            # sentinel bucket
    # Stable rank: carried histogram + strict-lower-triangular local count.
    carry = hist_ref[...]                                   # (n_dev + 1,)
    bins = jax.lax.broadcasted_iota(jnp.int32, (c, n_dev + 1), 1)
    oh_d = (d[:, None] == bins).astype(jnp.int32)
    base = (oh_d * carry[None, :]).sum(axis=1)              # carry[d]
    eq = d[:, None] == d[None, :]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    local = (eq & (col < row)).astype(jnp.int32).sum(axis=1)
    d_ref[...] = d
    tag_ref[...] = logical.reshape(c)
    rank_ref[...] = base + local
    hist_ref[...] = carry + oh_d.sum(axis=0)


def _map_count_kernel(*refs, routes, k, n_src, rows_per_src, block):
    *col_refs, counts_ref = refs
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    # One lane-dense (block,) vector per column: no (block, w) row tile,
    # whose w < 128 lanes would be padded to 128 in HBM and VMEM.
    logical, valid = _route_cols([r[...] for r in col_refs], routes, k)
    # Per-row wrapped-cell histogram (block, k), summed over the F copies.
    cells = jax.lax.broadcasted_iota(jnp.int32, (block, k), 1)
    cnt = jnp.zeros((block, k), jnp.int32)
    for lg, v in zip(logical, valid):
        wrapped = jnp.where(v, lg % k, -1)      # non-members match no cell
        cnt = cnt + (wrapped[:, None] == cells).astype(jnp.int32)
    # Fold rows onto their source device with a masked sublane sum per
    # source (VPU only; src beyond range matches no source).
    idx = b * block + jax.lax.broadcasted_iota(jnp.int32, (block, k), 0)
    src = idx // rows_per_src
    for s in range(n_src):
        counts_ref[s:s + 1, :] += jnp.sum(jnp.where(src == s, cnt, 0),
                                          axis=0, keepdims=True)


def _row_block(fanout: int, block_copies: int) -> int:
    """Rows per tile so copies-per-tile stays near the VMEM budget, in
    multiples of 8 (the TPU's sublane tiling)."""
    return max(8, (block_copies // max(fanout, 1)) // 8 * 8)


@functools.partial(jax.jit, static_argnames=("routes", "k", "n_dev", "cap",
                                             "block_copies", "interpret"))
def map_pack(rows: jnp.ndarray, ptable: jnp.ndarray, *, routes: RouteSpec,
             k: int, n_dev: int, cap: int,
             block_copies: int = DEFAULT_BLOCK_COPIES,
             interpret: bool = False) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused map phase: rows (n, w) -> ((n_dev, cap, w+1) buffer, overflow).

    rows int32 with -1-padding rows; ptable (k,) int32 placement table
    (`CellPlacement.table`, replicated); routes the static `RouteSpec`
    recipe whose cells wrap modulo `k`.  Bit-identical to the staged
    route_cells -> fold_cells -> bucket_pack composition.
    """
    n, w = rows.shape
    fanout = route_fanout(routes)
    if n == 0 or fanout == 0:
        return _empty_pack(w, n_dev, cap, rows.dtype)
    block = _row_block(fanout, block_copies)
    rows_p = jnp.pad(rows, ((0, -n % block), (0, 0)),
                     constant_values=INVALID)
    mpad = rows_p.shape[0] * fanout
    grid = (rows_p.shape[0] // block,)
    d, tag, rank, hist = pl.pallas_call(
        functools.partial(_map_pack_kernel, routes=routes, k=k, n_dev=n_dev,
                          block=block),
        grid=grid,
        in_specs=[pl.BlockSpec((block, w), lambda i: (i, 0)),
                  pl.BlockSpec((k,), lambda i: (0,))],
        out_specs=(
            pl.BlockSpec((block * fanout,), lambda i: (i,)),
            pl.BlockSpec((block * fanout,), lambda i: (i,)),
            pl.BlockSpec((block * fanout,), lambda i: (i,)),
            pl.BlockSpec((n_dev + 1,), lambda i: (0,)),     # revisited carry
        ),
        out_shape=(
            jax.ShapeDtypeStruct((mpad,), jnp.int32),
            jax.ShapeDtypeStruct((mpad,), jnp.int32),
            jax.ShapeDtypeStruct((mpad,), jnp.int32),
            jax.ShapeDtypeStruct((n_dev + 1,), jnp.int32),
        ),
        interpret=interpret,
    )(rows_p, ptable)
    m = n * fanout
    return _assemble_tagged(rows, tag[:m], d[:m], rank[:m], hist[:n_dev],
                            n_dev, cap, fanout)


@functools.partial(jax.jit, static_argnames=("routes", "k", "n_dev", "cap",
                                             "block"))
def map_pack_host(rows: jnp.ndarray, ptable: jnp.ndarray, *,
                  routes: RouteSpec, k: int, n_dev: int, cap: int,
                  block: int = DEFAULT_HOST_BLOCK
                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The megakernel's algorithm in vectorized XLA — bit-identical outputs.

    Routing and the placement fold are one fused elementwise pass (gather
    fold instead of the one-hot contraction), ranks come from
    `bucket_rank_host`, and the same `_assemble_tagged` gather builds the
    buffer straight from the original rows — still no (n·F, w+1) expansion.
    """
    n, w = rows.shape
    fanout = route_fanout(routes)
    if n == 0 or fanout == 0:
        return _empty_pack(w, n_dev, cap, rows.dtype)
    logical, valid = _route_block(rows, routes, k)          # (n, F)
    wrapped = jnp.where(valid, logical % k, 0)
    phys = jnp.where(valid, ptable[wrapped], INVALID).reshape(-1)
    rank, hist = bucket_rank_host(phys, k=n_dev, block=block)
    d = jnp.where(phys >= 0, phys, jnp.int32(n_dev))
    return _assemble_tagged(rows, logical.reshape(-1), d, rank, hist,
                            n_dev, cap, fanout)


@functools.partial(jax.jit, static_argnames=("routes", "k", "n_src",
                                             "interpret"))
def map_count(rows: jnp.ndarray, *, routes: RouteSpec, k: int, n_src: int,
              interpret: bool = False) -> jnp.ndarray:
    """Counting mode: (n_src, k) int32 routed copies per (source, cell).

    The same streaming pass as `map_pack` with the fold, rank, and scatter
    stripped out — rows [i·(n/n_src), (i+1)·(n/n_src)) count as source i,
    matching the executor's sharded layout.  No placement table needed: the
    histogram is over wrapped LOGICAL cells, exactly what LPT placement and
    the capacity fold consume.  Each column enters as its own 1-D operand in
    tiles of COUNT_BLOCK rows, the tiling the TPU keeps 1-D int32 arrays in.
    """
    n, w = rows.shape
    fanout = route_fanout(routes)
    if n == 0 or fanout == 0:
        return jnp.zeros((n_src, k), jnp.int32)
    block = COUNT_BLOCK
    rows_p = jnp.pad(rows, ((0, -n % block), (0, 0)),
                     constant_values=INVALID)
    grid = (rows_p.shape[0] // block,)
    return pl.pallas_call(
        functools.partial(_map_count_kernel, routes=routes, k=k, n_src=n_src,
                          rows_per_src=max(n // n_src, 1), block=block),
        grid=grid,
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))] * w,
        out_specs=pl.BlockSpec((n_src, k), lambda i: (0, 0)),  # carry block
        out_shape=jax.ShapeDtypeStruct((n_src, k), jnp.int32),
        interpret=interpret,
    )(*(rows_p[:, c] for c in range(w)))


@functools.partial(jax.jit, static_argnames=("routes", "k", "n_src"))
def map_count_host(rows: jnp.ndarray, *, routes: RouteSpec, k: int,
                   n_src: int) -> jnp.ndarray:
    """`map_count` in vectorized XLA: one scatter-add, no expansion."""
    n, _ = rows.shape
    fanout = route_fanout(routes)
    if n == 0 or fanout == 0:
        return jnp.zeros((n_src, k), jnp.int32)
    logical, valid = _route_block(rows, routes, k)
    wrapped = jnp.where(valid, logical % k, INVALID).reshape(-1)
    return count_scatter(wrapped, n, k, n_src)
