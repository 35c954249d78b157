"""The map phase's router, and its counting pass: the `map_count` kernel.

A relation's rows are routed to the logical cells of every residual join in
one elementwise pass (`_route_cols`): per residual route,

  member   §3 type constraints (eq / not-in against the HH values) and the
           INVALID-padding mask;
  route    multiply-shift hash of every hashed attribute, mixed-radix
           combine, static replication offsets — the unwrapped LOGICAL cell
           id per (row, copy), wrapped modulo k for the destination.

The route recipe is a static nested tuple (see `RouteSpec`), so it compiles
into the program — shares are powers of two and the HH constraint sets are
tiny, so constraints unroll into scalar compares.  The map itself
(`kernels/scatter_pack.py` `scatter_pack_host`) routes with `_route_block`
and assembles the shuffle buffer by one stable sort.

`map_count` is the same routing in COUNTING mode: it accumulates only the
``(n_src, k)`` histogram of routed copies per (source device, wrapped logical
cell) — the control-plane matrix `ExecutorSession.prepare` needs for LPT
cell loads and shuffle capacities.  Prepare therefore routes each relation's
data exactly once, with no placement table and no scatter.  It is the one
Pallas kernel the chip runs (`kernels/ops.py`); `map_count_host` is its
bit-identical vectorized-XLA twin, run on every other backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import MULT

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
    columns; every output has that shape.  Shared by the `map_count` kernel
    body, its twin and the map: pure jnp, constraints and hashes unrolled
    from the static recipe, logical ids masked to INVALID on non-members.
    Copies come in route order, and within a route in rep_strides order.
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
    copies of `_route_cols` stacked along axis 1, so flattening the result
    row-major gives each row's copies in turn."""
    logical, valid = _route_cols([rows[:, c] for c in range(rows.shape[1])],
                                 routes, k)
    return jnp.stack(logical, axis=1), jnp.stack(valid, axis=1)


# ---------------------------------------------------------------------------
# The counting pass
# ---------------------------------------------------------------------------

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


@functools.partial(jax.jit, static_argnames=("routes", "k", "n_src",
                                             "interpret"))
def map_count(rows: jnp.ndarray, *, routes: RouteSpec, k: int, n_src: int,
              interpret: bool = False) -> jnp.ndarray:
    """Counting mode: (n_src, k) int32 routed copies per (source, cell).

    The map's routing with only a histogram kept: no fold, no buffer — rows
    [i·(n/n_src), (i+1)·(n/n_src)) count as source i,
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
    """`map_count` in vectorized XLA: one scatter-add, no expansion.

    Copy j of row i counts toward source i // (n // n_src); non-member copies
    (and sources beyond n_src on non-divisible n, via the scatter's
    out-of-bounds drop) count toward nothing.
    """
    n, _ = rows.shape
    fanout = route_fanout(routes)
    if n == 0 or fanout == 0:
        return jnp.zeros((n_src, k), jnp.int32)
    logical, valid = _route_block(rows, routes, k)
    dest = jnp.where(valid, logical % k, INVALID).reshape(-1)
    src = jnp.repeat(jnp.arange(n, dtype=jnp.int32) // max(n // n_src, 1),
                     fanout)
    idx = jnp.where(dest >= 0, src * k + dest, n_src * k)
    counts = jnp.zeros((n_src * k + 1,), jnp.int32).at[idx].add(1)
    return counts[:n_src * k].reshape(n_src, k)
