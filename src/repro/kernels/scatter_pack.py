"""Pallas scatter kernels: gather-free buffer assembly for map and reduce.

Two XLA gathers survived the megakernel era, and this module retires both:

  scatter_pack   the map phase's `map_pack` with the final `_assemble_tagged`
                 gather replaced by a carried-offset IN-KERNEL scatter: as the
                 carried-histogram rank of each routed copy is produced, the
                 assembled row (original columns + unwrapped logical-cell tag)
                 is stored straight into its ``d·cap + rank`` slot of the
                 flat shuffle buffer with a dynamic store — no inverse
                 permutation, no gather, the buffer is final the moment its
                 tile is packed (what makes the executor's chunked
                 map↔all-to-all overlap legal).
  expand_rows    the reduce side's prefix-sum expansion: `_local_join` turned
                 each probe's (counts, lo, perm) into output rows by GATHERING
                 ``left[li]`` / ``right[perm[inner]]`` per output slot.  The
                 kernel reformulates both lookups as one-hot contractions
                 (MXU dots, the `fold_cells` idiom) over a right side
                 pre-permuted by ONE scatter — the expansion path lowers to
                 dynamic slices and dots, zero HLO gathers.

Kernel layout, scatter_pack: route → one-hot placement fold → carried-
histogram rank exactly as `_map_pack_kernel`, then a `fori_loop` of dynamic
stores writes each copy's assembled ``(w + 1,)`` row at ``pl.ds(slot, 1)`` of
a revisited ``(n_dev·cap + 1, w + 1)`` output block (initialized to INVALID on
the first grid step).  Invalid copies and rank overflow land on the trash row
``n_dev·cap``, sliced off outside.  Valid (device, rank) slots are globally
unique, so the sequential grid makes the stores race-free.  On a real TPU the
flat buffer block is the VMEM budget to watch — cap · n_dev · (w + 1) words;
the async-DMA HBM variant is the ROADMAP follow-up.

`scatter_pack_host` / `expand_rows_host` are the bit-identical vectorized-XLA
twins, which the main path runs on every backend (`kernels.ops.TPU_IMPL`).
The map twin assembles with ONE stable `lax.sort` keyed on the destination,
the copies' columns and tag riding as operands: a copy's place in its
bucket is its stable rank, so there is no rank pass, and each device's
`cap` rows are a dynamic slice of the sorted copies at the histogram's
exclusive prefix.  The assemble lowers with no gather and the twin with no
scatter, which `scripts/check_hlo.py` pins: the row scatter onto a trash
row that it replaced serialized on the TPU.  `expand_rows_host` finds
each slot's left row by merging the slots into the group offsets with two
sorts and a scan (`join_probe.sorted_locate`; a binary search is a loop of
gathers, slow on a TPU, and `scripts/check_hlo.py` pins its absence), then
gathers the rows (the gather-free contract is the KERNEL path's).
`scatter_pack_ref` / `expand_rows_ref` in kernels/ref.py are the
dead-simple oracles.

Outputs are bit-identical to `map_pack` / the `_local_join` expansion gather
they replace; `kernels.ops` dispatches Pallas on TPU, host twins elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .join_probe import sorted_locate
from .map_pack import (DEFAULT_BLOCK_COPIES, RouteSpec, _empty_pack,
                       _route_block, _row_block, route_fanout)

INVALID = -1

# Output slots per expand_rows tile; auto-shrunk so the (block, n_l) and
# (block, n_r) one-hot contraction operands stay within the VMEM budget.
DEFAULT_EXPAND_BLOCK = 256


def _expand_block(block: int, n_l: int, n_r: int) -> int:
    """Shrink the expansion tile so the two one-hots fit ~4 MiB."""
    return max(8, min(block, (1 << 20) // max(n_l + n_r, 1)))


# ---------------------------------------------------------------------------
# Map side: scatter_pack
# ---------------------------------------------------------------------------

def _scatter_assemble_host(rows: jnp.ndarray, tag: jnp.ndarray,
                           d: jnp.ndarray, n_dev: int, cap: int,
                           fanout: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(buf (n_dev, cap, w+1), overflow) from per-copy destinations — ONE
    stable sort.  The copies' columns and tag ride as operands of a
    `lax.sort` keyed on the destination `d` (invalid copies carry the
    sentinel ``n_dev`` and sort last); stability keeps each bucket in copy
    order, so a copy's place in its bucket IS its stable rank.  Device dd's
    rows are then the `cap` sorted rows from the exclusive prefix of the
    bucket histogram, with rows past ``min(hist[dd], cap)`` reset to
    INVALID.  Bit-identical to `_assemble_tagged`, with no rank pass, zero
    gather and zero scatter ops in the lowered HLO (`scripts/check_hlo.py`
    pins both)."""
    n, w = rows.shape
    m = n * fanout
    cols = [jnp.broadcast_to(rows[:, c, None], (n, fanout)).reshape(m)
            for c in range(w)]
    _, *srt = jax.lax.sort((d, *cols, tag.astype(rows.dtype)), num_keys=1,
                           is_stable=True)
    # Histogram as a compare-and-sum over the n_dev real buckets (no
    # scatter-add); the sentinel bucket needs no count.
    hist = (d[None, :] == jnp.arange(n_dev, dtype=d.dtype)[:, None]).sum(
        axis=1, dtype=jnp.int32)
    overflow = jnp.maximum(hist - cap, 0).sum()
    start = jnp.cumsum(hist) - hist
    # INVALID tail so a slice starting anywhere in [0, m] never clamps.
    pad = jnp.full((cap,), INVALID, rows.dtype)
    srt = [jnp.concatenate([col, pad]) for col in srt]
    j = jnp.arange(cap, dtype=jnp.int32)
    bufs = []
    for dd in range(n_dev):
        keep = j < jnp.minimum(hist[dd], cap)
        bufs.append(jnp.stack(
            [jnp.where(keep, jax.lax.dynamic_slice(col, (start[dd],), (cap,)),
                       INVALID) for col in srt], axis=1))
    return jnp.stack(bufs), overflow


def _scatter_pack_kernel(rows_ref, table_ref, buf_ref, hist_ref, *,
                         routes, k, n_dev, cap, block):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        buf_ref[...] = jnp.full_like(buf_ref, INVALID)
        hist_ref[...] = jnp.zeros_like(hist_ref)

    rows = rows_ref[...]                                    # (block, w)
    w = rows.shape[1]
    logical, valid = _route_block(rows, routes, k)          # (block, F)
    fanout = logical.shape[1]
    c = block * fanout                                      # copies this tile
    vflat = valid.reshape(c)
    lflat = logical.reshape(c)
    wrapped = jnp.where(vflat, lflat % k, 0)
    # Placement fold: one-hot contraction over the small k axis (the
    # fold_cells idiom) instead of a vector gather.
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
    rowi = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    coli = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    local = (eq & (coli < rowi)).astype(jnp.int32).sum(axis=1)
    rank = base + local
    hist_ref[...] = carry + oh_d.sum(axis=0)
    # The in-kernel scatter: each copy's assembled row goes straight to its
    # d·cap + rank slot the moment its rank exists; invalid copies and rank
    # overflow hit the trash row.  Dynamic stores, not a gather/scatter pair.
    expanded = jnp.broadcast_to(
        rows[:, None, :], (block, fanout, w)).reshape(c, w)
    vals = jnp.concatenate([expanded, lflat[:, None]], axis=1)  # (c, w+1)
    slot = jnp.where((d < n_dev) & (rank < cap), d * cap + rank,
                     jnp.int32(n_dev * cap))

    def body(j, _):
        s = jax.lax.dynamic_slice(slot, (j,), (1,))[0]
        v = jax.lax.dynamic_slice(vals, (j, 0), (1, w + 1))
        buf_ref[pl.ds(s, 1), :] = v
        return 0

    jax.lax.fori_loop(0, c, body, 0)


@functools.partial(jax.jit, static_argnames=("routes", "k", "n_dev", "cap",
                                             "block_copies", "interpret"))
def scatter_pack(rows: jnp.ndarray, ptable: jnp.ndarray, *,
                 routes: RouteSpec, k: int, n_dev: int, cap: int,
                 block_copies: int = DEFAULT_BLOCK_COPIES,
                 interpret: bool = False) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused map phase with in-kernel scatter assembly: rows (n, w) ->
    ((n_dev, cap, w+1) shuffle buffer, overflow).

    Same contract as `map_pack` (bit-identical output) minus the
    `_assemble_tagged` gather: the revisited flat output block IS the
    shuffle buffer, written by dynamic stores as ranks are produced.
    """
    n, w = rows.shape
    fanout = route_fanout(routes)
    if n == 0 or fanout == 0:
        return _empty_pack(w, n_dev, cap, rows.dtype)
    block = _row_block(fanout, block_copies)
    rows_p = jnp.pad(rows, ((0, -n % block), (0, 0)),
                     constant_values=INVALID)
    grid = (rows_p.shape[0] // block,)
    buf, hist = pl.pallas_call(
        functools.partial(_scatter_pack_kernel, routes=routes, k=k,
                          n_dev=n_dev, cap=cap, block=block),
        grid=grid,
        in_specs=[pl.BlockSpec((block, w), lambda i: (i, 0)),
                  pl.BlockSpec((k,), lambda i: (0,))],
        out_specs=(
            pl.BlockSpec((n_dev * cap + 1, w + 1), lambda i: (0, 0)),
            pl.BlockSpec((n_dev + 1,), lambda i: (0,)),     # revisited carry
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n_dev * cap + 1, w + 1), jnp.int32),
            jax.ShapeDtypeStruct((n_dev + 1,), jnp.int32),
        ),
        interpret=interpret,
    )(rows_p, ptable)
    overflow = jnp.maximum(hist[:n_dev] - cap, 0).sum()
    return buf[:n_dev * cap].reshape(n_dev, cap, w + 1), overflow


@functools.partial(jax.jit, static_argnames=("routes", "k", "n_dev", "cap"))
def scatter_pack_host(rows: jnp.ndarray, ptable: jnp.ndarray, *,
                      routes: RouteSpec, k: int, n_dev: int, cap: int
                      ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """`scatter_pack` in vectorized XLA — bit-identical outputs.

    Routing and the placement fold as in `map_pack_host`; the buffer is then
    assembled by `_scatter_assemble_host`'s one stable sort on the
    destination, which yields the stable ranks by position, so there is no
    `bucket_rank_host` pass and no scatter.
    """
    n, w = rows.shape
    fanout = route_fanout(routes)
    if n == 0 or fanout == 0:
        return _empty_pack(w, n_dev, cap, rows.dtype)
    logical, valid = _route_block(rows, routes, k)          # (n, F)
    wrapped = jnp.where(valid, logical % k, 0)
    d = jnp.where(valid, ptable[wrapped], n_dev).reshape(-1)
    return _scatter_assemble_host(rows, logical.reshape(-1), d, n_dev, cap,
                                  fanout)


# ---------------------------------------------------------------------------
# Reduce side: expand_rows
# ---------------------------------------------------------------------------

def _expand_rows_kernel(left_ref, right_ref, off_ref, lo_ref, out_ref, *,
                        block, n_l, n_r):
    b = pl.program_id(0)
    t = b * block + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)[:, 0]
    off = off_ref[...]                                      # (n_l,)
    lo = lo_ref[...]                                        # (n_l,)
    left = left_ref[...]                                    # (n_l, wl)
    right = right_ref[...]                                  # (n_r, wr) packed
    # li = searchsorted(off, t, 'right') - 1 as a dense compare-count, then
    # every per-slot lookup as a one-hot contraction (MXU dot) — no gather.
    le = (off[None, :] <= t[:, None]).astype(jnp.int32)     # (block, n_l)
    li = jnp.clip(le.sum(axis=1) - 1, 0, n_l - 1)
    oh_l = (li[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (block, n_l), 1)).astype(jnp.int32)
    lvals = jax.lax.dot_general(
        oh_l, left, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)                   # left[li]
    lo_li = (oh_l * lo[None, :]).sum(axis=1)
    off_li = (oh_l * off[None, :]).sum(axis=1)
    inner = jnp.clip(lo_li + t - off_li, 0, n_r - 1)
    oh_r = (inner[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (block, n_r), 1)).astype(jnp.int32)
    rvals = jax.lax.dot_general(
        oh_r, right, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)                   # right_g[inner]
    out_ref[...] = jnp.concatenate([lvals, rvals], axis=1)


@functools.partial(jax.jit, static_argnames=("cap", "block", "interpret"))
def expand_rows(left: jnp.ndarray, right: jnp.ndarray, counts: jnp.ndarray,
                lo: jnp.ndarray, perm: jnp.ndarray, *, cap: int,
                block: int = DEFAULT_EXPAND_BLOCK, interpret: bool = False
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Prefix-sum expansion of a probe result, gather-free.

    From a probe pass's (counts (n_l,), lo (n_l,), perm (n_r,)) — per-left
    match counts, group starts, and the grouped right permutation — produce
    (out (cap, wl + wr), valid (cap,)): output slot t concatenates
    ``left[li(t)]`` and ``right[perm[lo[li] + t - off[li]]]`` in (left row,
    right arrival) order, exactly the `_local_join` expansion contract.

    The right side is pre-permuted by ONE scatter (``right_g[p] =
    right[perm[p]]``), so the kernel needs no indexed loads at all: the
    slot → left-row map is a dense compare-count and both row lookups are
    one-hot dot contractions.  `perm` must be a permutation of [0, n_r) —
    both probe paths guarantee it.
    """
    n_l, wl = left.shape
    n_r, wr = right.shape
    if n_l == 0 or n_r == 0:
        return (jnp.full((cap, wl + wr), INVALID, left.dtype),
                jnp.zeros((cap,), bool))
    off = (jnp.cumsum(counts) - counts).astype(jnp.int32)
    idx = jnp.arange(n_r, dtype=jnp.int32)
    invp = jnp.zeros((n_r,), jnp.int32).at[perm].set(idx)
    right_g = jnp.zeros_like(right).at[invp].set(right)
    bt = _expand_block(block, n_l, n_r)
    cap_p = cap + (-cap % bt)
    grid = (cap_p // bt,)
    out = pl.pallas_call(
        functools.partial(_expand_rows_kernel, block=bt, n_l=n_l, n_r=n_r),
        grid=grid,
        in_specs=[pl.BlockSpec((n_l, wl), lambda i: (0, 0)),
                  pl.BlockSpec((n_r, wr), lambda i: (0, 0)),
                  pl.BlockSpec((n_l,), lambda i: (0,)),
                  pl.BlockSpec((n_l,), lambda i: (0,))],
        out_specs=pl.BlockSpec((bt, wl + wr), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((cap_p, wl + wr), jnp.int32),
        interpret=interpret,
    )(left, right_g, off, lo.astype(jnp.int32))
    valid = jnp.arange(cap, dtype=jnp.int32) < counts.sum()
    return out[:cap], valid


@functools.partial(jax.jit, static_argnames=("cap",))
def expand_rows_host(left: jnp.ndarray, right: jnp.ndarray,
                     counts: jnp.ndarray, lo: jnp.ndarray, perm: jnp.ndarray,
                     *, cap: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """`expand_rows` in vectorized XLA — bit-identical outputs.

    Slot t's left row li is the last row whose group offset is at most t,
    found by a merge sort of offsets and slots (`sorted_locate`) rather
    than a binary search; the rows are then gathered.  The gather-free
    contract belongs to the kernel lowering.
    """
    n_l, wl = left.shape
    n_r, wr = right.shape
    if n_l == 0 or n_r == 0:
        return (jnp.full((cap, wl + wr), INVALID, left.dtype),
                jnp.zeros((cap,), bool))
    off = jnp.cumsum(counts) - counts
    t = jnp.arange(cap, dtype=jnp.int32)
    li = jnp.clip(sorted_locate(off, t, side="right")[0] - 1, 0, n_l - 1)
    ri = perm[jnp.clip(lo[li] + t - off[li], 0, n_r - 1)]
    out = jnp.concatenate([left[li], right[ri]], axis=1)
    return out, t < counts.sum()
