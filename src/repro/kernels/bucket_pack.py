"""Pallas kernel: radix/tiled counting-sort shuffle pack (map-phase hot spot).

The shuffle needs every routed tuple placed at ``buf[dest, slot]`` where
``slot`` is the tuple's STABLE rank within its destination bucket — a counting
sort.  The superseded jnp implementation materialized an O(m·k) one-hot prefix
sum (and fell back to a full argsort past k = 32), which is exactly wrong in
the large-k regime the Shares analysis targets (hundreds of reducers).

This kernel is the classic radix scheme — per-tile histogram → exclusive scan
over tiles → stable scatter — fused into ONE streaming pass: TPU grids iterate
sequentially, so the running per-bucket histogram carried in a revisited
(k + 1,) output block IS the exclusive scan over tiles (the same
read-modify-write idiom as build_probe's segment scans).  Per tile of B rows:

  base   = carry[d]                   tuples of this bucket in earlier tiles
  local  = |{j < i in tile : d_j = d_i}|   strictly-lower triangular (B, B)
           equality count — O(B) per row, independent of k
  rank   = base + local               global stable rank within the bucket
  carry += tile histogram             one-hot column sum

HBM traffic is O(m + k) (each destination read once, rank written once, one
(k + 1,) histogram) versus the O(m·k) prefix-sum matrix of the old pack; VPU
work is O(m·(B + k)) in cheap compare/reduce form with no scan over m.  The
placement is left to XLA (`bucket_pack` below): an inverse permutation is
scattered as int32 row ids and the wide rows move in a single gather.  The
executor's map no longer ranks at all: `scatter_pack_host` places its copies
with one stable sort on the destination (kernels/scatter_pack.py), as an
XLA scatter of rows serializes on the TPU.

`bucket_rank_host` is the identical algorithm phrased in vectorized XLA ops
(scatter-add tile histograms, one small (T, k + 1) cumsum, batched triangular
local ranks) for non-TPU backends, where it beats both the one-hot pack
(~10x at k = 256 on the CPU container) and the argsort fallback at every k.
`kernels.ops.bucket_pack` picks the Pallas path on TPU and the host twin
elsewhere; interpret mode remains available to validate the kernel body.

Destinations outside [0, k) (INVALID routing padding) land in a sentinel
bucket k that is sliced off the histogram and dropped by the scatter.

The executor's map phase now runs this ranking scheme fused with routing and
the placement fold inside the `map_pack` megakernel (kernels/map_pack.py),
which never materializes the routed expansion this kernel would be fed;
`bucket_pack` remains the standalone pack for pre-routed destinations and
the staged oracle path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK = 256        # Pallas tile: (block, k+1) one-hot must fit VMEM
DEFAULT_HOST_BLOCK = 32    # host twin tile: B·m compares dominate off-TPU
INVALID = -1


def _bucket_rank_kernel(d_ref, rank_ref, hist_ref, *, k1: int, block: int):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    d = d_ref[...]                                            # (block,)
    carry = hist_ref[...]                                     # (k1,) counts so far
    bins = jax.lax.broadcasted_iota(jnp.int32, (block, k1), 1)
    oh = (d[:, None] == bins).astype(jnp.int32)               # (block, k1)
    base = (oh * carry[None, :]).sum(axis=1)                  # carry[d], gather-free
    eq = d[:, None] == d[None, :]                             # (block, block)
    row = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    local = (eq & (col < row)).astype(jnp.int32).sum(axis=1)  # strict lower tri
    rank_ref[...] = base + local
    hist_ref[...] = carry + oh.sum(axis=0)


def _clamp(dest: jnp.ndarray, k: int) -> jnp.ndarray:
    """Map every out-of-range destination to the sentinel bucket k."""
    d = dest.astype(jnp.int32)
    return jnp.where((d >= 0) & (d < k), d, jnp.int32(k))


@functools.partial(jax.jit, static_argnames=("k", "block", "interpret"))
def bucket_rank(dest: jnp.ndarray, *, k: int, block: int = DEFAULT_BLOCK,
                interpret: bool = False) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(rank, hist): stable within-bucket rank per row + bucket histogram.

    dest: (m,) int; values outside [0, k) count toward no bucket (their rank
    is their position in the sentinel bucket — callers drop them).  Returns
    rank int32 (m,) and hist int32 (k,).
    """
    m = dest.shape[0]
    if m == 0:
        return jnp.zeros((0,), jnp.int32), jnp.zeros((k,), jnp.int32)
    d = jnp.pad(_clamp(dest, k), (0, -m % block), constant_values=k)
    grid = (d.shape[0] // block,)
    rank, hist = pl.pallas_call(
        functools.partial(_bucket_rank_kernel, k1=k + 1, block=block),
        grid=grid,
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=(
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((k + 1,), lambda i: (0,)),     # revisited carry block
        ),
        out_shape=(
            jax.ShapeDtypeStruct((d.shape[0],), jnp.int32),
            jax.ShapeDtypeStruct((k + 1,), jnp.int32),
        ),
        interpret=interpret,
    )(d)
    return rank[:m], hist[:k]


@functools.partial(jax.jit, static_argnames=("k", "block"))
def bucket_rank_host(dest: jnp.ndarray, *, k: int,
                     block: int = DEFAULT_HOST_BLOCK
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The kernel's algorithm in vectorized XLA — bit-identical outputs.

    Tile histograms come from one scatter-add, the over-tiles exclusive scan
    from a (T, k + 1) cumsum, local ranks from batched strictly-lower
    triangular equality counts: O(m·B + T·k) work with no O(m·k) buffer.
    """
    m = dest.shape[0]
    if m == 0:
        return jnp.zeros((0,), jnp.int32), jnp.zeros((k,), jnp.int32)
    dp = jnp.pad(_clamp(dest, k), (0, -m % block), constant_values=k)
    t = dp.shape[0] // block
    d2 = dp.reshape(t, block)
    tile = jnp.repeat(jnp.arange(t, dtype=jnp.int32), block)
    hist_t = jnp.zeros((t, k + 1), jnp.int32).at[tile, dp].add(1)
    offs = jnp.cumsum(hist_t, axis=0) - hist_t                # excl. over tiles
    eq = d2[:, :, None] == d2[:, None, :]                     # (t, B, B)
    lower = jnp.tril(jnp.ones((block, block), bool), k=-1)
    local = (eq & lower[None]).sum(-1, dtype=jnp.int32)
    base = jnp.take_along_axis(offs, d2, axis=1)
    rank = (base + local).reshape(-1)[:m]
    return rank, hist_t.sum(0)[:k]


def _assemble(dest: jnp.ndarray, rows: jnp.ndarray, rank: jnp.ndarray,
              hist: jnp.ndarray, k: int, cap: int
              ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(buf (k, cap, w), overflow) from per-row ranks — the stable scatter.

    An int32 inverse permutation is scattered first (one word per row), then
    the wide rows move in a single gather; out-of-range destinations and
    ranks beyond cap fall on the sentinel slot and vanish.
    """
    m, w = rows.shape
    d = _clamp(dest, k)
    overflow = jnp.maximum(hist - cap, 0).sum()
    flat = jnp.where((d < k) & (rank < cap), d * cap + rank, k * cap)
    inv = jnp.full((k * cap + 1,), m, jnp.int32).at[flat].set(
        jnp.arange(m, dtype=jnp.int32), mode="drop")
    rows_pad = jnp.concatenate(
        [rows, jnp.full((1, w), INVALID, rows.dtype)], axis=0)
    return rows_pad[inv[:k * cap]].reshape(k, cap, w), overflow


@functools.partial(jax.jit, static_argnames=("k", "cap", "block", "interpret"))
def bucket_pack(dest: jnp.ndarray, rows: jnp.ndarray, *, k: int, cap: int,
                block: int = DEFAULT_BLOCK, interpret: bool = False
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pallas-ranked stable pack of (dest, rows) into a (k, cap, w) buffer.

    Bit-identical to the argsort pack oracle (core.executor's
    `_pack_buckets_argsort`); overflow counts valid rows beyond any bucket's
    cap.  O(m + k) for any k — no argsort, no one-hot prefix-sum matrix.
    """
    rank, hist = bucket_rank(dest, k=k, block=block, interpret=interpret)
    return _assemble(dest, rows, rank, hist, k, cap)


@functools.partial(jax.jit, static_argnames=("k", "cap", "block"))
def bucket_pack_host(dest: jnp.ndarray, rows: jnp.ndarray, *, k: int, cap: int,
                     block: int = DEFAULT_HOST_BLOCK
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """`bucket_pack` with ranks from the XLA host twin (non-TPU hot path)."""
    rank, hist = bucket_rank_host(dest, k=k, block=block)
    return _assemble(dest, rows, rank, hist, k, cap)
