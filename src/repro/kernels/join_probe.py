"""Pallas kernels: reduce-phase radix hash join (the `join_probe` family).

A reducer cell's local join cascades over its fragments; every cascade step
must produce, per left (accumulator) row, its matching right rows in ARRIVAL
order — the load-bearing output contract of `core.executor._local_join`.  The
sort-merge formulation pays for that with a full lexsort of the left∪right
key UNION (w sort passes over n_l + n_r rows) plus a stable argsort of the
right side at EVERY cascade step.  This family replaces all of it with a
radix hash join; no union buffer is ever materialized:

  hash    `join_hash` — fused multiply-shift hash of ALL shared key columns
          (named attributes + the hidden `__cell__` id) in one elementwise
          pass: h = (Σ_c key_c · seed_c) · MULT, bucket = top `n_bits` bits.
          Both sides hash identically; invalid rows land in a sentinel
          bucket P = 2^n_bits that valid rows can never reach.
  build   `build_table` — the same fused hash PLUS the carried-histogram
          stable rank of `bucket_pack`, in ONE streaming pass over the right
          side: TPU grids iterate sequentially, so a revisited (P + 1,)
          histogram block accumulates bucket loads while each row reads its
          stable within-bucket rank as carry + strict-lower-triangular local
          count.  Bucket offsets (exclusive histogram scan) turn the ranks
          into a COMPACT hash table: bucket p's rows sit contiguously at
          [offs[p], offs[p] + hist[p]), in arrival order — the right-side
          stable rank comes out of the same pass that builds the table.
  probe   `probe_tables` — key-verified chained resolution.  Distinct keys
          colliding in one bucket are resolved EXACTLY: each round peels the
          chain one link — every bucket's first unresolved row is that
          round's representative, all rows (and probing left rows) with keys
          equal to it resolve, everything else follows the chain next round.
          Resolving rows are assigned contiguous slots in a grouped final
          order via segmented prefix sums (groups contiguous, arrival order
          inside), so the step emits per-left-row match counts and
          group-start offsets that feed the executor's existing static-shape
          prefix-sum expansion gather unchanged.  Round count = max distinct
          keys per bucket (+1) — O(1) expected at the default table size of
          ~2·n_r buckets; a tiny `n_bits` forces deep chains (the
          forced-collision test knob).

Step cost drops from O((n_l + n_r) · w · log n) union sort work to
O(n_l + n_r) streaming work per chain round.  `join_hash_host` /
`build_table_host` are the bit-identical vectorized-XLA twins used off-TPU
(the host rank is the proven argsort-rank math of `_pack_buckets_argsort` —
ONE single-key int32 sort of the right side, still strictly less sorting
than the union lexsort it replaces); `join_hash_ref` / `build_table_ref` /
`join_probe_ref` in kernels/ref.py are the dead-simple oracles.  Output is
bit-identical to the sort-merge path (and through it to the dense-matrix
ground oracle); `kernels.ops` picks Pallas on TPU and the host twins
elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import MULT

# Rows per tile.  Every per-row operand is a 1-D int32 array, which the TPU
# keeps in tiles of 1024, so tiles are that long.
BLOCK = 1024
MAX_BITS = 16             # default table-size cap (2^16 buckets)
HOST_BITS = 30            # hash bits of `join_probe_host`'s sorted partitions
# Largest n_bits the single-pass (P+1, block) one-hot handles; beyond it
# `build_table` recurses on the high hash bits (the factored two-level
# histogram of `_build_table_multi_kernel`), keeping O(block · 2^(bits/2))
# VMEM per tile.
SINGLE_PASS_BITS = 10
INVALID = -1

# Per-column odd multipliers of the fused key hash (kernel, host twin, and
# ref MUST agree — the hash is a cross-side semantic contract).
_SEED0 = 0x9E3779B1
_SEED_STEP = 0x85EBCA77


def col_seeds(w: int) -> tuple[int, ...]:
    """Static odd multiply-shift seed per key column."""
    return tuple(((_SEED0 + 2 * c * _SEED_STEP) | 1) & 0xFFFFFFFF
                 for c in range(w))


def default_bits(n_r: int) -> int:
    """Default table size: ~2·n_r buckets, capped at 2^MAX_BITS."""
    return max(1, min(MAX_BITS, (max(n_r, 2) - 1).bit_length() + 1))


def _hash_cols(cols, n_bits: int) -> jnp.ndarray:
    """int32 bucket in [0, 2^n_bits): fused multiply-shift over key columns.

    `cols[c]` is key column c, all of one shape; the per-column seeds unroll
    statically (w is tiny).  Shared by the kernel bodies and the host twins.
    """
    h = jnp.zeros(cols[0].shape, jnp.uint32)
    for col, seed in zip(cols, col_seeds(len(cols))):
        h = h + col.astype(jnp.uint32) * jnp.uint32(seed)
    h = h * jnp.uint32(MULT)
    return (h >> jnp.uint32(32 - n_bits)).astype(jnp.int32)


def _hash_block(keys: jnp.ndarray, n_bits: int) -> jnp.ndarray:
    """(n,) bucket of each row of keys (n, w) — `_hash_cols` over columns."""
    return _hash_cols([keys[:, c] for c in range(keys.shape[1])], n_bits)


def _pad_cols(keys: jnp.ndarray, valid: jnp.ndarray
              ) -> list[jnp.ndarray]:
    """Key columns and the int32 valid flag, each padded to whole tiles.

    Each enters a kernel as its own 1-D operand: a (rows, w) tile with
    w < 128 would be padded to 128 lanes in HBM and VMEM.  Pad rows are
    invalid, so they land in the sentinel bucket.
    """
    pad = -keys.shape[0] % BLOCK
    cols = [jnp.pad(keys[:, c], (0, pad)) for c in range(keys.shape[1])]
    return cols + [jnp.pad(valid.astype(jnp.int32), (0, pad))]


def _local_rank(d: jnp.ndarray, block: int) -> jnp.ndarray:
    """(block,) count of earlier rows of the tile with the same bucket: a
    strict-lower-triangular equality count, summed over sublanes so the
    result comes out lane-major like `d`."""
    eq = d[:, None] == d[None, :]                       # [i, j]: d_i == d_j
    row = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    return jnp.sum((eq & (row < col)).astype(jnp.int32), axis=0)


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _join_hash_kernel(*refs, n_bits: int):
    *key_refs, valid_ref, out_ref = refs
    b = _hash_cols([r[...] for r in key_refs], n_bits)
    out_ref[...] = jnp.where(valid_ref[...] > 0, b, jnp.int32(1 << n_bits))


def _build_table_kernel(*refs, n_bits: int, block: int):
    *key_refs, valid_ref, bkt_ref, rank_ref, hist_ref = refs
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    d = jnp.where(valid_ref[...] > 0,
                  _hash_cols([r[...] for r in key_refs], n_bits),
                  jnp.int32(1 << n_bits))                   # (block,)
    # Carried-histogram stable rank (the bucket_pack idiom): base from the
    # running histogram, held as a (P1, 1) column so the one-hot is
    # (P1, block) and every sum runs over sublanes, local from the
    # strict-lower-triangular equality count.
    carry = hist_ref[...]                                   # (P1, 1)
    bins = jax.lax.broadcasted_iota(jnp.int32, (carry.shape[0], block), 0)
    oh = d[None, :] == bins                                 # (P1, block)
    base = jnp.sum(jnp.where(oh, carry, 0), axis=0)         # carry[d]
    bkt_ref[...] = d
    rank_ref[...] = base + _local_rank(d, block)
    hist_ref[...] = carry + jnp.sum(oh.astype(jnp.int32), axis=1,
                                    keepdims=True)


# Byte limbs of the factored histogram's one-hot lookups (see below).
_LIMBS = 4


def _build_table_multi_kernel(*refs, n_bits: int, lo_bits: int, block: int):
    """The multi-pass (recursion-on-high-bits) build: bucket d splits into
    hi = d >> lo_bits and lo = d & (2^lo_bits - 1), and the carried
    histogram becomes the FACTORED table Cᵀ (2^lo_bits, NH) with NH ≥
    2^hi_bits + 1 — carry lookup is the dot Cᵀ @ oh_hi masked by the lo
    one-hot, accumulation is the rank-1 update oh_lo @ oh_hiᵀ, both MXU dots.
    VMEM per tile drops from O(block · 2^bits) to O(block · 2^(bits/2)).
    The sentinel bucket P = 2^bits maps to the unique cell (hi = 2^hi_bits,
    lo = 0) no valid row can reach, so ranks and histogram stay
    bit-identical to the single-pass kernel.

    The dots run in bf16 with f32 accumulation and are exact: one-hot
    entries are 0 or 1; the lookup splits each count into `_LIMBS` bytes
    (every byte ≤ 255 is exact in bf16) and each output sums exactly one
    nonzero product per byte; the update sums at most `block` ones, exact
    in f32 below 2^24.  Counts are int32, so 4 bytes cover every count."""
    *key_refs, valid_ref, bkt_ref, rank_ref, hist_ref = refs
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    nl = 1 << lo_bits
    d = jnp.where(valid_ref[...] > 0,
                  _hash_cols([r[...] for r in key_refs], n_bits),
                  jnp.int32(1 << n_bits))                   # (block,)
    hi = d >> lo_bits                                       # sentinel -> nh
    lo = d & (nl - 1)                                       # sentinel -> 0
    ct = hist_ref[...]                                      # (nl, NH)
    oh_hi = (hi[None, :] == jax.lax.broadcasted_iota(
        jnp.int32, (ct.shape[1], block), 0)).astype(jnp.bfloat16)
    oh_lo = lo[None, :] == jax.lax.broadcasted_iota(
        jnp.int32, (nl, block), 0)                          # (nl, block)
    rows = jnp.zeros((nl, block), jnp.int32)                # C[hi_j, :]ᵀ
    for b in range(_LIMBS):
        limb = ((ct >> (8 * b)) & 0xFF).astype(jnp.bfloat16)
        part = jax.lax.dot_general(
            limb, oh_hi, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        rows = rows + (part.astype(jnp.int32) << (8 * b))
    base = jnp.sum(jnp.where(oh_lo, rows, 0), axis=0)       # C[hi, lo]
    bkt_ref[...] = d
    rank_ref[...] = base + _local_rank(d, block)
    hist_ref[...] = ct + jax.lax.dot_general(
        oh_lo.astype(jnp.bfloat16), oh_hi,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_bits", "interpret"))
def join_hash(keys: jnp.ndarray, valid: jnp.ndarray, *, n_bits: int,
              interpret: bool = False) -> jnp.ndarray:
    """(n,) int32 bucket ids; invalid rows land in the sentinel bucket P.

    keys (n, w) int32; valid (n,) int32/bool — False rows get bucket
    P = 2^n_bits, unreachable by any valid row on either side.
    """
    n, w = keys.shape
    if n == 0:
        return jnp.zeros((0,), jnp.int32)
    ops = _pad_cols(keys, valid)
    n_p = ops[0].shape[0]
    tile = pl.BlockSpec((BLOCK,), lambda i: (i,))
    out = pl.pallas_call(
        functools.partial(_join_hash_kernel, n_bits=n_bits),
        grid=(n_p // BLOCK,),
        in_specs=[tile] * (w + 1),
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((n_p,), jnp.int32),
        interpret=interpret,
    )(*ops)
    return out[:n]


@functools.partial(jax.jit, static_argnames=("n_bits",))
def join_hash_host(keys: jnp.ndarray, valid: jnp.ndarray, *, n_bits: int
                   ) -> jnp.ndarray:
    """`join_hash` in plain XLA — bit-identical buckets."""
    if keys.shape[0] == 0:
        return jnp.zeros((0,), jnp.int32)
    return jnp.where(valid.astype(bool), _hash_block(keys, n_bits),
                     jnp.int32(1 << n_bits))


@functools.partial(jax.jit, static_argnames=("n_bits", "multi_pass",
                                             "interpret"))
def build_table(keys: jnp.ndarray, valid: jnp.ndarray, *, n_bits: int,
                multi_pass: bool | None = None, interpret: bool = False
                ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(bucket (n,), rank (n,), hist (P,)) — hash + stable rank in ONE pass.

    rank is the row's stable arrival rank within its bucket; hist counts
    valid rows per bucket (the sentinel bin is dropped).  With the exclusive
    scan of hist as bucket offsets, `offs[bucket] + rank` lays the rows out
    as a compact per-bucket hash table in arrival order.

    `multi_pass` selects the factored two-level histogram (recursion on the
    high hash bits) of `_build_table_multi_kernel`; the default (None) picks
    it automatically once `n_bits` exceeds `SINGLE_PASS_BITS` — where the
    single-pass one-hot would outgrow VMEM.  Outputs are bit-identical
    either way.
    """
    n, w = keys.shape
    p = 1 << n_bits
    if n == 0:
        return (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32),
                jnp.zeros((p,), jnp.int32))
    if multi_pass is None:
        multi_pass = n_bits > SINGLE_PASS_BITS
    multi_pass = multi_pass and n_bits >= 2
    if multi_pass:
        lo_bits = n_bits // 2
        nh, nl = 1 << (n_bits - lo_bits), 1 << lo_bits
        # Cᵀ: (nl, NH) with the sentinel hi row nh inside, NH lane-aligned.
        hist_shape = (nl, nh + 1 + (-(nh + 1) % 128))
        kernel = functools.partial(_build_table_multi_kernel, n_bits=n_bits,
                                   lo_bits=lo_bits, block=BLOCK)
    else:
        hist_shape = (p + 1 + (-(p + 1) % 8), 1)            # (P1, 1) column
        kernel = functools.partial(_build_table_kernel, n_bits=n_bits,
                                   block=BLOCK)
    ops = _pad_cols(keys, valid)                            # pads -> sentinel
    n_p = ops[0].shape[0]
    tile = pl.BlockSpec((BLOCK,), lambda i: (i,))
    bkt, rank, hist = pl.pallas_call(
        kernel,
        grid=(n_p // BLOCK,),
        in_specs=[tile] * (w + 1),
        out_specs=(tile, tile,
                   pl.BlockSpec(hist_shape, lambda i: (0, 0))),  # carry
        out_shape=(
            jax.ShapeDtypeStruct((n_p,), jnp.int32),
            jax.ShapeDtypeStruct((n_p,), jnp.int32),
            jax.ShapeDtypeStruct(hist_shape, jnp.int32),
        ),
        interpret=interpret,
    )(*ops)
    if multi_pass:
        # Valid buckets are hi·nl + lo: the first nh columns of Cᵀ,
        # transposed and flattened row-major, ARE the flat (P,) histogram.
        return bkt[:n], rank[:n], hist[:, :nh].T.reshape(p)
    return bkt[:n], rank[:n], hist[:p, 0]


@functools.partial(jax.jit, static_argnames=("n_bits",))
def build_table_host(keys: jnp.ndarray, valid: jnp.ndarray, *, n_bits: int
                     ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """`build_table` in vectorized XLA — bit-identical outputs.

    The stable within-bucket rank comes from ONE single-key int32 stable
    argsort (the `_pack_buckets_argsort` rank math) — strictly less sorting
    than the w-pass union lexsort the hash join replaces.
    """
    n = keys.shape[0]
    p = 1 << n_bits
    if n == 0:
        return (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32),
                jnp.zeros((p,), jnp.int32))
    d = join_hash_host(keys, valid, n_bits=n_bits)
    order = jnp.argsort(d, stable=True)
    sd = d[order]
    start = jnp.searchsorted(sd, sd, side="left")
    pos = jnp.arange(n, dtype=jnp.int32) - start.astype(jnp.int32)
    rank = jnp.zeros((n,), jnp.int32).at[order].set(pos)
    hist = jnp.zeros((p + 1,), jnp.int32).at[d].add(1)[:p]
    return d, rank, hist


# ---------------------------------------------------------------------------
# Chained build + probe (shared by the kernel, host, and ref paths)
# ---------------------------------------------------------------------------

def _chain_probe(lk: jnp.ndarray, rk: jnp.ndarray, perm1: jnp.ndarray,
                 rstart: jnp.ndarray, rend: jnp.ndarray, s_l: jnp.ndarray,
                 l_miss: jnp.ndarray, fpos0: jnp.ndarray
                 ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Key-verified chained resolution over a partitioned packed table.

    Partition-scheme-agnostic core shared by the kernel, host, and ref
    paths.  `perm1` maps packed position -> original right row (rows grouped
    by hash partition, ARRIVAL order inside, invalid rows last);
    `rstart`/`rend` give each packed row its partition's [start, end) range;
    `s_l` is each left row's partition start (junk where `l_miss` — left
    rows with no partition: invalid, or hash value absent from the table);
    `fpos0` pre-assigns final slots to invalid packed rows (-1 elsewhere).

    Returns (counts (n_l,), lo (n_l,), perm (n_r,)): perm is a grouped
    permutation of the right side — every exact-key group contiguous and
    internally in arrival order — and each left row's matches are exactly
    perm[lo .. lo + counts), so the caller's static-shape prefix-sum
    expansion gather works unchanged (`counts`/`lo` of rows with no match
    are 0 and never gathered).

    One `lax.while_loop` round follows every partition's collision chain one
    link: the partition's first unresolved row (found scatter-free as the
    nearest unresolved row at or after the partition start, one reverse
    running minimum) is the round's representative;
    right rows with keys exactly equal to it resolve into one contiguous
    group of final slots read straight off the round's prefix sum
    (partitions are contiguous in packed order, so prefix-sum order IS
    grouped order), and probing left rows with equal keys take that group's
    (start, size).  The loop ends the moment the RIGHT side is fully
    resolved: a left row's key, if present at all, hits in the exact round
    its group resolves (reps enumerate the partition's distinct keys, and a
    key can equal at most one of them), so whatever never hit has no match
    and keeps counts = 0.  Round count = max distinct keys per partition —
    O(1) expected at default table sizes, deep only under the
    forced-collision tiny-bits knob.  Group layout across rounds is an
    internal choice — output depends only on the per-left-row enumeration.
    """
    n_l, n_r = lk.shape[0], rk.shape[0]
    if n_r == 0:
        return (jnp.zeros((n_l,), jnp.int32), jnp.zeros((n_l,), jnp.int32),
                jnp.zeros((0,), jnp.int32))
    pk = rk[perm1]                                          # packed keys
    s_l = jnp.clip(s_l, 0, n_r - 1)
    lmask = ~l_miss     # invalid / absent-partition rows can never hit: an
    # absent partition means the key exists nowhere on the right, so rep
    # keys from the neighbouring partition s_l points into never equal it.
    cnt0 = jnp.zeros((n_l,), jnp.int32)
    lo0 = jnp.zeros((n_l,), jnp.int32)
    idx = jnp.arange(n_r, dtype=jnp.int32)
    last_l = jnp.clip(rend[s_l] - 1, 0, n_r - 1)            # loop-invariant

    def cond(state):
        fpos, _cnt, _lo, _total = state
        return jnp.any(fpos < 0)

    def body(state):
        fpos, cnt, lo, total = state
        unres = fpos < 0
        # Per packed row, its partition's first unresolved row: the nearest
        # unresolved row at or after the partition start.
        nxt = jax.lax.cummin(jnp.where(unres, idx, n_r), reverse=True)
        pos = nxt[rstart]
        rep = jnp.where(pos < rend, pos, n_r)               # (n_r,) per row
        mask = unres & (pk == pk[jnp.clip(rep, 0, n_r - 1)]).all(axis=1)
        rep_l = rep[s_l]                                    # left partitions
        hit = lmask & (rep_l < n_r) \
            & (lk == pk[jnp.clip(rep_l, 0, n_r - 1)]).all(axis=1)
        # Final slots straight off the round's prefix sum: partitions are
        # contiguous in packed order, so mask rows in prefix-sum order are
        # already grouped (≤ 1 resolving group per partition per round).
        pcm = jnp.cumsum(mask.astype(jnp.int32))            # inclusive
        base_l = jnp.where(s_l > 0, pcm[jnp.clip(s_l - 1, 0, n_r - 1)], 0)
        reach_l = pcm[last_l]
        fpos = jnp.where(mask, total + pcm - 1, fpos)
        cnt = jnp.where(hit, reach_l - base_l, cnt)
        lo = jnp.where(hit, total + base_l, lo)
        return fpos, cnt, lo, total + pcm[-1]

    fpos, cnt, lo, _t = jax.lax.while_loop(
        cond, body, (fpos0, cnt0, lo0, jnp.int32(0)))
    perm = jnp.zeros((n_r,), jnp.int32).at[fpos].set(perm1)
    return cnt, lo, perm


def _run_bounds(rid: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row [start, end) of the run of equal values in a sorted (n,)
    array (the segment_scan_ref cummax idiom, forward + reversed)."""
    n = rid.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    flags = jnp.concatenate([jnp.ones((1,), bool), rid[1:] != rid[:-1]])
    start = jax.lax.cummax(jnp.where(flags, idx, jnp.int32(-1)))
    flags_r = jnp.concatenate([jnp.ones((1,), bool),
                               rid[::-1][1:] != rid[::-1][:-1]])
    start_r = jax.lax.cummax(jnp.where(flags_r, idx, jnp.int32(-1)))
    end = (n - 1) - start_r[::-1] + 1
    return start, end


def sorted_locate(a: jnp.ndarray, q: jnp.ndarray, side: str = "left"
                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(pos, nxt) of each query q (m,) in the sorted int32 array a (n,):
    pos is `jnp.searchsorted(a, q, side=side)`, nxt is a[pos], or the int32
    maximum where pos == n.

    A merge by sort, with no gather loop and no scatter: one two-key sort
    lays a and q out in merged order, each tagged with its place in their
    concatenation, so that the tag breaks ties (queries before equal
    elements of a for side="left", after them for "right").  A query's pos
    is then the running count of a's elements before it, and nxt the
    running minimum of a's elements after it.  A second sort, on the tag,
    returns both to query order.
    """
    n, m = a.shape[0], q.shape[0]
    with jax.named_scope("locate"):
        left = side == "left"
        keys = jnp.concatenate([q, a] if left else [a, q]).astype(jnp.int32)
        tag = jnp.arange(n + m, dtype=jnp.int32)
        keys, tag = jax.lax.sort((keys, tag), num_keys=2)
        is_a = (tag >= m) if left else (tag < n)
        pos = jnp.cumsum(is_a.astype(jnp.int32))
        nxt = jax.lax.cummin(
            jnp.where(is_a, keys, jnp.iinfo(jnp.int32).max), reverse=True)
        _, pos, nxt = jax.lax.sort((tag, pos, nxt), num_keys=1)
        return (pos[:m], nxt[:m]) if left else (pos[n:], nxt[n:])


def probe_tables(lk: jnp.ndarray, l_bkt: jnp.ndarray, rk: jnp.ndarray,
                 r_bkt: jnp.ndarray, rank: jnp.ndarray, hist: jnp.ndarray,
                 n_bits: int
                 ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Chained build+probe from `join_hash` (left) / `build_table` (right)
    outputs: lays the right side out as the compact per-bucket table
    (offs[bucket] + rank, sentinel bucket last) and runs `_chain_probe`
    with buckets as the partitions."""
    n_r = rk.shape[0]
    p = 1 << n_bits
    if n_r == 0:
        return (jnp.zeros((lk.shape[0],), jnp.int32),
                jnp.zeros((lk.shape[0],), jnp.int32),
                jnp.zeros((0,), jnp.int32))
    hist_full = jnp.concatenate(
        [hist, (jnp.int32(n_r) - hist.sum())[None]])        # (P + 1,)
    starts = jnp.concatenate(
        [jnp.zeros((1,), hist_full.dtype), jnp.cumsum(hist_full)])
    q = starts[r_bkt] + rank                                # packed position
    qidx = jnp.arange(n_r, dtype=jnp.int32)
    perm1 = jnp.zeros((n_r,), jnp.int32).at[q].set(qidx)    # packed -> orig
    pb = jnp.searchsorted(starts[1:], qidx, side="right")   # packed buckets
    rstart, rend = starts[pb], starts[pb + 1]
    fpos0 = jnp.where(qidx >= starts[p], qidx, jnp.int32(-1))
    l_safe = jnp.clip(l_bkt, 0, p)
    l_miss = (l_bkt >= p) | (hist_full[l_safe] == 0)
    return _chain_probe(lk, rk, perm1, rstart, rend, starts[l_safe], l_miss,
                        fpos0)


@functools.partial(jax.jit, static_argnames=("n_bits", "interpret"))
def join_probe(lk: jnp.ndarray, l_valid: jnp.ndarray, rk: jnp.ndarray,
               r_valid: jnp.ndarray, *, n_bits: int | None = None,
               interpret: bool = False
               ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Radix hash join via the Pallas kernels: (counts, lo, perm).

    lk (n_l, w) / rk (n_r, w) share the same key-column order; n_bits
    defaults to a ~2·n_r-bucket table (a tiny value forces collisions —
    resolution stays exact, only the chains deepen).
    """
    bits = n_bits or default_bits(rk.shape[0])
    bl = join_hash(lk, l_valid, n_bits=bits, interpret=interpret)
    br, rank, hist = build_table(rk, r_valid, n_bits=bits,
                                 interpret=interpret)
    return probe_tables(lk, bl, rk, br, rank, hist, bits)


@functools.partial(jax.jit, static_argnames=("n_bits",))
def join_probe_host(lk: jnp.ndarray, l_valid: jnp.ndarray, rk: jnp.ndarray,
                    r_valid: jnp.ndarray, *, n_bits: int | None = None
                    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """`join_probe` on the vectorized-XLA twins (non-TPU hot path).

    The packed table comes from ONE sort of (hash, arrival) pairs: the
    sorted pairs ARE (partition, original row), partition bounds fall out of
    two run scans, and left rows locate their partition, and whether it
    exists, by merging their hashes into the sorted ones (`sorted_locate`:
    two sorts and two scans, where a binary search would be a loop of
    gathers, slow on a TPU): no multi-column lexsort, no scatter.  The
    hash keeps `HOST_BITS` bits (invalid rows take the sentinel above them,
    sorting last), so partitions are far finer than the kernel's histogram
    table and the chain loop converges in O(1) rounds at any n_r; an
    explicit tiny `n_bits` still forces deep chains for the collision tests.
    """
    n_l, n_r = lk.shape[0], rk.shape[0]
    if n_r == 0:
        return (jnp.zeros((n_l,), jnp.int32), jnp.zeros((n_l,), jnp.int32),
                jnp.zeros((0,), jnp.int32))
    bits = min(n_bits or HOST_BITS, HOST_BITS)
    qidx = jnp.arange(n_r, dtype=jnp.int32)
    hw_l = _hash_block(lk, bits)
    hw_r = _hash_block(rk, bits)
    key = jnp.where(r_valid.astype(bool), hw_r, jnp.int32(1 << bits))
    rid, perm1 = jax.lax.sort((key, qidx), num_keys=2)      # packed -> orig
    rstart, rend = _run_bounds(rid)
    fpos0 = jnp.where(rid >= (1 << bits), qidx, jnp.int32(-1))
    s_l, nxt = sorted_locate(rid, hw_l, side="left")
    exists = (s_l < n_r) & (nxt == hw_l)
    l_miss = ~l_valid.astype(bool) | ~exists
    return _chain_probe(lk, rk, perm1, rstart, rend, s_l, l_miss, fpos0)
