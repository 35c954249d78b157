"""The reduce's probe: a radix hash join with key-verified chains.

A reducer cell's local join cascades over its fragments; every cascade step
must produce, per left (accumulator) row, its matching right rows in ARRIVAL
order — the load-bearing output contract of `core.executor._local_join`.
`join_probe_host` produces it as (counts, lo, perm) with no union buffer and
no multi-column lexsort:

  hash    a fused multiply-shift hash of ALL shared key columns (named
          attributes + the hidden `__cell__` id) in one elementwise pass:
          h = (Σ_c key_c · seed_c) · MULT, keeping the top `HOST_BITS` bits.
          Invalid right rows take a sentinel above every valid hash.
  build   ONE sort of (hash, arrival) pairs lays the right side out as a
          packed table: the sorted pairs ARE (partition, original row), and
          partition bounds fall out of two run scans (`_run_bounds`).
  locate  left rows find their partition, and whether it exists, by merging
          their hashes into the sorted ones (`sorted_locate`: two sorts and
          two scans, where a binary search would be a loop of gathers).
  probe   `_chain_probe` — key-verified chained resolution.  Distinct keys
          colliding in one partition are resolved EXACTLY: each round peels
          the chain one link, and resolving rows take contiguous slots in a
          grouped final order via segmented prefix sums (groups contiguous,
          arrival order inside).  Round count = max distinct keys per
          partition (+1): O(1) at 30 bits; a tiny `n_bits` forces deep
          chains (the forced-collision test knob).

`join_hash_host` / `build_table_host` are the same hash and a bucket table
with stable within-bucket ranks over a table of `n_bits` buckets;
`probe_tables` runs the chained probe on such a table.  With
`join_hash_ref` / `build_table_ref` / `join_probe_ref` in kernels/ref.py
they are the oracles the tests hold `join_probe_host` to.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .ref import MULT

MAX_BITS = 16             # `default_bits` table-size cap (2^16 buckets)
HOST_BITS = 30            # hash bits of `join_probe_host`'s sorted partitions
INVALID = -1

# Per-column odd multipliers of the fused key hash (host functions and ref
# MUST agree — the hash is a cross-side semantic contract).
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
    statically (w is tiny).
    """
    h = jnp.zeros(cols[0].shape, jnp.uint32)
    for col, seed in zip(cols, col_seeds(len(cols))):
        h = h + col.astype(jnp.uint32) * jnp.uint32(seed)
    h = h * jnp.uint32(MULT)
    return (h >> jnp.uint32(32 - n_bits)).astype(jnp.int32)


def _hash_block(keys: jnp.ndarray, n_bits: int) -> jnp.ndarray:
    """(n,) bucket of each row of keys (n, w) — `_hash_cols` over columns."""
    return _hash_cols([keys[:, c] for c in range(keys.shape[1])], n_bits)


@functools.partial(jax.jit, static_argnames=("n_bits",))
def join_hash_host(keys: jnp.ndarray, valid: jnp.ndarray, *, n_bits: int
                   ) -> jnp.ndarray:
    """(n,) int32 bucket ids in [0, 2^n_bits); invalid rows land in the
    sentinel bucket P = 2^n_bits, unreachable by any valid row on either
    side."""
    if keys.shape[0] == 0:
        return jnp.zeros((0,), jnp.int32)
    return jnp.where(valid.astype(bool), _hash_block(keys, n_bits),
                     jnp.int32(1 << n_bits))


@functools.partial(jax.jit, static_argnames=("n_bits",))
def build_table_host(keys: jnp.ndarray, valid: jnp.ndarray, *, n_bits: int
                     ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(bucket (n,), rank (n,), hist (P,)) of a table of P = 2^n_bits
    buckets.

    rank is the row's stable arrival rank within its bucket; hist counts
    valid rows per bucket (the sentinel bin is dropped).  With the exclusive
    scan of hist as bucket offsets, `offs[bucket] + rank` lays the rows out
    as a compact per-bucket hash table in arrival order (`probe_tables`).
    The rank comes from ONE single-key int32 stable argsort.
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
# Chained build + probe
# ---------------------------------------------------------------------------

def _chain_probe(lk: jnp.ndarray, rk: jnp.ndarray, perm1: jnp.ndarray,
                 rstart: jnp.ndarray, rend: jnp.ndarray, s_l: jnp.ndarray,
                 l_miss: jnp.ndarray, fpos0: jnp.ndarray
                 ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Key-verified chained resolution over a partitioned packed table.

    Partition-scheme-agnostic core shared by `join_probe_host` (sorted
    30-bit partitions) and `probe_tables` (bucket tables).  `perm1` maps
    packed position -> original right row (rows grouped by hash partition,
    ARRIVAL order inside, invalid rows last);
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
    array: a running maximum of run-start flags, forward and reversed."""
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
    """Chained build+probe from `join_hash_host` (left) / `build_table_host`
    (right) outputs, or their refs: lays the right side out as the compact per-bucket table
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


@functools.partial(jax.jit, static_argnames=("n_bits",))
def join_probe_host(lk: jnp.ndarray, l_valid: jnp.ndarray, rk: jnp.ndarray,
                    r_valid: jnp.ndarray, *, n_bits: int | None = None
                    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The reduce's probe: (counts, lo, perm) of left keys lk (n_l, w)
    against right keys rk (n_r, w), in the same key-column order.

    The packed table comes from ONE sort of (hash, arrival) pairs: the
    sorted pairs ARE (partition, original row), partition bounds fall out of
    two run scans, and left rows locate their partition, and whether it
    exists, by merging their hashes into the sorted ones (`sorted_locate`:
    two sorts and two scans, where a binary search would be a loop of
    gathers, slow on a TPU): no multi-column lexsort, no scatter.  The
    hash keeps `HOST_BITS` bits (invalid rows take the sentinel above them,
    sorting last), so partitions are far finer than a bucket table of
    `default_bits` and the chain loop converges in O(1) rounds at any n_r; an
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
