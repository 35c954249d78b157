"""The reduce's probe: an exact-key merge sort of both sides.

A reducer cell's local join cascades over its fragments; every cascade step
must produce, per left (accumulator) row, its matching right rows in ARRIVAL
order — the load-bearing output contract of `core.executor._local_join`.
`join_probe_host` produces it as (counts, lo, perm) from two sorts and a
few scans, with no hash, no loop, no gather and no scatter:

  merge   ONE sort of left ++ right keyed on (validity, every shared key
          column including the hidden `__cell__` id, tag): equal keys form
          one run, left entries first, each side in arrival order.
  scan    a running count of right entries gives each left entry its `lo`;
          a reverse running minimum carries each run's end count back, and
          the difference is its `counts`.
  return  ONE sort on a single key puts (counts, lo) back in left order and
          the right entries' tags, in merged order, out as `perm`.

Its cost depends on n_l + n_r alone, not on the key count or the skew: a
heavy key is one long run.  `sorted_locate`, a merge of queries into a
sorted array by the same two-sort pattern, serves the expansion
(`scatter_pack.expand_rows_host`).

The rest of the module serves only the tests as an independent oracle:
`join_hash_host` / `build_table_host` hash ALL shared key columns with a
fused multiply-shift into a table of 2^n_bits buckets with stable
within-bucket ranks, and `probe_tables` runs `_chain_probe`, a key-verified
chained probe, on such a table (a tiny `n_bits` forces deep collision
chains).  With `join_hash_ref` / `build_table_ref` / `join_probe_ref` in
kernels/ref.py they are what the tests hold `join_probe_host` to.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .ref import MULT

MAX_BITS = 16             # `default_bits` table-size cap (2^16 buckets)
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
# Chained build + probe: the tests' bucket-table oracle
# ---------------------------------------------------------------------------

def _chain_probe(lk: jnp.ndarray, rk: jnp.ndarray, perm1: jnp.ndarray,
                 rstart: jnp.ndarray, rend: jnp.ndarray, s_l: jnp.ndarray,
                 l_miss: jnp.ndarray, fpos0: jnp.ndarray
                 ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Key-verified chained resolution over a partitioned packed table.

    The tests' oracle probe, run by `probe_tables` on a bucket table
    (`join_probe_host` no longer partitions by hash).  `perm1` maps
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


@jax.jit
def join_probe_host(lk: jnp.ndarray, l_valid: jnp.ndarray, rk: jnp.ndarray,
                    r_valid: jnp.ndarray
                    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The reduce's probe: (counts, lo, perm) of left keys lk (n_l, w)
    against right keys rk (n_r, w), in the same key-column order.

    An exact-key merge sort, with no hash, no loop, no gather and no
    scatter.  Both sides go into one sort keyed on (validity, the key
    columns, tag): validity is 0 for valid rows, 1 for invalid right rows and
    2 for invalid left rows, so an invalid row never shares a run of equal
    keys with a valid one; the tag is the row's place in left ++ right, so
    on equal keys left entries come first and each side keeps its arrival
    order.  In merged order a left entry's `lo` is the running count of
    right entries before it, and its `counts` the count at the end of its
    run less `lo`: a reverse running minimum carries each run's end count
    back, as the count never decreases.  One sort on a single key then
    returns the left entries' (counts, lo) to left order and lays the
    right entries' tags out in merged order, which is `perm`: every exact-key
    group contiguous and in arrival order, invalid rows last.  Rows with no
    match get counts = lo = 0.
    """
    n_l, n_r = lk.shape[0], rk.shape[0]
    if n_r == 0:
        return (jnp.zeros((n_l,), jnp.int32), jnp.zeros((n_l,), jnp.int32),
                jnp.zeros((0,), jnp.int32))
    n = n_l + n_r
    inv = jnp.concatenate([jnp.where(l_valid.astype(bool), 0, 2),
                           jnp.where(r_valid.astype(bool), 0, 1)]
                          ).astype(jnp.int32)
    cols = [jnp.concatenate([lk[:, c], rk[:, c]]) for c in range(lk.shape[1])]
    tag = jnp.arange(n, dtype=jnp.int32)
    *keys, tag = jax.lax.sort((inv, *cols, tag), num_keys=len(cols) + 2)
    is_r = tag >= n_l
    cum_r = jnp.cumsum(is_r.astype(jnp.int32))             # inclusive
    new_run = jnp.zeros((n - 1,), bool)
    for k in keys:
        new_run = new_run | (k[1:] != k[:-1])
    run_end = jnp.concatenate([new_run, jnp.ones((1,), bool)])
    end_r = jax.lax.cummin(
        jnp.where(run_end, cum_r, jnp.iinfo(jnp.int32).max), reverse=True)
    counts = end_r - cum_r            # a left entry's right rows after it
    lo = jnp.where(counts > 0, cum_r, 0)
    back = jnp.where(is_r, n_l + jnp.arange(n, dtype=jnp.int32), tag)
    _, counts, lo, tag = jax.lax.sort((back, counts, lo, tag), num_keys=1)
    return counts[:n_l], lo[:n_l], tag[n_l:] - n_l
