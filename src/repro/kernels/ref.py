"""Pure-jnp oracles for the kernels and the step's phases in this package.

Each `<name>_ref` is the semantic ground truth the tests hold an
implementation to: the Pallas kernels (interpret mode on CPU, compiled on
TPU) and the vectorized-XLA map, probe and expansion.  Keep these dead
simple — no blocking, no tricks.
"""
from __future__ import annotations

import jax.numpy as jnp

# Knuth's multiplicative constant — must match core.hypercube._MULT.
MULT = 2654435769


def hash_partition_ref(keys: jnp.ndarray, seed: int, nbuckets: int
                       ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Multiply-shift hash to power-of-two buckets + bucket histogram.

    h(v) = top log2(nbuckets) bits of (v · seed · MULT) over uint32.
    Returns (bucket_ids int32 (n,), histogram int32 (nbuckets,)).
    """
    assert nbuckets & (nbuckets - 1) == 0, "nbuckets must be a power of two"
    if nbuckets == 1:
        ids = jnp.zeros(keys.shape, jnp.int32)
    else:
        b = nbuckets.bit_length() - 1
        h = (keys.astype(jnp.uint32) * jnp.uint32(seed)) * jnp.uint32(MULT)
        ids = (h >> jnp.uint32(32 - b)).astype(jnp.int32)
    hist = jnp.zeros((nbuckets,), jnp.int32).at[ids].add(1)
    return ids, hist


def segment_histogram_ref(values: jnp.ndarray, n_bins: int) -> jnp.ndarray:
    """Frequency histogram of int values in [0, n_bins) (int32 (n_bins,)).

    The heavy-hitter counting pass: values outside the range are dropped.
    """
    valid = (values >= 0) & (values < n_bins)
    clipped = jnp.clip(values, 0, n_bins - 1)
    return jnp.zeros((n_bins,), jnp.int32).at[clipped].add(
        valid.astype(jnp.int32))


def bucket_rank_ref(dest: jnp.ndarray, k: int
                    ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(rank, hist): stable within-bucket rank + histogram, one-hot cumsum.

    rank[i] = |{j < i : dest[j] == dest[i]}| for dest in [0, k); values
    outside the range rank within a sentinel bucket.  O(m·k), dead simple
    on purpose.
    """
    m = dest.shape[0]
    d = jnp.where((dest >= 0) & (dest < k), dest.astype(jnp.int32),
                  jnp.int32(k))
    if m == 0:
        return jnp.zeros((0,), jnp.int32), jnp.zeros((k,), jnp.int32)
    onehot = d[:, None] == jnp.arange(k + 1, dtype=jnp.int32)[None, :]
    pos = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
    rank = jnp.take_along_axis(pos, d[:, None], axis=1)[:, 0]
    return rank, pos[-1, :k] + 1


def bucket_pack_ref(dest: jnp.ndarray, rows: jnp.ndarray, k: int, cap: int
                    ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Stable counting-sort pack into (k, cap, w) + overflow count.

    The map's assemble, as its contract: row i lands at buf[dest[i], rank[i]];
    invalid destinations and ranks beyond cap are dropped; overflow counts
    the dropped valid rows.
    """
    m, w = rows.shape
    rank, hist = bucket_rank_ref(dest, k)
    d = jnp.where((dest >= 0) & (dest < k), dest.astype(jnp.int32),
                  jnp.int32(k))
    overflow = jnp.maximum(hist - cap, 0).sum()
    buf = jnp.full((k, cap, w), jnp.int32(-1), dtype=rows.dtype)
    buf = buf.at[d, rank].set(rows, mode="drop")
    return buf, overflow


def route_cells_ref(rows: jnp.ndarray,
                    recipe: tuple[tuple[int, int, int, int], ...]
                    ) -> jnp.ndarray:
    """Hypercube routing oracle: Σ_i h_i(row[col_i]) · stride_i."""
    cell = jnp.zeros((rows.shape[0],), jnp.int32)
    for col, seed, share, stride in recipe:
        if share == 1:
            continue
        ids, _ = hash_partition_ref(rows[:, col], seed, share)
        cell = cell + ids * stride
    return cell


def _map_route_ref(rows: jnp.ndarray, routes, k: int
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(logical (n, F), wrapped (n, F)) per-copy ids — the routing stage of
    the map-phase oracle, one column per (route, replication offset).

    `routes` is the static `kernels.map_pack.RouteSpec` nested tuple; masked
    entries (type-constraint non-members, INVALID padding rows) are -1 in
    both outputs.
    """
    n = rows.shape[0]
    logical_cols, wrapped_cols = [], []
    for hashed, reps, offset, eqs, notins in routes:
        member = rows[:, 0] != jnp.int32(-1)
        for col, val in eqs:
            member &= rows[:, col] == val
        for col, vals in notins:
            hh = jnp.asarray(vals, rows.dtype)
            member &= ~(rows[:, col][:, None] == hh[None, :]).any(axis=1)
        base = route_cells_ref(rows, hashed)
        for r in reps:
            logical = base + (r + offset)
            logical_cols.append(jnp.where(member, logical, jnp.int32(-1)))
            wrapped_cols.append(jnp.where(member, logical % k, jnp.int32(-1)))
    return (jnp.stack(logical_cols, axis=1), jnp.stack(wrapped_cols, axis=1))


def scatter_pack_ref(rows: jnp.ndarray, ptable: jnp.ndarray, routes, k: int,
                 n_dev: int, cap: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Map-phase oracle: the staged route -> fold -> pack composition.

    Deliberately materializes the (n·F, w+1) tagged expansion that
    `scatter_pack_host` never builds — ground truth, not a hot path.
    Returns ((n_dev, cap, w+1) buffer, overflow), bit-identical to it.
    """
    n, w = rows.shape
    if n == 0 or not routes:
        return (jnp.full((n_dev, cap, w + 1), jnp.int32(-1), rows.dtype),
                jnp.int32(0))
    logical, wrapped = _map_route_ref(rows, routes, k)
    fanout = logical.shape[1]
    phys = fold_cells_ref(wrapped.reshape(-1), ptable)
    tagged = jnp.concatenate(
        [jnp.broadcast_to(rows[:, None, :], (n, fanout, w)),
         logical[:, :, None].astype(rows.dtype)],
        axis=-1).reshape(n * fanout, w + 1)
    return bucket_pack_ref(phys, tagged, n_dev, cap)


def map_count_ref(rows: jnp.ndarray, routes, k: int, n_src: int
                  ) -> jnp.ndarray:
    """Counting-mode oracle: (n_src, k) routed copies per (source, cell).

    Source of row i is i // (n // n_src) — the executor's sharded layout.
    """
    n = rows.shape[0]
    if n == 0 or not routes:
        return jnp.zeros((n_src, k), jnp.int32)
    _, wrapped = _map_route_ref(rows, routes, k)
    fanout = wrapped.shape[1]
    flat = wrapped.reshape(-1)
    src = jnp.repeat(jnp.arange(n, dtype=jnp.int32) // max(n // n_src, 1),
                     fanout)
    idx = jnp.where(flat >= 0, src * k + flat, n_src * k)
    counts = jnp.zeros((n_src * k + 1,), jnp.int32).at[idx].add(1)
    return counts[:n_src * k].reshape(n_src, k)


def expand_rows_ref(left: jnp.ndarray, right: jnp.ndarray,
                    counts: jnp.ndarray, lo: jnp.ndarray, perm: jnp.ndarray,
                    cap: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Prefix-sum expansion oracle: slot t of the (cap, wl + wr) output is
    ``left[li] ++ right[perm[lo[li] + t - off[li]]]`` where li is the row
    whose [off, off + counts) window covers t.  searchsorted + plain jnp
    gathers, where `expand_rows_host` merges by sorts."""
    n_l = left.shape[0]
    n_r = right.shape[0]
    if n_l == 0 or n_r == 0:
        return (jnp.full((cap, left.shape[1] + right.shape[1]), jnp.int32(-1),
                         left.dtype), jnp.zeros((cap,), bool))
    off = jnp.cumsum(counts) - counts
    t = jnp.arange(cap, dtype=jnp.int32)
    li = jnp.clip(jnp.searchsorted(off, t, side="right") - 1, 0, n_l - 1)
    ri = perm[jnp.clip(lo[li] + t - off[li], 0, n_r - 1)]
    out = jnp.concatenate([left[li], right[ri]], axis=1)
    return out, t < counts.sum()


def join_hash_ref(keys: jnp.ndarray, valid: jnp.ndarray, n_bits: int
                  ) -> jnp.ndarray:
    """Fused multi-column bucket hash of the `join_probe` family.

    h = (Σ_c key_c · seed_c) · MULT over uint32, bucket = top n_bits bits;
    seed_c = (0x9E3779B1 + 2c·0x85EBCA77) | 1.  Invalid rows land in the
    sentinel bucket 2^n_bits.  The formula is a cross-side contract — the
    host functions and this oracle must agree bit for bit.
    """
    h = jnp.zeros((keys.shape[0],), jnp.uint32)
    for c in range(keys.shape[1]):
        seed = ((0x9E3779B1 + 2 * c * 0x85EBCA77) | 1) & 0xFFFFFFFF
        h = h + keys[:, c].astype(jnp.uint32) * jnp.uint32(seed)
    h = (h * jnp.uint32(MULT)) >> jnp.uint32(32 - n_bits)
    return jnp.where(valid.astype(bool), h.astype(jnp.int32),
                     jnp.int32(1 << n_bits))


def build_table_ref(keys: jnp.ndarray, valid: jnp.ndarray, n_bits: int
                    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(bucket, stable within-bucket rank, histogram) — one-hot cumsum.

    Ground truth for `build_table_host`: O(n·P), dead simple.
    """
    d = join_hash_ref(keys, valid, n_bits)
    rank, hist = bucket_rank_ref(d, 1 << n_bits)
    return d, rank, hist


def join_probe_ref(lk: jnp.ndarray, l_valid: jnp.ndarray, rk: jnp.ndarray,
                   r_valid: jnp.ndarray, cap: int
                   ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Dense probe oracle: the expanded (li, ri, valid) match pairs.

    Enumerates all (left, right) exact-key matches in (left row, right
    ARRIVAL order) — the output contract `join_probe`'s (counts, lo, perm)
    must reproduce through the prefix-sum expansion gather.  O(n_l·n_r).
    """
    n_r = rk.shape[0]
    match = l_valid.astype(bool)[:, None] & r_valid.astype(bool)[None, :]
    match &= (lk[:, None, :] == rk[None, :, :]).all(axis=-1)
    n_match = match.sum()
    flat = jnp.nonzero(match.reshape(-1), size=cap, fill_value=0)[0]
    li, ri = flat // n_r, flat % n_r
    return li, ri, jnp.arange(cap) < n_match


def fold_cells_ref(dest: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Placement lookup oracle: physical device per wrapped logical cell.

    dest int32 (m,) in [0, k) with -1 marking non-members (passed through);
    table int32 (k,) maps logical cell -> physical device.  This is the
    logical->physical fold of `core.placement.CellPlacement`, composed after
    the routing in the map-phase oracle.
    """
    valid = dest >= 0
    safe = jnp.where(valid, dest, 0)
    return jnp.where(valid, table[safe], jnp.int32(-1))
