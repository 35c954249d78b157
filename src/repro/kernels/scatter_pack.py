"""The map's buffer assembly and the reduce's expansion, in vectorized XLA.

  scatter_pack_host  the map phase of one relation: route every row through
                     all of its residual routes (`map_pack._route_block`),
                     fold each wrapped logical cell onto its device through
                     the placement table, and assemble the
                     ``(n_dev, cap, w+1)`` shuffle buffer — each copy's
                     columns plus its unwrapped logical-cell tag — with ONE
                     stable `lax.sort` keyed on the destination
                     (`_scatter_assemble_host`).  A copy's place in its
                     bucket is its stable rank, so there is no rank pass, and
                     each device's `cap` rows are a dynamic slice of the
                     sorted copies at the histogram's exclusive prefix.  The
                     assemble lowers with no gather and the whole map with no
                     scatter (tests/test_scatter_pack.py pins both): a row
                     scatter onto a trash row serialized on the TPU.
  expand_rows_host   the reduce's prefix-sum expansion of one probe result
                     (counts, lo, perm) into `cap` output rows.  Slot t's
                     left row is found by merging the slots into the group
                     offsets with two sorts and a scan
                     (`join_probe.sorted_locate`; a binary search is a loop
                     of gathers, slow on a TPU, and the tests pin its
                     absence), then the rows are gathered.

Both run on every backend.  `scatter_pack_ref` / `expand_rows_ref` in
kernels/ref.py are the dead-simple oracles the tests compare against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .join_probe import sorted_locate
from .map_pack import RouteSpec, _route_block, route_fanout

INVALID = -1


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
    INVALID.  No rank pass, and zero gather and zero scatter ops in the
    lowered HLO (tests/test_scatter_pack.py pins both)."""
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


@functools.partial(jax.jit, static_argnames=("routes", "k", "n_dev", "cap"))
def scatter_pack_host(rows: jnp.ndarray, ptable: jnp.ndarray, *,
                      routes: RouteSpec, k: int, n_dev: int, cap: int
                      ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The map phase of one relation: rows (n, w) -> ((n_dev, cap, w+1)
    shuffle buffer, overflow).

    rows int32 with -1-padding rows; ptable (k,) int32 placement table
    (`CellPlacement.table`, replicated); routes the static `RouteSpec`
    recipe whose cells wrap modulo `k`.  Routing and the placement fold are
    one elementwise pass with a table lookup; the buffer is then assembled
    by `_scatter_assemble_host`'s one stable sort on the destination, which
    yields the stable ranks by position, so there is no rank pass and no
    scatter.  Device dd's rows are its copies in row-major copy order, the
    first `cap` of them; `overflow` counts the rest.
    """
    n, w = rows.shape
    fanout = route_fanout(routes)
    if n == 0 or fanout == 0:
        return (jnp.full((n_dev, cap, w + 1), INVALID, rows.dtype),
                jnp.int32(0))
    logical, valid = _route_block(rows, routes, k)          # (n, F)
    wrapped = jnp.where(valid, logical % k, 0)
    d = jnp.where(valid, ptable[wrapped], n_dev).reshape(-1)
    return _scatter_assemble_host(rows, logical.reshape(-1), d, n_dev, cap,
                                  fanout)


# ---------------------------------------------------------------------------
# Reduce side: expand_rows
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cap",))
def expand_rows_host(left: jnp.ndarray, right: jnp.ndarray,
                     counts: jnp.ndarray, lo: jnp.ndarray, perm: jnp.ndarray,
                     *, cap: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Prefix-sum expansion of a probe result.

    From a probe pass's (counts (n_l,), lo (n_l,), perm (n_r,)) — per-left
    match counts, group starts, and the grouped right permutation — produce
    (out (cap, wl + wr), valid (cap,)): output slot t concatenates
    ``left[li(t)]`` and ``right[perm[lo[li] + t - off[li]]]`` in (left row,
    right arrival) order, exactly the `_local_join` expansion contract.
    Slot t's left row li is the last row whose group offset is at most t,
    found by a merge sort of offsets and slots (`sorted_locate`) rather
    than a binary search; the rows are then gathered.
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
