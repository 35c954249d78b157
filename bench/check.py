"""The plain reference and the comparison that decides `correct`.

The reference is a left-to-right cascade of two-way natural joins in numpy,
written here from the definition and importing nothing of the program.  A
served request is right when its rows equal the reference's as a multiset:
every result row of the bag join, each exactly as often as the join makes
it.  `Expected.rows_wrong` counts the rows in which the two differ (missing
plus extra), so its limit is 0.

`hash_match_join` is the control: the same cascade matching on a hash of
the key of `log2(rows) + 8` bits, with no check of the key itself, the step
a faster reduce would be tempted to take.  Its false matches are extra rows.
"""
from __future__ import annotations

from typing import Callable, Mapping

import numpy as np

from .gen import Relations, attributes

KeyFn = Callable[[np.ndarray], np.ndarray]


def _key_ids(keys: np.ndarray) -> np.ndarray:
    """One int64 id per row of a (n, c) key matrix; equal rows, equal ids."""
    if keys.shape[1] == 1:
        return keys[:, 0].astype(np.int64)
    _, ids = np.unique(keys, axis=0, return_inverse=True)
    return ids.reshape(-1).astype(np.int64)


def _join_two(left: np.ndarray, left_attrs: tuple, right: np.ndarray,
              right_attrs: tuple, key_fn: KeyFn | None
              ) -> tuple[np.ndarray, tuple]:
    shared = [a for a in left_attrs if a in right_attrs]
    extra = [i for i, a in enumerate(right_attrs) if a not in shared]
    out_attrs = left_attrs + tuple(right_attrs[i] for i in extra)
    if not shared:
        raise ValueError("cross products are not benchmark queries")
    lk = left[:, [left_attrs.index(a) for a in shared]]
    rk = right[:, [right_attrs.index(a) for a in shared]]
    ids = _key_ids(np.concatenate([lk, rk]))
    if key_fn is not None:
        ids = key_fn(ids)
    lid, rid = ids[:len(left)], ids[len(left):]
    order = np.argsort(rid, kind="stable")
    srid = rid[order]
    start = np.searchsorted(srid, lid, side="left")
    cnt = np.searchsorted(srid, lid, side="right") - start
    total = int(cnt.sum())
    li = np.repeat(np.arange(len(left)), cnt)
    first = np.cumsum(cnt) - cnt
    ri = order[np.repeat(start - first, cnt) + np.arange(total)]
    rows = np.concatenate([left[li], right[ri][:, extra]], axis=1)
    return rows, out_attrs


def natural_join(relations: Relations, data: Mapping[str, np.ndarray],
                 key_fn: KeyFn | None = None) -> np.ndarray:
    """The bag natural join of the relations, columns in `attributes`
    order, rows in no particular order."""
    (name0, attrs0), *rest = relations
    acc, acc_attrs = np.asarray(data[name0], np.int64), tuple(attrs0)
    for name, attrs in rest:
        acc, acc_attrs = _join_two(acc, acc_attrs,
                                   np.asarray(data[name], np.int64),
                                   tuple(attrs), key_fn)
    return acc[:, [acc_attrs.index(a) for a in attributes(relations)]]


def prefix_sizes(relations: Relations, data: Mapping[str, np.ndarray]
                 ) -> list[int]:
    """Row counts of each prefix R0 ⋈ … ⋈ Rj of the cascade, counted
    without building it where each relation joins the prefix only on
    attributes of the relation before it (paths, the paper's example);
    built otherwise."""
    (name0, attrs0), *rest = relations
    weight = np.ones(len(data[name0]), np.float64)
    sizes, seen, prev = [len(weight)], set(attrs0), (name0, tuple(attrs0))
    for name, attrs in rest:
        shared = [a for a in attrs if a in seen]
        if not shared or not set(shared) <= set(prev[1]):
            return [len(natural_join(relations[:j + 1], data))
                    for j in range(len(relations))]
        pk = np.asarray(data[prev[0]])[:, [prev[1].index(a) for a in shared]]
        rk = np.asarray(data[name])[:, [tuple(attrs).index(a) for a in shared]]
        ids = _key_ids(np.concatenate([pk, rk]))
        hist = np.bincount(ids[:len(pk)], weights=weight,
                           minlength=int(ids.max()) + 1)
        weight = hist[ids[len(pk):]]
        sizes.append(int(round(weight.sum())))
        seen |= set(attrs)
        prev = (name, tuple(attrs))
    return sizes


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer: a well-spread 64-bit hash of each value."""
    x = x.astype(np.uint64)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def hash_match_join(relations: Relations, data: Mapping[str, np.ndarray]
                    ) -> np.ndarray:
    """The control: `natural_join` matching on a hash of each key of
    log2(rows) + 8 bits, without checking the key."""
    rows = max(len(a) for a in data.values())
    shift = np.uint64(64 - min(max(rows - 1, 1).bit_length() + 8, 62))
    return natural_join(relations, data,
                        lambda ids: (_mix64(ids) >> shift).astype(np.int64))


def _packed(rows: np.ndarray, lo: np.ndarray, widths: list[int]
            ) -> np.ndarray:
    """Each row as one opaque record of uint64 words holding its columns:
    equal rows, equal records, so sorted records compare as multisets."""
    words, cur, used = [], np.zeros(len(rows), np.uint64), 0
    for c, w in enumerate(widths):
        if used + w > 64:
            words.append(cur)
            cur, used = np.zeros(len(rows), np.uint64), 0
        cur = (cur << np.uint64(w)) | (rows[:, c] - lo[c]).astype(np.uint64)
        used += w
    words.append(cur)
    return np.ascontiguousarray(np.stack(words, axis=1)).view(
        f"V{8 * len(words)}").reshape(-1)


class Expected:
    """A reference result, sorted once, against which answers are counted."""

    def __init__(self, want: np.ndarray):
        want = np.asarray(want, np.int64)
        self.n, self.width = want.shape
        self.lo = want.min(axis=0) if self.n else np.zeros(self.width, int)
        hi = want.max(axis=0) if self.n else self.lo
        self.widths = [max(int(h - l).bit_length(), 1)
                       for h, l in zip(hi, self.lo)]
        self.hi = hi
        self.records = np.sort(_packed(want, self.lo, self.widths))

    def rows_wrong(self, got: np.ndarray) -> int:
        """Rows by which `got` differs from the reference as a multiset:
        missing plus extra."""
        got = np.asarray(got, np.int64)
        if got.ndim != 2 or got.shape[1] != self.width:
            return self.n + len(got)
        inside = ((got >= self.lo) & (got <= self.hi)).all(axis=1)
        outside = int(len(got) - inside.sum())
        g = np.sort(_packed(got[inside], self.lo, self.widths))
        if len(g) == self.n and np.array_equal(g, self.records):
            return outside
        keys, inv = np.unique(np.concatenate([g, self.records]),
                              return_inverse=True)
        inv = inv.reshape(-1)
        cg = np.bincount(inv[:len(g)], minlength=len(keys))
        cw = np.bincount(inv[len(g):], minlength=len(keys))
        return outside + int(np.abs(cg - cw).sum())
