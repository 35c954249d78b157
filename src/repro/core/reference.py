"""Reference (single-machine) multiway natural join — correctness oracle.

Plain left-to-right join cascade in numpy, each step grouping keys by sort.
Output columns follow the query's attribute order (`query.attributes`).  Used
by tests and benchmarks to validate the distributed executor and the
local-join kernels.
"""
from __future__ import annotations

from typing import Mapping

import numpy as np

from .plan import JoinQuery


def join_two(
    left: np.ndarray, left_attrs: tuple[str, ...],
    right: np.ndarray, right_attrs: tuple[str, ...],
) -> tuple[np.ndarray, tuple[str, ...]]:
    """Natural join of two column-store arrays; returns (rows, attrs)."""
    common = [a for a in left_attrs if a in right_attrs]
    out_attrs = tuple(left_attrs) + tuple(a for a in right_attrs if a not in common)
    if left.size == 0 or right.size == 0:
        return np.zeros((0, len(out_attrs)), dtype=np.int64), out_attrs
    if not common:
        li = np.repeat(np.arange(len(left)), len(right))
        ri = np.tile(np.arange(len(right)), len(left))
    else:
        lkey = left[:, [left_attrs.index(a) for a in common]]
        rkey = right[:, [right_attrs.index(a) for a in common]]
        # Number the distinct keys of both sides one column at a time (ids
        # stay below the row count and values span int32, so int64 holds
        # the pair), group right rows by key number (in row order inside a
        # group), and pair every left row with its key's group.
        keys = np.concatenate([lkey, rkey])
        ids = np.zeros(len(keys), np.int64)
        for col in keys.T:
            col = col - col.min()
            _, ids = np.unique(ids * (int(col.max()) + 1) + col,
                               return_inverse=True)
            ids = ids.reshape(-1)
        lid, rid = ids[:len(left)], ids[len(left):]
        order = np.argsort(rid, kind="stable")
        start = np.searchsorted(rid[order], lid, side="left")
        cnt = np.searchsorted(rid[order], lid, side="right") - start
        if not cnt.sum():
            return np.zeros((0, len(out_attrs)), dtype=np.int64), out_attrs
        li = np.repeat(np.arange(len(left)), cnt)
        first = np.cumsum(cnt) - cnt                # li's first output slot
        ri = order[np.repeat(start - first, cnt) + np.arange(cnt.sum())]
    extra = [right_attrs.index(a) for a in right_attrs if a not in common]
    rows = np.concatenate([left[li], right[ri][:, extra].reshape(len(ri), -1)], axis=1)
    return rows.astype(np.int64), out_attrs


def reference_join(query: JoinQuery, data: Mapping[str, np.ndarray]) -> np.ndarray:
    """Full natural multiway join; columns ordered as `query.attributes`."""
    rels = list(query.relations)
    acc, attrs = data[rels[0].name].astype(np.int64), tuple(rels[0].attrs)
    for rel in rels[1:]:
        acc, attrs = join_two(acc, attrs, data[rel.name].astype(np.int64), tuple(rel.attrs))
    order = [attrs.index(a) for a in query.attributes]
    out = acc[:, order]
    # Canonical row order for multiset comparison.
    if len(out):
        out = out[np.lexsort(out.T[::-1])]
    return out


def cascade_sizes(query: JoinQuery, data: Mapping[str, np.ndarray]
                  ) -> list[int]:
    """Row counts of every prefix R0 ⋈ … ⋈ Rj of the left-to-right cascade.

    Counts without materializing: for a path query, where each relation
    shares exactly one attribute with the prefix and that attribute belongs
    to the relation before it, the number of prefix tuples ending in each
    row of Rj is a weighted histogram of the previous relation's weights.
    Returns [|R0|, |R0 ⋈ R1|, ...]; raises ValueError for other shapes.
    """
    rels = list(query.relations)
    w = np.ones(len(data[rels[0].name]), np.float64)
    sizes = [len(w)]
    for prev, rel in zip(rels, rels[1:]):
        seen = {a for r in rels[:rels.index(rel)] for a in r.attrs}
        shared = [a for a in rel.attrs if a in seen]
        if len(shared) != 1 or shared[0] not in prev.attrs:
            raise ValueError(f"{query} is not a path query")
        a = shared[0]
        pcol = data[prev.name][:, prev.attrs.index(a)]
        col = data[rel.name][:, rel.attrs.index(a)]
        hist = np.bincount(pcol, weights=w,
                           minlength=int(max(pcol.max(initial=0),
                                             col.max(initial=0))) + 1)
        w = hist[col]
        sizes.append(int(w.sum()))
    return sizes


def canonical(rows: np.ndarray) -> np.ndarray:
    """Sort rows lexicographically (multiset-comparable form)."""
    rows = np.asarray(rows, dtype=np.int64)
    if len(rows) == 0:
        return rows
    return rows[np.lexsort(rows.T[::-1])]
