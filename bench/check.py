"""The plain reference and the comparison that decides `correct`.

The reference is a left-to-right cascade of two-way natural joins in numpy,
written here from the definition and importing nothing of the program.  A
served request is right when its rows equal the reference's as a multiset:
every result row of the bag join, each exactly as often as the join makes
it.  `Reference.rows_wrong` counts the rows in which the two differ
(missing plus extra), so its limit is 0.

It decides first by fingerprint, without building the reference.  `Keys`
draws, from a seed the program never sees, `N_KEYS` tables that map each
attribute's values to uniform residues in [1, P).  A row's hash is the
product of its columns' residues mod P, and a multiset's fingerprint the
sum of its rows' hashes mod P: a multilinear polynomial in the residues
whose coefficients are the rows' multiplicities.  Two different multisets
of rows of c columns agree on one key with probability at most c / P
(Schwartz-Zippel), on all keys with at most (c / P) ** N_KEYS.  The
reference's fingerprint factorises along the join tree of an acyclic
query (`fingerprint_join`), so only the answer is read row by row; a
cyclic query's join is built.  Where the row count or a fingerprint
differs, the exact multiset (`Expected`) is built and counts the rows
wrong.

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
    """One int64 id per row of a (n, c) key matrix; equal rows, equal ids.
    A key of several columns gets its rank among the distinct rows in
    lexicographic order, as `np.unique(keys, axis=0)` gives it; where the
    columns' spans allow, the rows are packed into one int64 first, which
    keeps that order and sorts scalars, not records."""
    if keys.shape[1] == 1:
        return keys[:, 0].astype(np.int64)
    keys = np.asarray(keys, np.int64)
    if len(keys):
        lo = keys.min(axis=0)
        span = keys.max(axis=0) - lo + 1
        if float(np.prod(span.astype(float))) < 2.0 ** 62:
            packed = np.zeros(len(keys), np.int64)
            for c in range(keys.shape[1]):
                packed = packed * span[c] + (keys[:, c] - lo[c])
            keys = packed
    _, ids = np.unique(keys, axis=0 if keys.ndim == 2 else None,
                       return_inverse=True)
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


def _join_tree(relations: Relations, data: Mapping[str, np.ndarray]
               ) -> list[tuple[int, np.ndarray, np.ndarray]] | None:
    """The cascade as a join tree: for each relation after the first, its
    parent, an earlier relation that holds every attribute it shares with
    the prefix (the latest such, so a path's parent is the relation before
    it), and the ids of the parent's rows and of its own on those attributes
    (equal values, equal ids).  None where some relation has no parent, or
    shares nothing with the prefix: a cyclic query, or one in an order that
    breaks the running intersection."""
    out, seen = [], set(relations[0][1])
    for j, (name, attrs) in enumerate(relations[1:], 1):
        shared = [a for a in attrs if a in seen]
        parents = [i for i in range(j) if set(shared) <= set(relations[i][1])]
        if not shared or not parents:
            return None
        p_name, p_attrs = relations[parents[-1]]
        pk = np.asarray(data[p_name])[:, [tuple(p_attrs).index(a)
                                          for a in shared]]
        rk = np.asarray(data[name])[:, [tuple(attrs).index(a) for a in shared]]
        ids = _key_ids(np.concatenate([pk, rk]))
        out.append((parents[-1], ids[:len(pk)], ids[len(pk):]))
        seen |= set(attrs)
    return out


def _group_sum(left: np.ndarray, weight: np.ndarray | None,
               right: np.ndarray) -> np.ndarray:
    """For each right row, the sum of the weights of the left rows with its
    id: integers, summed exactly in their own dtype; with no weight, the
    number of such rows."""
    n = int(max(left.max(initial=0), right.max(initial=0))) + 1
    if weight is None:
        return np.bincount(left, minlength=n)[right]
    acc = np.zeros(n, weight.dtype)
    np.add.at(acc, left, weight)
    return acc[right]


def _fold(tree: list, weights: list[np.ndarray | None],
          modulus: int | None = None) -> np.ndarray:
    """The sum over the rows of the join of relations 0..len(weights) - 1
    of the product of their rows' weights, `weights[j]` of shape (K, rows
    of j) for K sums at once, or None for K = 1 and a weight of 1 per row
    (not for relation 0), without building the join.  Leaves first, each
    relation's rows hold their own weight times their children's messages
    and send their parent the sum of that over the rows of each key they
    share with it.  With `modulus` each product is reduced, so that a
    group's sum of residues below 2^31 stays below 2^64 while it holds
    fewer than 2^33 rows."""
    w = list(weights)
    for j in range(len(w) - 1, 0, -1):
        p, parent_ids, own_ids = tree[j - 1]
        msg = np.stack([_group_sum(own_ids, wk, parent_ids)
                        for wk in (w[j] if w[j] is not None else [None])])
        if modulus is None:
            w[p] = msg if w[p] is None else w[p] * msg
        else:
            w[p] = w[p] * (msg % np.uint64(modulus)) % np.uint64(modulus)
    total = w[0].sum(axis=1)
    return total if modulus is None else total % np.uint64(modulus)


def _counts(relations: Relations, data: Mapping[str, np.ndarray]) -> list:
    """`_fold`'s weights that count the join's rows."""
    return [np.ones((1, len(data[relations[0][0]])), np.int64)] + [
        None] * (len(relations) - 1)


def prefix_sizes(relations: Relations, data: Mapping[str, np.ndarray]
                 ) -> list[int]:
    """Row counts of each prefix R0 ⋈ … ⋈ Rj of the cascade, each a subtree
    of the join tree (`_join_tree`), counted without building it; built
    where there is no tree."""
    tree = _join_tree(relations, data)
    if tree is None:
        return [len(natural_join(relations[:j + 1], data))
                for j in range(len(relations))]
    ones = _counts(relations, data)
    return [int(_fold(tree, ones[:j + 1])[0]) for j in range(len(relations))]


P = (1 << 31) - 1   # prime; a product of two residues fits in uint64
N_KEYS = 2
CHUNK = 1 << 16     # rows hashed at a time, so that temporaries stay cached


class Keys:
    """`N_KEYS` random residues in [1, P) for every value of every
    attribute, over the range of values that the request's relations hold:
    `tables[a][k, v - lo[a]]` is key k's residue of value v.  Drawn from
    `seed` alone, after the window, so the program never sees them."""

    def __init__(self, relations: Relations, data: Mapping[str, np.ndarray],
                 seed):
        rng = np.random.default_rng(seed)
        self.attrs = attributes(relations)
        self.lo, self.tables = {}, {}
        for a in self.attrs:
            cols = [np.asarray(data[name])[:, tuple(attrs).index(a)]
                    for name, attrs in relations if a in attrs]
            lo = min(int(c.min(initial=0)) for c in cols)
            hi = max(int(c.max(initial=0)) for c in cols)
            self.lo[a] = lo
            self.tables[a] = rng.integers(1, P, size=(N_KEYS, hi - lo + 1),
                                          dtype=np.uint32)

    def index(self, values: np.ndarray, attr: str) -> np.ndarray | None:
        """Each value's place in `attr`'s tables; None where one lies
        outside them."""
        idx = np.asarray(values, np.intp) - self.lo[attr]
        if len(idx) and (idx.min() < 0
                         or idx.max() >= self.tables[attr].shape[1]):
            return None
        return idx


def _row_hashes(rows: np.ndarray, attrs, keys: Keys) -> np.ndarray | None:
    """(N_KEYS, n) products mod P of each row's residues over `attrs`, its
    columns; None where a value lies outside the tables."""
    idx = [keys.index(rows[:, c], a) for c, a in enumerate(attrs)]
    if any(i is None for i in idx):
        return None
    h = np.ones((N_KEYS, len(rows)), np.uint64)
    for k in range(N_KEYS):
        for i, a in zip(idx, attrs):
            h[k] *= keys.tables[a][k].take(i)
            h[k] %= np.uint64(P)
    return h


def fingerprint_rows(rows: np.ndarray, keys: Keys) -> tuple[int, ...] | None:
    """The fingerprints of a multiset of result rows, columns in
    `attributes` order: per key, the sum of the rows' hashes mod P.  None
    for rows that cannot be the join's: a wrong width, or a value outside
    the tables (the -1 padding among them)."""
    rows = np.asarray(rows)
    if rows.ndim != 2 or rows.shape[1] != len(keys.attrs):
        return None
    acc = np.zeros(N_KEYS, np.uint64)
    for start in range(0, len(rows), CHUNK):
        h = _row_hashes(rows[start:start + CHUNK], keys.attrs, keys)
        if h is None:
            return None
        acc = (acc + h.sum(axis=1)) % np.uint64(P)
    return tuple(int(x) for x in acc)


def fingerprint_join(relations: Relations, data: Mapping[str, np.ndarray],
                     keys: Keys) -> tuple[int, tuple[int, ...]]:
    """(rows, fingerprints) of the bag natural join, as `fingerprint_rows`
    would give them for `natural_join`'s rows.  Over the join tree
    (`_join_tree`) a relation's rows weigh the residues of the attributes
    they bring to the prefix, each attribute brought once, and the sums of
    the products are folded leaves first (`_fold`): the join is never
    built.  Built where there is no tree."""
    tree = _join_tree(relations, data)
    if tree is None:
        rows = natural_join(relations, data)
        return len(rows), fingerprint_rows(rows, keys)
    seen: set[str] = set()
    own = []
    for name, attrs in relations:
        new = [a for a in attrs if a not in seen]
        own.append(_row_hashes(np.asarray(data[name])[
            :, [tuple(attrs).index(a) for a in new]], new, keys))
        seen |= set(attrs)
    fps = _fold(tree, own, P)
    return (int(_fold(tree, _counts(relations, data))[0]),
            tuple(int(x) for x in fps))


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


class Reference:
    """One request's reference.  An answer is judged by its row count and
    fingerprints (`fingerprint_join`); only where one differs is the
    exact multiset built, once, to count the rows wrong."""

    def __init__(self, relations: Relations, data: Mapping[str, np.ndarray],
                 seed):
        self.relations, self.data = relations, data
        self.keys = Keys(relations, data, seed)
        self.n, self.fingerprints = fingerprint_join(relations, data,
                                                     self.keys)
        self.exact: Expected | None = None

    def rows_wrong(self, got: np.ndarray) -> int:
        """Rows by which `got` differs from the reference as a multiset:
        missing plus extra."""
        got = np.asarray(got)
        if (len(got) == self.n
                and fingerprint_rows(got, self.keys) == self.fingerprints):
            return 0
        if self.exact is None:
            self.exact = Expected(natural_join(self.relations, self.data))
        return self.exact.rows_wrong(got)
