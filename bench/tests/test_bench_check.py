"""The plain reference, the comparison, and the control that must fail it."""
import itertools

import numpy as np
import pytest

from bench import check, gen

# The queries of the paper's examples and of the Shares paper's chain joins,
# each with the relation that holds each join attribute's heavy hitter.
TENANTS = {
    "paper3": {"relations": [["R", ["A", "B"]], ["S", ["B", "E", "C"]],
                             ["T", ["C", "D"]]],
               "heavy": {"B": "R", "C": "T"}},
    "pair": {"relations": [["R", ["A", "B"]], ["S", ["B", "C"]]],
             "heavy": {"B": "R"}},
    "chain4": {"relations": [["R0", ["X0", "X1"]], ["R1", ["X1", "X2"]],
                             ["R2", ["X2", "X3"]], ["R3", ["X3", "X4"]]],
               "heavy": {"X1": "R1", "X2": "R1", "X3": "R2"}},
}
CONFIG = {"generator": "pinned_skew", "tenants": TENANTS,
          "heavy_share": 4, "light_rows": 4}


def _loops(relations, data):
    """Nested loops over every combination of rows: the definition."""
    attrs = gen.attributes(relations)
    out = []
    for combo in itertools.product(*(data[n] for n, _ in relations)):
        row = {}
        ok = True
        for (name, rattrs), tup in zip(relations, combo):
            for a, v in zip(rattrs, tup):
                if row.setdefault(a, v) != v:
                    ok = False
        if ok:
            out.append([row[a] for a in attrs])
    return np.asarray(out, np.int64).reshape(-1, len(attrs))


@pytest.mark.parametrize("tenant", ["paper3", "pair", "chain4"])
def test_reference_is_the_natural_join(tenant):
    rels = gen.relations(CONFIG, tenant)
    rng = np.random.default_rng(3)
    data = {n: rng.integers(0, 4, size=(9, len(a))) for n, a in rels}
    want = _loops(rels, data)
    got = check.natural_join(rels, data)
    assert check.Expected(want).rows_wrong(got) == 0 and len(got) == len(want)
    assert check.prefix_sizes(rels, data)[-1] == len(want)


def test_rows_wrong_counts_missing_and_extra_rows():
    rng = np.random.default_rng(0)
    want = rng.integers(0, 1 << 22, size=(1000, 5))
    assert check.Expected(want).rows_wrong(want[::-1]) == 0
    bad = want.copy()
    bad[10, 3] += 1
    assert check.Expected(want).rows_wrong(bad) == 2
    assert check.Expected(want).rows_wrong(want[:600]) == 400
    assert check.Expected(want).rows_wrong(np.concatenate([want, want[:5]])) == 5
    assert check.Expected(want).rows_wrong(want[:, :4]) == 2000


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("tenant", ["paper3", "pair", "chain4"])
def test_control_fails_the_comparison(tenant, seed):
    """Matching on a hash of log2(rows) + 8 bits without checking the key
    invents rows, down to a size far below the cell's."""
    rels = gen.relations(CONFIG, tenant)
    data = gen.tenant_data(CONFIG, tenant, 8193, (seed, 1, 0))
    want = check.natural_join(rels, data)
    assert check.Expected(want).rows_wrong(check.hash_match_join(rels, data)) > 0


# A cyclic query: T joins the prefix on C and A, and no earlier relation
# holds both, so the fingerprint is taken over the built join.
TRIANGLE = {"relations": [["R", ["A", "B"]], ["S", ["B", "C"]],
                          ["T", ["C", "A"]]],
            "heavy": {"A": "R", "B": "S", "C": "T"}}

# Acyclic queries that are not paths, each in a cascade order in which every
# relation joins the prefix: a star, whose dimensions both hang off the fact
# table, and the join graph of TPC-H Q9, a snowflake in which lineitem joins
# partsupp on two attributes and nation hangs off supplier, once with the
# fact table first and once with part first.
Q9 = {"lineitem": ("O", "P", "S", "Q"), "part": ("P", "Pn"),
      "supplier": ("S", "N"), "partsupp": ("P", "S", "C"),
      "orders": ("O", "D"), "nation": ("N", "Nn")}
TREES = {
    "star": [("F", ("A", "B", "X")), ("DA", ("A", "Y")), ("DB", ("B", "Z"))],
    "q9": [(r, Q9[r]) for r in ("lineitem", "part", "supplier", "partsupp",
                                "orders", "nation")],
    "q9_part_first": [(r, Q9[r]) for r in ("part", "lineitem", "supplier",
                                           "partsupp", "orders", "nation")],
}


def _zipf(rng, n_keys, n):
    """`n` keys of 0..n_keys - 1, the i-th most frequent drawn with weight
    1/i (Zipf, z = 1), the ranks shuffled."""
    w = 1.0 / np.arange(1, n_keys + 1)
    return rng.permutation(n_keys)[rng.choice(n_keys, n, p=w / w.sum())]


def _bag(rng, cols, dangling):
    """A table from its columns, with some rows repeated (a bag) and
    `dangling` rows whose first column no other table holds."""
    t = np.stack(cols, axis=1)
    extra = t[rng.integers(0, len(t), size=max(len(t) // 32, 1))]
    lost = t[:dangling].copy()
    lost[:, 0] += 1 << 20
    return rng.permutation(np.concatenate([t, extra, lost]))


def _tree_data(shape, rows, seed):
    """Seeded tables of a tree query at `rows` fact rows: dimensions keyed
    by primary keys, the fact's foreign keys Zipf-drawn over them."""
    rng = np.random.default_rng(seed)
    if shape == "star":
        n_a, n_b = rows // 4, rows // 16
        return {"F": _bag(rng, [_zipf(rng, n_a, rows), _zipf(rng, n_b, rows),
                                np.arange(rows)], 0),
                "DA": _bag(rng, [np.arange(n_a), rng.integers(0, 9, n_a)], 3),
                "DB": _bag(rng, [np.arange(n_b), rng.integers(0, 9, n_b)], 3)}
    n_part, n_supp, n_ord = rows // 4, rows // 16, rows // 4
    ps_part = np.repeat(np.arange(n_part), 4)
    ps_supp = (ps_part + np.tile(np.arange(4), n_part) * (n_supp // 4 + 1)
               ) % n_supp
    li = _zipf(rng, len(ps_part), rows)
    return {
        "lineitem": _bag(rng, [_zipf(rng, n_ord, rows), ps_part[li],
                               ps_supp[li], rng.integers(1, 51, rows)], 0),
        "part": _bag(rng, [np.arange(n_part), rng.integers(0, 7, n_part)], 3),
        "supplier": _bag(rng, [np.arange(n_supp),
                               _zipf(rng, 25, n_supp)], 3),
        "partsupp": _bag(rng, [ps_part, ps_supp,
                               rng.integers(1, 1000, len(ps_part))], 3),
        "orders": _bag(rng, [np.arange(n_ord),
                             rng.integers(1992, 1999, n_ord)], 3),
        "nation": _bag(rng, [np.arange(25), np.arange(25)], 1)}


def _request(tenant, rows, seed):
    if tenant in TREES:
        return TREES[tenant], _tree_data(tenant, rows, seed)
    config = {**CONFIG, "tenants": {**TENANTS, "triangle": TRIANGLE}}
    rels = gen.relations(config, tenant)
    return rels, gen.tenant_data(config, tenant, rows, (seed, 1, 0))


@pytest.mark.parametrize("tenant,rows,seed", [
    ("paper3", 4096, 1), ("paper3", 4096, 2), ("paper3", 65536, 3),
    ("paper3", 16384, 2**31 + 9), ("pair", 8193, 4), ("chain4", 8193, 5),
    ("triangle", 2048, 6), ("star", 4096, 7), ("star", 8192, 2**31 + 10),
    ("q9", 4096, 8), ("q9", 8192, 12), ("q9_part_first", 4096, 13)])
def test_fingerprint_join_is_the_fingerprint_of_the_join(
        monkeypatch, tenant, rows, seed):
    rels, data = _request(tenant, rows, seed)
    keys = check.Keys(rels, data, (seed, 2, 0))
    want = check.natural_join(rels, data)
    prefixes = [len(check.natural_join(rels[:j + 1], data))
                for j in range(len(rels))]
    built = []
    join = check.natural_join
    monkeypatch.setattr(check, "natural_join",
                        lambda *a: built.append(1) or join(*a))
    n, fps = check.fingerprint_join(rels, data, keys)
    assert n == len(want) and len(fps) == check.N_KEYS
    assert fps == check.fingerprint_rows(want, keys)
    assert fps == check.fingerprint_rows(
        np.random.default_rng(seed).permutation(want), keys)
    assert check.prefix_sizes(rels, data) == prefixes
    # Only the cyclic query has no join tree, and only it builds the join.
    assert (check._join_tree(rels, data) is None) == (tenant == "triangle")
    assert bool(built) == (tenant == "triangle")
    assert check.Reference(rels, data, (seed, 2, 0)).rows_wrong(want) == 0


@pytest.mark.parametrize("tenant,parents", [
    ("paper3", [0, 1]), ("chain4", [0, 1, 2]), ("star", [0, 0]),
    ("q9", [0, 0, 0, 0, 2]), ("q9_part_first", [0, 1, 1, 1, 2])])
def test_join_tree_gives_each_relation_its_latest_parent(tenant, parents):
    """A path's parent is the relation before; partsupp's is lineitem, the
    one relation holding both its join attributes; nation's is supplier."""
    rels, data = _request(tenant, 1024, 1)
    assert [p for p, _, _ in check._join_tree(rels, data)] == parents


def _swap(want):
    """Two rows' values of the last column swapped, rows that differ in
    that column and in the first."""
    i = 0
    j = int(np.nonzero((want[:, -1] != want[i, -1])
                       & (want[:, 0] != want[i, 0]))[0][0])
    bad = want.copy()
    bad[[i, j], -1] = want[[j, i], -1]
    return bad


def _duplicated(want):
    bad = want.copy()
    j = int(np.nonzero((want != want[0]).any(axis=1))[0][0])
    bad[j] = want[0]
    return bad


def _altered(want):
    bad = want.copy()
    bad[len(bad) // 2, 2] += 1
    return bad


FAULTS = {
    "value_altered": _altered,
    "row_dropped": lambda w: np.delete(w, 7, axis=0),
    "row_duplicated_for_another": _duplicated,
    "column_values_swapped": _swap,
    "padding_row": lambda w: np.concatenate([w[1:], -np.ones((1, w.shape[1]),
                                                             w.dtype)]),
    "wrong_width": lambda w: w[:, :-1],
    "hash_match": None,
}


@pytest.mark.parametrize("tenant,fault", [
    pytest.param("paper3", f, id=f) for f in sorted(FAULTS)] + [
    pytest.param("q9", f, id=f"q9-{f}") for f in sorted(FAULTS)])
def test_fingerprint_tells_a_wrong_answer_apart(tenant, fault):
    """Each fault changes the fingerprint or the row count, and the rows
    wrong are then counted exactly, as by the sorted multisets: on the
    paper's path and on the Q9 snowflake."""
    rels, data = _request(tenant, 8193, 11)
    want = check.natural_join(rels, data)
    bad = (check.hash_match_join(rels, data) if fault == "hash_match"
           else FAULTS[fault](want))
    ref = check.Reference(rels, data, (11, 2, 0))
    assert (len(bad) != ref.n
            or check.fingerprint_rows(bad, ref.keys) != ref.fingerprints)
    assert ref.exact is None
    n = ref.rows_wrong(bad)
    assert n > 0 and n == check.Expected(want).rows_wrong(bad)
    assert ref.exact is not None


@pytest.mark.parametrize("lo,hi,cols", [
    (0, 5, 2), (-3, 3, 3), (0, 1 << 30, 2), (-(1 << 40), 1 << 40, 2),
    (0, 1 << 20, 4)], ids=["narrow", "negative", "int32", "wide", "four"])
def test_key_ids_rank_rows_as_unique_does(lo, hi, cols):
    """Packed or not, a key of several columns gets the rank of its row
    among the distinct rows, so the control's hashes of it stay put."""
    rng = np.random.default_rng(cols)
    keys = rng.integers(lo, hi, size=(5000, cols))
    keys[::7] = keys[1::7][:len(keys[::7])]
    _, want = np.unique(keys, axis=0, return_inverse=True)
    assert np.array_equal(check._key_ids(keys), want.reshape(-1))
    assert len(check._key_ids(keys[:0])) == 0
