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
CONFIG = {"tenants": TENANTS, "heavy_share": 4, "light_rows": 4}


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


# A cyclic query: T joins the prefix on C and A, and S, the relation before
# it, lacks A, so the fingerprint is taken over the built join.
TRIANGLE = {"relations": [["R", ["A", "B"]], ["S", ["B", "C"]],
                          ["T", ["C", "A"]]],
            "heavy": {"A": "R", "B": "S", "C": "T"}}


def _request(tenant, rows, seed):
    config = {**CONFIG, "tenants": {**TENANTS, "triangle": TRIANGLE}}
    rels = gen.relations(config, tenant)
    return rels, gen.tenant_data(config, tenant, rows, (seed, 1, 0))


@pytest.mark.parametrize("tenant,rows,seed", [
    ("paper3", 4096, 1), ("paper3", 4096, 2), ("paper3", 65536, 3),
    ("paper3", 16384, 2**31 + 9), ("pair", 8193, 4), ("chain4", 8193, 5),
    ("triangle", 2048, 6)])
def test_fingerprint_join_is_the_fingerprint_of_the_join(tenant, rows, seed):
    rels, data = _request(tenant, rows, seed)
    assert (check._path_keys(rels, data) is None) == (tenant == "triangle")
    keys = check.Keys(rels, data, (seed, 2, 0))
    want = check.natural_join(rels, data)
    n, fps = check.fingerprint_join(rels, data, keys)
    assert n == len(want) and len(fps) == check.N_KEYS
    assert fps == check.fingerprint_rows(want, keys)
    assert fps == check.fingerprint_rows(
        np.random.default_rng(seed).permutation(want), keys)
    assert check.Reference(rels, data, (seed, 2, 0)).rows_wrong(want) == 0


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


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fingerprint_tells_a_wrong_answer_apart(fault):
    """Each fault changes the fingerprint or the row count, and the rows
    wrong are then counted exactly, as by the sorted multisets."""
    rels, data = _request("paper3", 8193, 11)
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
