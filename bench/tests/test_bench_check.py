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
