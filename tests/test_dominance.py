"""Dominance rule + Theorem 5.1 (auxiliary attributes get share 1)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st


from repro.core import (JoinQuery, Relation, cost_expression,
                        dominated_attributes, dominates,
                        free_share_attributes, optimize_shares,
                        running_example, two_way)


def test_basic_dominance():
    q = running_example()
    # A appears only in R; B appears in R and S -> B dominates A.
    assert dominates(q, "B", "A")
    assert not dominates(q, "A", "B")
    assert dominates(q, "C", "D")
    assert dominates(q, "B", "E") and dominates(q, "C", "E")
    assert dominated_attributes(q) == frozenset({"A", "D", "E"})


def test_frozen_attrs_cannot_dominate():
    """Example 5.2 item 2: with B frozen, A is no longer dominated."""
    q = running_example()
    dom = dominated_attributes(q, frozen=frozenset({"B"}))
    assert "A" not in dom
    assert dom == frozenset({"D", "E"})   # C still dominates D and E


def test_mutual_dominance_breaks_deterministically():
    q = JoinQuery((Relation("R", ("A", "B"), 10),))
    # A and B appear in exactly the same relations; lexicographically smaller wins.
    assert dominated_attributes(q) == frozenset({"B"})
    assert free_share_attributes(q) == ("A",)


def test_theorem_5_1_shares_of_frozen_are_one():
    """HH-typed (auxiliary-collapsed) attributes always end with share 1."""
    q = running_example(10**6, 10**5, 10**4)
    for frozen in [frozenset({"B"}), frozenset({"C"}), frozenset({"B", "C"})]:
        sol = optimize_shares(q, 256, frozen=frozen)
        for a in frozen:
            assert sol.shares[a] == 1


@settings(max_examples=40, deadline=None)
@given(st.data())
def test_dominated_never_get_shares_random_queries(data):
    """Property: for random acyclic-ish queries, dominated/frozen attrs -> share 1,
    and the product of free shares is exactly k."""
    n_rel = data.draw(st.integers(1, 4))
    attrs_pool = list("ABCDEF")
    rels = []
    for i in range(n_rel):
        arity = data.draw(st.integers(1, 3))
        attrs = tuple(sorted(data.draw(
            st.sets(st.sampled_from(attrs_pool), min_size=arity, max_size=arity))))
        size = data.draw(st.integers(1, 10**6))
        rels.append(Relation(f"R{i}", attrs, size))
    q = JoinQuery(tuple(rels))
    join_attrs = list(q.join_attributes())
    frozen = frozenset(data.draw(st.sets(st.sampled_from(join_attrs))) if join_attrs else [])
    k = 1 << data.draw(st.integers(0, 6))
    sol = optimize_shares(q, k, frozen=frozen)
    dom = dominated_attributes(q, frozen)
    for a in q.attributes:
        if a in frozen or a in dom:
            assert sol.shares[a] == 1
    free = free_share_attributes(q, frozen)
    prod = 1
    for a in free:
        prod *= sol.shares[a]
    if free:
        assert prod == k
    else:
        # All attributes frozen/dominated (the paper's footnote-4 degenerate:
        # an all-auxiliary residual holds one tuple per relation) — no share
        # variables exist, so the block is a single cell.
        assert prod == 1
    # Cost expression never mentions frozen/dominated attributes.
    expr = cost_expression(q, frozen)
    for t in expr.terms:
        assert not (t.repl_attrs & frozen)
        assert not (t.repl_attrs & dom)
