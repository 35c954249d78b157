"""Shares optimizer: paper Example 1.2 + optimality against brute force."""
import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st


from repro.core import (JoinQuery, Relation, brute_force_shares,
                        cost_expression, naive_hh_cost, optimize_shares,
                        optimize_shares_expr, shares_hh_cost, shares_hh_splits,
                        solve_continuous, triangle, two_way)


# ---------------------------------------------------------------------------
# Example 1.2: the HH residual of R(A,B) ⋈ S(B,C) has cost ry + sx, xy = k,
# optimum 2√(krs), always ≤ naive r + ks; optimum grows as √k vs linear.
# ---------------------------------------------------------------------------

def test_example_1_2_closed_form():
    r, s, k = 1_000_000, 10_000, 64
    x, y = shares_hh_splits(r, s, k)
    assert math.isclose(x * y, k, rel_tol=1e-9)
    assert math.isclose(r * y + s * x, shares_hh_cost(r, s, k), rel_tol=1e-9)


@given(r=st.integers(1, 10**9), s=st.integers(1, 10**9), k=st.integers(1, 4096))
def test_example_1_2_beats_naive(r, s, k):
    # 2√(krs) ≤ r + ks  (AM-GM) — the paper's headline comparison.
    assert shares_hh_cost(r, s, k) <= naive_hh_cost(r, s, k) + 1e-6 * naive_hh_cost(r, s, k)


def test_example_1_2_sqrt_k_growth():
    r, s = 10**7, 10**5
    costs = [shares_hh_cost(r, s, k) for k in (16, 64, 256)]
    # quadrupling k should double (√k) the optimal cost, not quadruple it
    assert costs[1] / costs[0] == pytest.approx(2.0, rel=1e-6)
    assert costs[2] / costs[1] == pytest.approx(2.0, rel=1e-6)
    naive = [naive_hh_cost(r, s, k) for k in (16, 64, 256)]
    # naive grows linearly in k: marginal cost quadruples when k quadruples
    assert (naive[2] - naive[1]) / (naive[1] - naive[0]) == pytest.approx(4.0, rel=1e-6)


def test_hh_residual_matches_closed_form():
    # Freeze B (the HH attribute): cost expression r·y(C) + s·x(A), shares xy=k.
    r, s, k = 3_000_000, 40_000, 256
    q = two_way(r, s)
    sol = optimize_shares(q, k, frozen=frozenset({"B"}))
    assert sol.shares["B"] == 1
    assert sol.shares["A"] * sol.shares["C"] == k
    # Integer power-of-two optimum is within √2 of the continuous optimum.
    assert sol.cost <= math.sqrt(2.0) * shares_hh_cost(r, s, k) * (1 + 1e-9)
    assert sol.cont_cost == pytest.approx(shares_hh_cost(r, s, k), rel=1e-3)


# ---------------------------------------------------------------------------
# No-skew residual of the 2-way join: budget soaks into the join attribute.
# ---------------------------------------------------------------------------

def test_ordinary_two_way_all_budget_on_join_attr():
    q = two_way(10**6, 10**6)
    sol = optimize_shares(q, 64)
    assert sol.shares["B"] == 64
    assert sol.shares["A"] == sol.shares["C"] == 1
    assert sol.cost == pytest.approx(2 * 10**6)     # r + s, no replication


# ---------------------------------------------------------------------------
# Triangle query: known Shares result — symmetric sizes give equal shares k^(1/3).
# ---------------------------------------------------------------------------

def test_triangle_symmetric_shares():
    q = triangle(10**6, 10**6, 10**6)
    sol = optimize_shares(q, 64)
    assert sorted(sol.shares.values()) == [4, 4, 4]
    assert sol.cost == pytest.approx(3 * 10**6 * 4)  # each relation replicated k^(1/3)


def test_triangle_continuous_cost_scaling():
    # Known: optimal triangle communication = 3 r k^(1/3) for equal sizes.
    r, k = 10**6, 512
    expr = cost_expression(triangle(r, r, r))
    cont = solve_continuous(expr, k)
    assert expr.evaluate(cont) == pytest.approx(3 * r * k ** (1 / 3), rel=1e-3)


# ---------------------------------------------------------------------------
# Integer rounding is optimal (vs brute force over all factorizations of k).
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(
    sizes=st.lists(st.integers(1, 10**6), min_size=2, max_size=3),
    logk=st.integers(0, 6),
)
def test_pow2_rounding_matches_bruteforce_two_and_three_way(sizes, logk):
    k = 1 << logk
    if len(sizes) == 2:
        q = JoinQuery((Relation("R", ("A", "B"), sizes[0]),
                       Relation("S", ("B", "C"), sizes[1])))
        frozen = frozenset({"B"})   # HH residual: both A and C free
    else:
        q = triangle(*sizes)
        frozen = frozenset()
    expr = cost_expression(q, frozen)
    sol = optimize_shares_expr(expr, k)
    _, bf_cost = brute_force_shares(expr, k)
    # Brute force allows non-power-of-2 factorizations, so it may be slightly
    # better; our pow2 solution must be within 2x (worst case for pow2 grids)
    # and never better than the true optimum.
    assert sol.cost >= bf_cost - 1e-6 * max(1.0, bf_cost)
    assert sol.cost <= 2.0 * bf_cost + 1e-6


@settings(max_examples=20, deadline=None)
@given(
    r=st.integers(1, 10**8), s=st.integers(1, 10**8), logk=st.integers(0, 8),
)
def test_continuous_is_lower_bound(r, s, logk):
    k = 1 << logk
    q = two_way(r, s)
    sol = optimize_shares(q, k, frozen=frozenset({"B"}))
    assert sol.cont_cost <= sol.cost + 1e-6 * max(1.0, sol.cost)
    # Continuous optimum matches the closed form 2√(krs) whenever the
    # unconstrained optimum is feasible (x=√(kr/s) ≥ 1 and y=√(ks/r) ≥ 1);
    # otherwise the x,y ≥ 1 constraint binds and the solver must do better
    # than naively clamping.
    x, y = shares_hh_splits(r, s, k)
    if x >= 1.0 and y >= 1.0:
        assert sol.cont_cost == pytest.approx(shares_hh_cost(r, s, k), rel=5e-3)
    else:
        clamp = min(r * k + s, s * k + r)   # all budget on one side
        assert sol.cont_cost <= clamp * (1 + 5e-3)


def test_reducers_used_equals_k():
    q = triangle(5, 1000, 100000)
    for k in (1, 2, 8, 64, 128):
        sol = optimize_shares(q, k)
        used = 1
        for v in sol.shares.values():
            used *= v
        assert used == k
