"""SkewShares MoE dispatch planner: balance, routing validity, closed forms."""
import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st


from repro.core.moe_shares import (MoEDispatchPlan, dispatch_cost,
                                   plan_dispatch, route_tokens, shares_split)


def test_uniform_loads_one_slot_each():
    plan = plan_dispatch(np.full(8, 100.0), 8)
    assert (plan.group_size == 1).all()
    assert (plan.slot_to_expert == np.arange(8)).all()


def test_hot_expert_gets_replicas():
    loads = np.array([1000.0] + [10.0] * 7)
    plan = plan_dispatch(loads, 16)
    assert plan.group_size[0] == 8          # all spare budget on the hot expert
    assert plan.group_size[1:].max() == 1
    slot_loads = plan.expected_slot_loads(loads)
    assert slot_loads.max() <= 1000.0 / 8 + 1e-9


def test_classical_vs_skewshares_imbalance():
    """The headline MoE claim: hot-expert straggle collapses under replication."""
    rng = np.random.default_rng(0)
    loads = np.r_[[4096.0], rng.uniform(10, 60, 63)]     # one very hot expert
    classical = plan_dispatch(loads, 64)                 # no spare slots -> g=1
    skew = plan_dispatch(loads, 128)                     # 2x slots, Shares split
    c = dispatch_cost(loads, classical, weight_cost=100)
    s = dispatch_cost(loads, skew, weight_cost=100)
    assert c["max_slot_load"] == 4096.0
    assert s["max_slot_load"] <= c["max_slot_load"] / 16


@settings(max_examples=30, deadline=None)
@given(
    e=st.integers(2, 64),
    spare_pow=st.integers(0, 3),
    seed=st.integers(0, 2**31 - 1),
)
def test_balance_property(e, spare_pow, seed):
    rng = np.random.default_rng(seed)
    loads = rng.pareto(1.2, size=e) * 100 + 1
    n_slots = e * (1 << spare_pow)
    plan = plan_dispatch(loads, n_slots)
    assert plan.group_size.sum() <= n_slots
    assert (plan.group_size & (plan.group_size - 1)).max() == 0   # powers of two
    # Every expert has exactly group_size valid slots, all distinct.
    flat = plan.slots_of_expert[plan.slots_of_expert >= 0]
    assert len(np.unique(flat)) == len(flat)
    # Greedy can't be worse than no replication at all.
    assert plan.expected_slot_loads(loads).max() <= loads.max() + 1e-9


def test_route_tokens_valid_and_balanced():
    loads = np.array([10000.0] + [100.0] * 15)
    plan = plan_dispatch(loads, 32)
    g0 = int(plan.group_size[0])
    assert g0 >= 8
    n = 50_000
    expert_ids = jnp.zeros(n, jnp.int32)            # all tokens to hot expert 0
    token_ids = jnp.arange(n, dtype=jnp.int32)
    slots = np.asarray(route_tokens(plan, expert_ids, token_ids))
    valid_slots = plan.slots_of_expert[0, :g0]
    assert set(slots.tolist()) <= set(valid_slots.tolist())
    counts = np.bincount(slots, minlength=plan.n_slots)[valid_slots]
    assert counts.max() <= 1.3 * counts.mean()      # hash split is even


def test_route_tokens_single_slot_expert():
    plan = plan_dispatch(np.full(4, 1.0), 4)
    slots = np.asarray(route_tokens(
        plan, jnp.array([0, 1, 2, 3, 2]), jnp.arange(5)))
    np.testing.assert_array_equal(slots, [0, 1, 2, 3, 2])


def test_shares_split_closed_form():
    x, y = shares_split(tokens=10**6, weight_cost=10**4, k=16)
    assert x * y == pytest.approx(16, rel=1e-9)
    # Token side dominates -> more token partitions than weight partitions.
    assert x > y
    # Balanced case.
    x, y = shares_split(10**5, 10**5, 16)
    assert x == pytest.approx(4) and y == pytest.approx(4)
    # Clamping: tiny token side never drives x below 1.
    x, y = shares_split(1, 10**6, 4)
    assert x == 1.0 and y == 4.0
