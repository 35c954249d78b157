"""repro.core — the paper's contribution: skew-resilient multiway joins.

Public surface:
  plan         — JoinQuery / Relation IR
  shares       — the Shares optimizer (continuous + integer power-of-two)
  dominance    — dominance rule (share-1 attributes)
  residual     — heavy-hitter residual-join decomposition
  heavy_hitters— exact + Misra-Gries HH detection
  adapt        — online drift detection (windowed loads + HH sketches)
  cost         — communication-cost expressions and analytic baselines
  hypercube    — tuple -> reducer-cell routing
  placement    — logical cell -> physical device fold (LPT / modulo)
  skewjoin     — end-to-end planner (SkewJoinPlan)
  reference    — numpy multiway-join oracle
  executor     — shard_map distributed execution engine
  moe_shares   — the technique instantiated for MoE expert dispatch
"""
from .adapt import AdaptPolicy, DriftDetector, tv_distance
from .cost import (CostExpression, CostTerm, cost_expression, naive_hh_cost,
                   shares_hh_cost, shares_hh_splits)
from .dominance import dominated_attributes, dominates, free_share_attributes
from .heavy_hitters import HHSet, MisraGries, exact_heavy_hitters
from .hypercube import Hypercube, hash_seed, multiply_shift
from .placement import (CellPlacement, lpt_placement, modulo_placement,
                        place_cells, placement_gain)
from .plan import JoinQuery, Relation, running_example, triangle, two_way
from .reference import canonical, cascade_sizes, reference_join
from .residual import (ORDINARY, ResidualJoin, TypeCombination, decompose,
                       enumerate_combinations, residual_sizes, tuple_mask)
from .shares import (SharesSolution, brute_force_shares, optimize_shares,
                     optimize_shares_expr, round_pow2, solve_continuous)
from .skewjoin import (ResidualPlan, SkewJoinPlan, naive_two_way_cost,
                       plan_from_hhs, plan_no_skew, plan_skew_join)

__all__ = [
    "AdaptPolicy", "DriftDetector", "tv_distance",
    "CostExpression", "CostTerm", "cost_expression", "naive_hh_cost",
    "shares_hh_cost", "shares_hh_splits", "dominated_attributes", "dominates",
    "free_share_attributes", "HHSet", "MisraGries", "exact_heavy_hitters",
    "Hypercube", "hash_seed", "multiply_shift", "CellPlacement",
    "lpt_placement", "modulo_placement", "place_cells", "placement_gain",
    "JoinQuery", "Relation",
    "running_example", "triangle", "two_way", "canonical", "cascade_sizes",
    "reference_join",
    "ORDINARY", "ResidualJoin", "TypeCombination", "decompose",
    "enumerate_combinations", "residual_sizes", "tuple_mask", "SharesSolution",
    "brute_force_shares", "optimize_shares", "optimize_shares_expr",
    "round_pow2", "solve_continuous", "ResidualPlan", "SkewJoinPlan",
    "naive_two_way_cost", "plan_from_hhs", "plan_no_skew", "plan_skew_join",
]
