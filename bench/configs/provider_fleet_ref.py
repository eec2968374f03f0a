"""Plain reference for the provider fleet: one quota on one tier, shared
by every tenant of the fleet. Straight numpy on ``cost_ref``'s cost
model; nothing of the program is imported.

One quota on the fleet's total is one cap on the concatenated problem:
the tenants' datasets side by side form one ``(sum N, L, K)`` cost
tensor, and ``cost_ref.capped_solve``'s Lagrangian bound on it bounds the
cost of every fleet plan that keeps to the quota. Per tenant a plan is a
list of arrays, one entry per tenant, in the fleet's order.
"""

from __future__ import annotations

import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import cost_ref as ref  # noqa: E402


def fleet_use(spans, Rs, tiers, schemes, tier: int) -> float:
    """GB the whole fleet's plan puts on ``tier``."""
    return float(sum(ref.usage(s, R, l, k, tier + 1)[tier]
                     for s, R, l, k in zip(spans, Rs, tiers, schemes)))


def unconstrained_use(costs, spans, Rs, tier: int) -> float:
    """GB on ``tier`` when every tenant takes its exact unconstrained
    optimum (each dataset's cheapest cell)."""
    plans = [ref.argmin_plan(c) for c in costs]
    return fleet_use(spans, Rs, [p[0] for p in plans], [p[1] for p in plans],
                     tier)


def move_gains(costs, spans, Rs, tiers, schemes, tier: int,
               quota: float) -> np.ndarray:
    """(T,) per tenant the most one dataset's move lowers its cost, as a
    share of its cost. A move fits when it adds no more to ``tier`` than
    the fleet's leftover quota, ``max(quota - fleet use, 0)``: a move that
    adds nothing always fits, even where the fleet is over the quota. A
    plan no single move improves reads 0 for every tenant."""
    L = costs[0].shape[1]
    uses = [ref.usage(s, R, l, k, L)[tier]
            for s, R, l, k in zip(spans, Rs, tiers, schemes)]
    left = max(quota - sum(uses), 0.0)
    # cost_ref.move_gain lets a move add up to (cap - the tenant's use):
    # a cap of the tenant's use plus the leftover is the fleet's rule
    return np.array([ref.move_gain(c, s, R, l, k, tier, u + left)
                     for c, s, R, l, k, u in zip(costs, spans, Rs, tiers,
                                                 schemes, uses)])


def fleet_solve(costs, spans, Rs, tier: int, quota: float,
                dtype=np.float64):
    """``(lower bound, tiers, schemes)``: ``cost_ref.capped_solve`` on the
    concatenated fleet, the plan split back per tenant. The plan is the
    relaxed plan on the quota's side, the rounding of the bound."""
    cut = np.cumsum([len(s) for s in spans])[:-1]
    bound, tier_all, scheme_all = ref.capped_solve(
        np.concatenate(costs), np.concatenate(spans), np.concatenate(Rs),
        tier, quota, dtype=dtype)
    return bound, np.split(tier_all, cut), np.split(scheme_all, cut)
