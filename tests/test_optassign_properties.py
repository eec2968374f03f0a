"""Property-based invariants for the capacitated OPTASSIGN solvers.

Runs under ``hypothesis``. Strategies draw a SEED, not arrays: every
example uses the same (N, L, K) shapes so the jitted Lagrangian scan
compiles once, and the seeded ``default_rng`` varies the values.

Invariants:

* a feasible solution never violates per-tier, per-group, or fleet-shared
  capacities;
* batch padding cells are inert — the batched solve is bit-identical to
  independent per-tenant solves;
* the returned assignment is 1-swap optimal: no single partition can move
  to another feasible, capacity-respecting cell and lower the objective;
* ``sla_lambda=0`` reduces exactly to the pre-SLA solver, and
  ``sla_lambda=lam`` is identical to folding ``cost + lam * penalty``
  by hand.
"""

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.costs import (Weights, azure_table, cost_tensor,
                              sla_penalty_tensor)
from repro.core.optassign import (capacitated_assign,
                                  capacitated_assign_batch)

N, K = 8, 2
TABLE = azure_table()
L = TABLE.num_tiers
EPS = 1e-9


def _instance(seed: int, tight: float = 0.6):
    """One random capacitated instance with caps that usually bind."""
    rng = np.random.default_rng(seed)
    spans = rng.uniform(0.5, 30.0, N)
    rho = rng.gamma(1.0, 25.0, N)
    cur = rng.integers(-1, L, N)
    R = np.concatenate([np.ones((N, 1)), rng.uniform(1.2, 6.0, (N, K - 1))],
                       1)
    D = np.concatenate([np.zeros((N, 1)),
                        rng.uniform(0.01, 2.0, (N, K - 1))], 1)
    cost = cost_tensor(spans, rho, cur, R, D, TABLE, Weights(), months=4.0)
    feas = rng.random((N, L, K)) > 0.15
    feas[:, rng.integers(0, L), :] = True      # at least one open tier
    stored = np.repeat((spans[:, None] / R)[:, None, :], L, 1)
    tot = spans.sum()
    cap = np.array([tight * tot * rng.uniform(0.2, 0.6),
                    tight * tot * rng.uniform(0.3, 0.8), tot, np.inf])
    return cost, feas, stored, cap, D, rho


def _usage(stored, tier, scheme):
    use = np.zeros(L)
    np.add.at(use, tier, stored[np.arange(N), tier, scheme])
    return use


@settings(max_examples=100, deadline=None)
@given(st.integers(0, 2**32 - 1))
def test_capacities_never_violated(seed):
    cost, feas, stored, cap, _, _ = _instance(seed)
    groups = np.array([0, 0, 1, 1])
    gcap = np.array([cap[0] + cap[1], np.inf])
    a = capacitated_assign(cost, feas, stored, cap, tier_groups=groups,
                           group_capacity_gb=gcap)
    if not a.feasible:
        return
    use = _usage(stored, a.tier, a.scheme)
    assert (use <= cap + EPS).all(), (use, cap)
    for g in range(gcap.shape[0]):
        assert use[groups == g].sum() <= gcap[g] + EPS
    # every chosen cell was actually feasible
    assert feas[np.arange(N), a.tier, a.scheme].all()


@settings(max_examples=100, deadline=None)
@given(st.integers(0, 2**32 - 1))
def test_batch_padding_inert(seed):
    """Ragged tenants through the padded batch == independent solves."""
    insts = [_instance(seed * 3 + t) for t in range(3)]
    # ragged: drop rows from two tenants so padding cells exist
    keep = (N, N - 3, N - 5)
    costs = [i[0][:k] for i, k in zip(insts, keep)]
    feats = [i[1][:k] for i, k in zip(insts, keep)]
    stores = [i[2][:k] for i, k in zip(insts, keep)]
    caps = [i[3] for i in insts]
    singles = [capacitated_assign(c, f, s, cap)
               for c, f, s, cap in zip(costs, feats, stores, caps)]
    batch = capacitated_assign_batch(costs, feats, stores, caps)
    for one, got in zip(singles, batch.assignments):
        assert np.array_equal(one.tier, got.tier)
        assert np.array_equal(one.scheme, got.scheme)
        assert one.cost == got.cost and one.feasible == got.feasible
    assert batch.cost == float(sum(s.cost for s in singles))


@settings(max_examples=100, deadline=None)
@given(st.integers(0, 2**32 - 1))
def test_one_swap_optimality(seed):
    """No single-partition move to a feasible, capacity-respecting cell
    may lower the objective of the returned assignment."""
    cost, feas, stored, cap, _, _ = _instance(seed)
    a = capacitated_assign(cost, feas, stored, cap)
    if not a.feasible:
        return
    use = _usage(stored, a.tier, a.scheme)
    total = cost[np.arange(N), a.tier, a.scheme].sum()
    for n in range(N):
        l0, k0 = int(a.tier[n]), int(a.scheme[n])
        for l in range(L):
            for k in range(K):
                if (l, k) == (l0, k0) or not feas[n, l, k]:
                    continue
                u = use.copy()
                u[l0] -= stored[n, l0, k0]
                u[l] += stored[n, l, k]
                if not (u <= cap + EPS).all():
                    continue
                swapped = total - cost[n, l0, k0] + cost[n, l, k]
                assert swapped >= total - 1e-6, (n, l, k)


@settings(max_examples=100, deadline=None)
@given(st.integers(0, 2**32 - 1))
def test_sla_lambda_zero_reduces_to_base_solver(seed):
    cost, feas, stored, cap, D, rho = _instance(seed)
    rng = np.random.default_rng(seed + 1)
    sla = rng.choice([10.0, 75.0, np.inf], N)
    pen = sla_penalty_tensor(rho, sla, D, TABLE)
    base = capacitated_assign(cost, feas, stored, cap)
    zero = capacitated_assign(cost, feas, stored, cap, sla_penalty=pen,
                              sla_lambda=0.0)
    assert np.array_equal(base.tier, zero.tier)
    assert np.array_equal(base.scheme, zero.scheme)
    assert base.cost == zero.cost and base.feasible == zero.feasible

    lam = float(rng.uniform(0.01, 3.0))
    with_sla = capacitated_assign(cost, feas, stored, cap, sla_penalty=pen,
                                  sla_lambda=lam)
    by_hand = capacitated_assign(cost + lam * pen, feas, stored, cap)
    assert np.array_equal(with_sla.tier, by_hand.tier)
    assert np.array_equal(with_sla.scheme, by_hand.scheme)
    assert with_sla.cost == by_hand.cost
