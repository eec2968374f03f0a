"""OPTASSIGN solver correctness: greedy/matching/capacitated vs brute force."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.costs import (Weights, azure_table, cost_tensor,
                              latency_feasible, tpch_capacity_table)
from repro.core.optassign import (brute_force, capacitated_assign,
                                  capacitated_assign_ref, greedy_assign,
                                  lock_schemes, matching_assign)


def _random_instance(rng, N=6, K=3):
    table = azure_table()
    spans = rng.uniform(0.5, 50.0, N)
    rho = rng.gamma(1.0, 20.0, N)
    cur = rng.integers(-1, table.num_tiers, N)
    R = np.concatenate([np.ones((N, 1)), rng.uniform(1.2, 6.0, (N, K - 1))], 1)
    D = np.concatenate([np.zeros((N, 1)), rng.uniform(0.01, 3.0, (N, K - 1))], 1)
    T = rng.choice([0.1, 1.0, 5.0, np.inf], N)
    cost = cost_tensor(spans, rho, cur, R, D, table, Weights(), months=6)
    feas = latency_feasible(D, T, table)
    return cost, feas, spans, R, table


def test_greedy_matches_bruteforce_unbounded():
    rng = np.random.default_rng(0)
    for trial in range(10):
        cost, feas, *_ = _random_instance(rng)
        if not feas.any(axis=(1, 2)).all():
            continue
        g = greedy_assign(cost, feas)
        b = brute_force(cost, feas)
        assert g.feasible and b.feasible
        assert g.cost == pytest.approx(b.cost, rel=1e-6)


def test_greedy_respects_latency_mask():
    rng = np.random.default_rng(1)
    cost, feas, *_ = _random_instance(rng)
    g = greedy_assign(cost, feas)
    for n in range(cost.shape[0]):
        assert feas[n, g.tier[n], g.scheme[n]]


def test_greedy_infeasible_reported():
    cost = np.ones((2, 4, 2))
    feas = np.zeros((2, 4, 2), bool)
    g = greedy_assign(cost, feas)
    assert not g.feasible and g.cost == float("inf")


def test_scheme_locking():
    rng = np.random.default_rng(2)
    cost, feas, *_ = _random_instance(rng, N=5, K=3)
    locked = np.array([1, -1, 2, -1, 0])
    feas2 = lock_schemes(feas, locked)
    g = greedy_assign(cost, feas2)
    if g.feasible:
        for n, k in enumerate(locked):
            if k >= 0:
                assert g.scheme[n] == k


def test_matching_vs_bruteforce_capacitated_equal_sizes():
    """Thm 2 case: unit partitions, capacities in units, no compression."""
    rng = np.random.default_rng(3)
    for _ in range(8):
        N, L = 6, 3
        cost_nl = rng.uniform(1.0, 100.0, (N, L))
        feas_nl = rng.random((N, L)) > 0.15
        cap = np.array([2, 2, 6])
        m = matching_assign(cost_nl, feas_nl, cap)
        # brute force over tier choices with unit capacities
        cost3 = cost_nl[:, :, None]
        feas3 = feas_nl[:, :, None]
        stored = np.ones((N, L, 1))
        b = brute_force(cost3, feas3, stored, cap.astype(float))
        assert m.feasible == b.feasible
        if m.feasible:
            assert m.cost == pytest.approx(b.cost, rel=1e-9)
            used = np.bincount(m.tier, minlength=L)
            assert (used <= cap).all()


def test_capacitated_ref_close_to_bruteforce():
    rng = np.random.default_rng(4)
    gaps = []
    for _ in range(6):
        cost, feas, spans, R, table = _random_instance(rng, N=5, K=2)
        stored = np.repeat((spans[:, None] / R)[:, None, :], table.num_tiers, 1)
        cap = np.array([spans.sum() / 3, spans.sum() / 2, spans.sum(), np.inf])
        c = capacitated_assign_ref(cost, feas, stored, cap)
        b = brute_force(cost, feas, stored, cap)
        if not b.feasible:
            continue
        assert c.feasible
        gaps.append(c.cost / b.cost - 1.0)
    assert gaps and max(gaps) < 0.02, f"capacitated gap too large: {gaps}"


def test_capacitated_vectorized_matches_bruteforce():
    """The jitted-Lagrangian + repair + 1-swap solver finds the optimum on
    tiny instances (f64 rescoring makes this exact, not approximate)."""
    rng = np.random.default_rng(4)
    checked = 0
    for _ in range(12):
        cost, feas, spans, R, table = _random_instance(rng, N=5, K=2)
        stored = np.repeat((spans[:, None] / R)[:, None, :], table.num_tiers, 1)
        cap = np.array([spans.sum() / 3, spans.sum() / 2, spans.sum(), np.inf])
        b = brute_force(cost, feas, stored, cap)
        if not b.feasible:
            continue
        v = capacitated_assign(cost, feas, stored, cap)
        assert v.feasible
        assert v.cost == pytest.approx(b.cost, rel=1e-9)
        used = np.zeros(table.num_tiers)
        np.add.at(used, v.tier, stored[np.arange(len(v.tier)), v.tier, v.scheme])
        assert (used <= cap + 1e-9).all()
        checked += 1
    assert checked >= 6


def test_capacitated_vectorized_not_worse_than_ref():
    rng = np.random.default_rng(5)
    for _ in range(6):
        cost, feas, spans, R, table = _random_instance(rng, N=8, K=3)
        stored = np.repeat((spans[:, None] / R)[:, None, :], table.num_tiers, 1)
        cap = np.array([spans.sum() / 4, spans.sum() / 3, spans.sum(), np.inf])
        v = capacitated_assign(cost, feas, stored, cap)
        r = capacitated_assign_ref(cost, feas, stored, cap)
        if r.feasible:
            assert v.feasible
            assert v.cost <= r.cost * (1 + 1e-9)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_capacitated_vectorized_optimality_property(seed):
    """Hypothesis: vectorized == brute force on tiny capacitated instances."""
    rng = np.random.default_rng(seed)
    cost, feas, spans, R, table = _random_instance(rng, N=4, K=2)
    stored = np.repeat((spans[:, None] / R)[:, None, :], table.num_tiers, 1)
    cap = np.array([spans.sum() / 3, spans.sum() / 2, spans.sum(), np.inf])
    b = brute_force(cost, feas, stored, cap)
    if not b.feasible:
        return
    v = capacitated_assign(cost, feas, stored, cap)
    assert v.feasible
    assert v.cost == pytest.approx(b.cost, rel=1e-9)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10_000))
def test_greedy_optimality_property(seed):
    """Hypothesis: greedy == brute force whenever capacities are unbounded."""
    rng = np.random.default_rng(seed)
    cost, feas, *_ = _random_instance(rng, N=4, K=2)
    g = greedy_assign(cost, feas)
    b = brute_force(cost, feas)
    assert g.feasible == b.feasible
    if g.feasible:
        assert g.cost == pytest.approx(b.cost, rel=1e-6)


def test_tier_change_cost_matrix():
    t = azure_table()
    delta = t.tier_change_cents_gb()
    assert delta.shape == (5, 4)
    assert np.allclose(np.diag(delta[:4]), 0.0)       # stay-put is free
    assert (delta[-1] == t.write_cents_gb).all()      # ingestion row
    # moving out of archive is expensive (rehydration read)
    assert delta[3, 1] > delta[1, 3]
