"""The 1-swap descent (``optassign._local_search_vec``) against a frozen
full-rescan descent: the same moves, in the same order, so the same
tiers, schemes, per-tier usage and move count after every call, under
every kind of constraint row the solvers hand it."""

import numpy as np
import pytest

from repro.core import optassign
from repro.core.optassign import (BIG, _chosen_usage, _constraint_rows,
                                  _local_search_vec, _masked,
                                  capacitated_assign,
                                  capacitated_assign_batch)


def _full_rescan(tier, scheme, use, masked, stored, A, cap_all, finite_all,
                 max_moves=None):
    """The descent as it was before it kept its delta matrix: every step
    rebuilds the whole (N, L, K) problem. Verbatim, but for the move count
    it returns."""
    N, L, K = masked.shape
    n_idx = np.arange(N)
    Af = A & finite_all[:, None]                    # (C, L)
    A_f = A.astype(np.float64)
    any_finite = bool(finite_all.any())
    moves = 0
    for _ in range(8 * N + 64 if max_moves is None else max_moves):
        cur = masked[n_idx, tier, scheme]
        stored_cur = stored[n_idx, tier, scheme]
        if any_finite:
            # einsum, not @: the lockstep fleet descent replicates this
            # exact ascending-l accumulation for bitwise-equal trajectories
            use_c = np.einsum("cl,l->c", A_f, use)
            # slack[n, c]: room left in constraint c once n vacates its cell
            slack = ((cap_all - use_c)[None, :]
                     + A[:, tier].T * stored_cur[:, None])           # (N, C)
            # per-destination room = tightest finite constraint containing it
            room = np.where(Af[None, :, :], slack[:, :, None],
                            np.inf).min(1)                           # (N, L)
            ok = (masked < BIG) & (stored <= room[:, :, None] + 1e-9)
        else:
            ok = masked < BIG
        delta = np.where(ok, masked - cur[:, None, None], np.inf)
        j = int(delta.argmin())
        n, rem = divmod(j, L * K)
        l2, k2 = divmod(rem, K)
        if not delta[n, l2, k2] < -1e-12:
            break
        use[tier[n]] -= stored[n, tier[n], scheme[n]]
        use[l2] += stored[n, l2, k2]
        tier[n], scheme[n] = l2, k2
        moves += 1
    return moves


def _both(tier, scheme, use, masked, stored, A, cap_all, finite_all,
          max_moves=None):
    """Run both descents on copies of one start; assert they agree bit for
    bit and return the move count."""
    got = [tier.copy(), scheme.copy(), use.copy()]
    want = [tier.copy(), scheme.copy(), use.copy()]
    n_got = _local_search_vec(*got, masked, stored, A, cap_all, finite_all,
                              max_moves=max_moves)
    n_want = _full_rescan(*want, masked, stored, A, cap_all, finite_all,
                          max_moves=max_moves)
    assert n_got == n_want
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and np.array_equal(g, w)
    return n_got


def _instance(rng, N, L=4, K=3, ints=False):
    """A random (masked, stored) pair and a start on feasible cells.
    ``ints`` draws small whole numbers, so that gains tie exactly and
    stored bytes meet the room exactly."""
    if ints:
        cost = rng.integers(0, 6, (N, L, K)).astype(np.float64)
        stored = rng.integers(1, 4, (N, L, K)).astype(np.float64)
    else:
        cost = rng.gamma(2.0, 3.0, (N, L, K))
        stored = np.repeat(rng.lognormal(1.0, 1.0, (N, 1, K)), L, 1)
    feas = rng.random((N, L, K)) > 0.1
    feas[:, 0, 0] = True
    masked = _masked(cost, feas)
    tier = np.zeros(N, np.int64)
    scheme = np.zeros(N, np.int64)
    for n in range(N):
        cells = np.flatnonzero(feas[n].ravel())
        tier[n], scheme[n] = divmod(int(rng.choice(cells)), K)
    return masked, stored, tier, scheme


def _caps(use, tiers, slack):
    """Per-tier caps: the start's use times ``slack`` on ``tiers``, the
    rest unbounded."""
    cap = np.full(use.shape[0], np.inf)
    cap[list(tiers)] = use[list(tiers)] * slack
    return cap


@pytest.mark.parametrize("N", [1, 7, 40, 520])
@pytest.mark.parametrize("ints", [False, True])
def test_no_finite_row(N, ints):
    rng = np.random.default_rng(N + 1000 * ints)
    masked, stored, tier, scheme = _instance(rng, N, ints=ints)
    use = _chosen_usage(stored, tier, scheme)
    A, cap_all = _constraint_rows(np.full(4, np.inf), None, None)
    _both(tier, scheme, use, masked, stored, A, cap_all,
          np.isfinite(cap_all))


@pytest.mark.parametrize("N,tiers,ints", [
    (1, (2,), False), (9, (1,), True), (33, (2,), False),
    (60, (0, 2), True), (200, (1, 2, 3), False), (520, (2,), False),
    (80, (0, 1, 2, 3), True)])
def test_per_tier_caps(N, tiers, ints):
    rng = np.random.default_rng(7 * N + len(tiers))
    masked, stored, tier, scheme = _instance(rng, N, ints=ints)
    use = _chosen_usage(stored, tier, scheme)
    cap = _caps(use, tiers, 1.0 if ints else 1.05)
    A, cap_all = _constraint_rows(cap, None, None)
    moves = _both(tier, scheme, use, masked, stored, A, cap_all,
                  np.isfinite(cap_all))
    assert N == 1 or moves > 0


@pytest.mark.parametrize("N,tier_caps,ints", [(12, False, True),
                                              (50, True, False),
                                              (300, True, True)])
def test_group_rows(N, tier_caps, ints):
    """Two providers' blocks of three tiers, each with a group capacity."""
    rng = np.random.default_rng(N)
    L = 6
    masked, stored, tier, scheme = _instance(rng, N, L=L, ints=ints)
    use = _chosen_usage(stored, tier, scheme)
    groups = np.array([0, 0, 0, 1, 1, 1])
    gcap = np.array([use[:3].sum(), use[3:].sum()]) * 1.02
    cap = _caps(use, (1, 4), 1.1) if tier_caps else np.full(L, np.inf)
    A, cap_all = _constraint_rows(cap, groups, gcap)
    _both(tier, scheme, use, masked, stored, A, cap_all,
          np.isfinite(cap_all))


@pytest.mark.parametrize("N,fraction", [(30, 0.25), (300, 0.5),
                                        (520, 0.9)])
def test_max_moves_cuts_the_trajectory(N, fraction):
    """A budget smaller than the trajectory, as the lockstep descent hands
    its tail rows over: both stop after the same moves."""
    rng = np.random.default_rng(N + 5)
    masked, stored, tier, scheme = _instance(rng, N)
    use = _chosen_usage(stored, tier, scheme)
    A, cap_all = _constraint_rows(_caps(use, (2,), 1.0), None, None)
    fin = np.isfinite(cap_all)
    whole = _both(tier, scheme, use, masked, stored, A, cap_all, fin)
    budget = int(whole * fraction)
    assert 0 < budget < whole
    assert _both(tier, scheme, use, masked, stored, A, cap_all, fin,
                 max_moves=budget) == budget
    assert _both(tier, scheme, use, masked, stored, A, cap_all, fin,
                 max_moves=0) == 0


@pytest.mark.parametrize("free_first", [True, False])
def test_planted_tie_between_a_free_and_a_capped_cell(free_first):
    """Two cells of one row gain the same: the one first in the flattened
    grid moves, whether it lies in the capped tier or the free one."""
    capped, free = (2, 1) if free_first else (0, 3)
    masked = np.full((2, 4, 2), 10.0)
    masked[0, capped, 1] = masked[0, free, 0] = 4.0
    masked[1] = 9.0
    stored = np.ones((2, 4, 2))
    tier, scheme = np.array([capped, 1]), np.array([0, 0])
    if capped == 0:
        tier[1] = 3                                 # row 1 leaves tier 0 alone
    use = _chosen_usage(stored, tier, scheme)
    cap = np.full(4, np.inf)
    cap[capped] = use[capped]                       # exactly full
    A, cap_all = _constraint_rows(cap, None, None)
    got = [tier.copy(), scheme.copy(), use.copy()]
    _local_search_vec(*got, masked, stored, A, cap_all, np.isfinite(cap_all),
                      max_moves=1)
    want = min(capped * 2 + 1, free * 2)            # flat index in the row
    assert divmod(want, 2) == (got[0][0], got[1][0])
    _both(tier, scheme, use, masked, stored, A, cap_all, np.isfinite(cap_all))


def test_planted_tie_between_two_rows():
    """Two rows' best moves gain the same: the lower row moves first."""
    masked = np.full((3, 3, 2), 5.0)
    masked[:, 0, 0] = 8.0
    masked[1, 2, 1] = masked[2, 1, 0] = 2.0
    stored = np.ones((3, 3, 2))
    tier = np.zeros(3, np.int64)
    scheme = np.zeros(3, np.int64)
    use = _chosen_usage(stored, tier, scheme)
    A, cap_all = _constraint_rows(np.array([np.inf, 1.0, np.inf]), None,
                                  None)
    fin = np.isfinite(cap_all)
    got = [tier.copy(), scheme.copy(), use.copy()]
    _local_search_vec(*got, masked, stored, A, cap_all, fin, max_moves=1)
    assert (got[0][1], got[1][1]) == (2, 1) and (got[0][2], got[1][2]) == (0, 0)
    assert _both(tier, scheme, use, masked, stored, A, cap_all, fin) == 3


def test_empty_problem_makes_no_move():
    """N = 0: nothing to move. The full rescan cannot take an empty grid
    (its argmin over no cells raises); the descent returns at once."""
    masked = np.zeros((0, 4, 3))
    tier = np.zeros(0, np.int64)
    scheme = np.zeros(0, np.int64)
    use = np.zeros(4)
    A, cap_all = _constraint_rows(np.array([np.inf, np.inf, 1.0, np.inf]),
                                  None, None)
    fin = np.isfinite(cap_all)
    assert _local_search_vec(tier, scheme, use, masked, masked, A, cap_all,
                             fin) == 0
    assert np.array_equal(use, np.zeros(4))
    with pytest.raises(ValueError):
        _full_rescan(tier, scheme, use, masked, masked, A, cap_all, fin)


def _checked(monkeypatch):
    """Route every solver call of the descent through :func:`_both`, on
    the arguments the solver built, and count the calls."""
    calls = []
    run = optassign._local_search_vec

    def check(tier, scheme, use, masked, stored, A, cap_all, finite_all,
              max_moves=None):
        moves = _both(tier, scheme, use, masked, stored, A, cap_all,
                      finite_all, max_moves)
        calls.append(moves)
        return run(tier, scheme, use, masked, stored, A, cap_all,
                   finite_all, max_moves)

    monkeypatch.setattr(optassign, "_local_search_vec", check)
    return calls


def _tenants(rng, T, L=4, K=3):
    costs, feas, stored = [], [], []
    for n in rng.integers(8, 41, T):
        spans = rng.lognormal(3.0, 1.5, n)
        R = np.concatenate([np.ones((n, 1)), rng.uniform(1.2, 6.0, (n, 2))],
                           1)
        s = np.broadcast_to((spans[:, None] / R)[:, None, :], (n, L, K))
        costs.append(s * rng.uniform(0.5, 2.0, (L, K))
                     + rng.gamma(1.0, 5.0, (n, 1, 1))
                     * rng.uniform(0.1, 3.0, (1, L, K)))
        feas.append(rng.random((n, L, K)) > 0.05)
        stored.append(s.copy())
    return costs, feas, stored


def _free_use(costs, feas, stored, K=3):
    use = np.zeros(costs[0].shape[1])
    for c, f, s in zip(costs, feas, stored):
        cell = _masked(c, f).reshape(len(c), -1).argmin(1)
        np.add.at(use, cell // K,
                  s.reshape(len(c), -1)[np.arange(len(c)), cell])
    return use


@pytest.mark.parametrize("seed", [0, 1])
def test_the_residual_shared_rows_of_the_fleet_polish(monkeypatch, seed):
    """Every descent of a fleet under one shared quota, on the augmented
    rows (the tenant's own plus the shared rows at their residual caps)
    that ``_fleet_polish`` builds."""
    rng = np.random.default_rng(seed)
    costs, feas, stored = _tenants(rng, 6)
    quota = np.full(4, np.inf)
    quota[2] = 0.7 * _free_use(costs, feas, stored)[2]
    calls = _checked(monkeypatch)
    fa = capacitated_assign_batch(costs, feas, stored, np.full(4, np.inf),
                                  shared_tier_groups=np.arange(4),
                                  shared_capacity_gb=quota)
    assert fa.feasible and len(calls) >= 6 and sum(calls) > 0


@pytest.mark.parametrize("seed", [2, 3])
def test_the_capped_fleet_and_the_single_tenant_solve(monkeypatch, seed):
    """The lockstep descent's tail hand-off (with its move budget) and the
    single-tenant solve's candidates."""
    rng = np.random.default_rng(seed)
    costs, feas, stored = _tenants(rng, 12)
    # tier 1 capped at half of each tenant's unconstrained use of it
    caps = [np.where(np.arange(4) == 1, 0.5 * _free_use([c], [f], [s])[1],
                     np.inf) for c, f, s in zip(costs, feas, stored)]
    calls = _checked(monkeypatch)
    capacitated_assign_batch(costs, feas, stored, caps)
    tail = len(calls)
    for t in range(3):
        capacitated_assign(costs[t], feas[t], stored[t], caps[t])
    assert tail > 0 and len(calls) > tail
