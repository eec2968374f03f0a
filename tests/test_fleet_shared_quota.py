"""A fleet under one quota that every tenant shares, against the plain
reference (``bench/configs/provider_fleet_ref.py`` on ``bench/cost_ref.py``):
the quota holds, no single move of a dataset lowers a tenant's cost, the
plan costs at least the fleet's Lagrangian bound and not far above it,
and the bills are exact. And the coupled finish's three passes give the
plans of the candidate-by-candidate order, bit for bit."""

import importlib.util
import json
import pathlib
import sys

import numpy as np
import pytest

from repro.core import optassign
from repro.core.optassign import (BIG, _constraint_rows, _dedupe_candidates,
                                  _fleet_polish, _fleet_repair_shared,
                                  _masked, _repair_vec,
                                  capacitated_assign_batch)

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "bench" / "configs"


def _load(name: str):
    """A module of the benchmark's configurations, by path. They put the
    benchmark's directories on ``sys.path`` to import their neighbours;
    that is undone once they are loaded."""
    spec = importlib.util.spec_from_file_location(
        "bench_configs_" + name, CONFIGS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    path = list(sys.path)
    try:
        spec.loader.exec_module(mod)
    finally:
        sys.path[:] = path
    return mod


class _NoSpans:
    def wrap(self, name, fn):
        return fn


def _cell(tenants: int, datasets, seed: int):
    """The provider fleet's cell with ``tenants`` tenants drawn from
    ``seed`` whose customers hold ``datasets`` datasets each, under a
    quota at 0.7 of the fleet's unconstrained cool-tier use."""
    config = json.loads((CONFIGS / "provider_fleet.json").read_text())
    config["tenants"], config["data_seed"] = tenants, seed
    for c, n in zip(config["customers"], datasets):
        c["datasets"] = n
    mix = {"replan_months": [12, 14], "horizon_months": 6,
           "quota": {"tier": 2, "share": 0.7}}
    return _load("provider_fleet").build(config, mix, 0, _NoSpans())


@pytest.mark.parametrize("tenants,datasets,seed", [
    (8, (60, 45, 20, 30), 1),
    (12, (40, 60, 25, 50), 3_000_000_019),
])
def test_shared_quota_plan_against_the_reference(tenants, datasets, seed):
    pref = _load("provider_fleet_ref")
    cell = _cell(tenants, datasets, seed)
    spans, Rs = cell.spans_and_ratios()
    for month in cell.pool:
        costs = cell.costs(month)
        free = pref.unconstrained_use(costs, spans, Rs, cell.tier)
        assert free > cell.quota           # the quota binds
        ans = cell.serve(month)
        r = cell.readings(month, ans, costs)
        assert r["shared_excess"] <= 1e-12
        assert r["move_gain"] == 0.0
        assert r["plan_gap"] <= 1e-12
        assert r["bill_gap"] <= 1e-12
        bound = pref.fleet_solve(costs, spans, Rs, cell.tier, cell.quota)[0]
        cost = cell.plan_costs(ans, costs).sum()
        assert bound * (1 - 1e-12) <= cost < bound * (1 + 5e-2)


def _fleet(seed: int, T: int, tenant_cap: bool):
    """T random tenants (cost, feasible, stored, per-tenant caps) and a
    shared quota on tier 2 at 0.7 of the unconstrained fleet's use."""
    rng = np.random.default_rng(seed)
    L, K = 4, 3
    costs, feas, stored, caps = [], [], [], []
    for n in rng.integers(20, 61, T):
        spans = rng.lognormal(3.0, 1.5, n)
        R = np.concatenate([np.ones((n, 1)), rng.uniform(1.2, 6.0, (n, 2))],
                           1)
        s = np.broadcast_to((spans[:, None] / R)[:, None, :], (n, L, K))
        costs.append(s * rng.uniform(0.5, 2.0, (L, K))
                     + rng.gamma(1.0, 5.0, (n, 1, 1)) * rng.uniform(
                         0.1, 3.0, (1, L, K)))
        feas.append(rng.random((n, L, K)) > 0.05)
        stored.append(s.copy())
        cap = np.full(L, np.inf)
        if tenant_cap:
            cap[1] = 0.6 * s[:, 1, 0].sum()
        caps.append(cap)
    use = np.zeros(L)
    for c, f, s in zip(costs, feas, stored):
        cell = _masked(c, f).reshape(len(c), -1).argmin(1)
        np.add.at(use, cell // K, s.reshape(len(c), -1)[np.arange(len(c)),
                                                          cell])
    quota = np.full(L, np.inf)
    quota[2] = 0.7 * use[2]
    return costs, feas, stored, caps, quota


def _per_candidate(cells, costs, feas, stored, caps, quota,
                   max_candidates=16):
    """The coupled finish one candidate at a time: every tenant's repair,
    the shared repair, the polish and the score, candidate by candidate."""
    T, K = len(costs), costs[0].shape[2]
    Ns = [len(c) for c in costs]
    m_l = [_masked(np.asarray(c, np.float64), f) for c, f in zip(costs, feas)]
    s_l = [np.asarray(s, np.float64) for s in stored]
    rows = [_constraint_rows(c, None, None) for c in caps]
    A_l = [r[0] for r in rows]
    c_l = [r[1] for r in rows]
    f_l = [np.isfinite(c) for c in c_l]
    L = len(quota)
    A_sh = np.arange(L)[:, None] == np.arange(L)[None, :]
    fin_sh = np.isfinite(quota)
    best, best_score = None, float("inf")
    for cand in _dedupe_candidates((cells[r].ravel()
                                    for r in range(cells.shape[0])),
                                   max_candidates):
        grid = cand.reshape(T, -1)
        tiers = [grid[t, :Ns[t]] // K for t in range(T)]
        schemes = [grid[t, :Ns[t]] % K for t in range(T)]
        uses = [_repair_vec(tiers[t], schemes[t], m_l[t], s_l[t], A_l[t],
                            c_l[t], f_l[t]) for t in range(T)]
        if any(u is None for u in uses):
            continue
        su = _fleet_repair_shared(tiers, schemes, uses, m_l, s_l, A_l, c_l,
                                  f_l, A_sh, quota, fin_sh)
        if su is None:
            continue
        _fleet_polish(tiers, schemes, uses, m_l, s_l, A_l, c_l, f_l, A_sh,
                      quota, fin_sh, su)
        score = sum(float(m_l[t][np.arange(Ns[t]), tiers[t],
                                 schemes[t]].sum()) for t in range(T))
        if score < BIG and score < best_score:
            best, best_score = (tiers, schemes), score
    return best


@pytest.mark.parametrize("seed,T,tenant_cap", [(0, 6, False), (1, 9, True),
                                               (2, 12, True)])
def test_shared_finish_is_the_per_candidate_order(monkeypatch, seed, T,
                                                  tenant_cap):
    costs, feas, stored, caps, quota = _fleet(seed, T, tenant_cap)
    scans = []
    run_scan = optassign._run_fleet_scan

    def keep(*a, **kw):
        scans.append(run_scan(*a, **kw))
        return scans[-1]
    monkeypatch.setattr(optassign, "_run_fleet_scan", keep)
    got = capacitated_assign_batch(costs, feas, stored, caps,
                                   shared_tier_groups=np.arange(4),
                                   shared_capacity_gb=quota)
    cells, = scans
    want = _per_candidate(cells, costs, feas, stored, caps, quota)
    assert got.feasible and want is not None
    for t, a in enumerate(got.assignments):
        assert np.array_equal(a.tier, want[0][t])
        assert np.array_equal(a.scheme, want[1][t])
        n = np.arange(len(costs[t]))
        assert a.cost == float(_masked(costs[t], feas[t])[
            n, want[0][t], want[1][t]].sum())
