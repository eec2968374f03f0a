"""Fleet solver: batched vs per-tenant bit-parity, shared fleet-wide caps,
ragged padding invariance, fleet daemon parity, N=0 corner regressions."""

import dataclasses
import hashlib

import numpy as np
import pytest

from repro.core.costs import (ProviderCostTable, Weights, azure_table,
                              cost_tensor, latency_feasible,
                              multi_cloud_table)
from repro.core.daemon import MigrationBudget, ReoptimizationDaemon
from repro.core.engine import PlacementEngine, PlacementProblem, ScopeConfig
from repro.core.fleet import FleetEngine
from repro.core.optassign import (capacitated_assign, capacitated_assign_batch,
                                  greedy_assign, greedy_assign_batch)


# ----------------------------------------------------------------- fixtures
def _tenant_instance(rng, N, K=3):
    """One tenant's (cost, feas, stored, cap) with tier caps that bind."""
    table = azure_table()
    spans = rng.uniform(0.5, 50.0, max(N, 1))[:N]
    rho = rng.gamma(1.0, 20.0, max(N, 1))[:N]
    cur = rng.integers(-1, table.num_tiers, max(N, 1))[:N]
    R = np.concatenate([np.ones((max(N, 1), 1)),
                        rng.uniform(1.2, 6.0, (max(N, 1), K - 1))], 1)[:N]
    D = np.concatenate([np.zeros((max(N, 1), 1)),
                        rng.uniform(0.01, 3.0, (max(N, 1), K - 1))], 1)[:N]
    T = rng.choice([0.1, 1.0, 5.0, np.inf], max(N, 1))[:N]
    cost = cost_tensor(spans, rho, cur, R, D, table, Weights(), months=6)
    feas = latency_feasible(D, T, table)
    stored = np.repeat((spans[:, None] / R)[:, None, :], table.num_tiers, 1)
    tot = spans.sum() if N else 1.0
    cap = np.array([tot / 3, tot / 2, tot, np.inf])
    return cost, feas, stored, cap


def _ragged_fleet(seed=0, Ns=(5, 9, 3, 9, 1, 8, 6)):
    rng = np.random.default_rng(seed)
    return [_tenant_instance(rng, n) for n in Ns]


def _make_problem(rng, N, table, cfg, K=3):
    spans = rng.uniform(0.5, 50.0, N)
    rho = rng.gamma(1.0, 20.0, N)
    R = np.concatenate([np.ones((N, 1)), rng.uniform(1.2, 6.0, (N, K - 1))],
                       1)
    D = np.concatenate([np.zeros((N, 1)),
                        rng.uniform(0.01, 3.0, (N, K - 1))], 1)
    return PlacementProblem(spans_gb=spans, rho=rho,
                            current_tier=np.full(N, -1), R=R, D=D,
                            schemes=list(cfg.schemes)[:K], table=table,
                            cfg=cfg)


def _identical(a, b):
    return (np.array_equal(a.tier, b.tier)
            and np.array_equal(a.scheme, b.scheme)
            and a.cost == b.cost and a.feasible == b.feasible)


# -------------------------------------------------------------- core parity
def test_batch_bit_identical_to_per_tenant_solves():
    """THE fleet parity pin: no shared rows => every tenant's assignment,
    cost, and feasibility bit-identical to its own capacitated_assign."""
    fleet = _ragged_fleet()
    singles = [capacitated_assign(c, f, s, cap) for c, f, s, cap in fleet]
    batch = capacitated_assign_batch([x[0] for x in fleet],
                                     [x[1] for x in fleet],
                                     [x[2] for x in fleet],
                                     [x[3] for x in fleet])
    assert batch.feasible
    for single, got in zip(singles, batch.assignments):
        assert _identical(single, got)
    assert batch.cost == float(sum(s.cost for s in singles))


def test_greedy_batch_bit_identical():
    fleet = _ragged_fleet(seed=7)
    singles = [greedy_assign(c, f) for c, f, _, _ in fleet]
    batch = greedy_assign_batch([x[0] for x in fleet], [x[1] for x in fleet])
    for single, got in zip(singles, batch):
        assert _identical(single, got)


def test_ragged_padding_invariance_empty_tenant_changes_nothing():
    """Adding an N=0 tenant anywhere in the batch is a no-op for everyone
    else — padded rows carry zero cost and zero usage."""
    fleet = _ragged_fleet(seed=1)
    base = capacitated_assign_batch([x[0] for x in fleet],
                                    [x[1] for x in fleet],
                                    [x[2] for x in fleet],
                                    [x[3] for x in fleet])
    rng = np.random.default_rng(9)
    empty = _tenant_instance(rng, 0)
    for pos in (0, len(fleet) // 2, len(fleet)):
        fleet2 = fleet[:pos] + [empty] + fleet[pos:]
        got = capacitated_assign_batch([x[0] for x in fleet2],
                                       [x[1] for x in fleet2],
                                       [x[2] for x in fleet2],
                                       [x[3] for x in fleet2])
        others = got.assignments[:pos] + got.assignments[pos + 1:]
        for a, b in zip(base.assignments, others):
            assert _identical(a, b)
        inserted = got.assignments[pos]
        assert inserted.feasible and inserted.cost == 0.0
        assert inserted.tier.shape == (0,)


def test_shared_inf_caps_preserve_bit_parity():
    """Shared rows with infinite caps never couple anything: still
    bit-identical to per-tenant solves (the zero-multiplier pin)."""
    fleet = _ragged_fleet(seed=2)
    L = fleet[0][0].shape[1]
    singles = [capacitated_assign(c, f, s, cap) for c, f, s, cap in fleet]
    batch = capacitated_assign_batch(
        [x[0] for x in fleet], [x[1] for x in fleet],
        [x[2] for x in fleet], [x[3] for x in fleet],
        shared_tier_groups=np.zeros(L, int),
        shared_capacity_gb=np.array([np.inf]))
    for single, got in zip(singles, batch.assignments):
        assert _identical(single, got)
    assert batch.shared_use_gb is not None


def test_shared_cap_binds_fleet_wide_where_per_tenant_solves_violate():
    """A global cap on one tier that every per-tenant solve (which cannot
    see the other tenants) collectively violates: the fleet solve respects
    it, stays feasible, and pays at least the uncoupled cost."""
    fleet = _ragged_fleet(seed=3)
    L = fleet[0][0].shape[1]
    uncoupled = capacitated_assign_batch([x[0] for x in fleet],
                                         [x[1] for x in fleet],
                                         [x[2] for x in fleet],
                                         [x[3] for x in fleet])
    # fleet-wide usage per tier under the uncoupled optimum
    use = np.zeros(L)
    for (c, f, s, cap), a in zip(fleet, uncoupled.assignments):
        np.add.at(use, a.tier.astype(int),
                  s[np.arange(len(a.tier)), a.tier.astype(int),
                    a.scheme.astype(int)])
    tgt = int(use.argmax())
    scap = np.full(L, np.inf)
    scap[tgt] = 0.5 * use[tgt]          # binds: fleet must shed half
    coupled = capacitated_assign_batch(
        [x[0] for x in fleet], [x[1] for x in fleet],
        [x[2] for x in fleet], [x[3] for x in fleet],
        shared_tier_groups=np.arange(L),
        shared_capacity_gb=scap)
    assert coupled.feasible
    assert coupled.shared_use_gb[tgt] <= scap[tgt] + 1e-9
    assert coupled.cost >= uncoupled.cost - 1e-9
    # per-tenant solves cannot coordinate: summed usage violates the cap
    assert use[tgt] > scap[tgt]


def test_shared_cap_infeasible_when_below_minimum_footprint():
    fleet = _ragged_fleet(seed=4, Ns=(4, 6))
    L = fleet[0][0].shape[1]
    batch = capacitated_assign_batch(
        [x[0] for x in fleet], [x[1] for x in fleet],
        [x[2] for x in fleet], [x[3] for x in fleet],
        shared_tier_groups=np.zeros(L, int),
        shared_capacity_gb=np.array([1e-6]))   # below any possible footprint
    assert not batch.feasible and batch.cost == float("inf")


# ----------------------------------------------------------- corner cases
def test_zero_partition_tenant_and_empty_fleet():
    """step0 / argmin padding hazards: N=0 tenants, empty fleets, and
    all-infinite capacities must not divide by empty means or reshape
    zero-size arrays."""
    rng = np.random.default_rng(5)
    empty = _tenant_instance(rng, 0)
    # single-tenant N=0 (direct and batched)
    single = capacitated_assign(*empty)
    assert single.feasible and single.cost == 0.0
    assert greedy_assign(empty[0], empty[1]).feasible
    got = capacitated_assign_batch([empty[0]], [empty[1]], [empty[2]],
                                   [empty[3]])
    assert got.feasible and got.cost == 0.0
    # fleet of zero tenants
    out = capacitated_assign_batch([], [], [], np.ones(4))
    assert out.feasible and out.cost == 0.0 and out.assignments == []


def test_all_infeasible_tenant_reported_not_crashed():
    L, K = 4, 2
    cost = np.ones((3, L, K))
    feas = np.zeros((3, L, K), bool)
    stored = np.ones((3, L, K))
    cap = np.full(L, np.inf)
    got = capacitated_assign_batch([cost], [feas], [stored], [cap])
    assert not got.feasible and got.cost == float("inf")
    # all-infinite caps + all-infeasible cells is the step0 0/0 corner
    single = capacitated_assign(cost, feas, stored, cap)
    assert not single.feasible


# ------------------------------------------------------------ FleetEngine
def test_fleet_engine_solve_matches_placement_engine():
    table = azure_table()
    cfg = ScopeConfig(schemes=("none", "lz4", "zstd3"))
    rng = np.random.default_rng(6)
    probs = [_make_problem(rng, n, table, cfg) for n in (6, 9, 4, 7)]
    pe = PlacementEngine(table, cfg)
    fe = FleetEngine(table, cfg)
    fp = fe.solve(probs)
    for p, plan in zip(probs, fp.plans):
        single = pe.solve(p)
        assert _identical(single.assignment, plan.assignment)
        assert single.report.total_cents == plan.report.total_cents
    assert fp.total_cents == pytest.approx(
        sum(pe.solve(p).report.total_cents for p in probs))


def test_fleet_engine_capacitated_solve_and_reoptimize_parity():
    table = azure_table()
    caps = np.array([25.0, 50.0, 300.0, np.inf])
    cfg = ScopeConfig(schemes=("none", "lz4", "zstd3"), capacity_gb=caps)
    rng = np.random.default_rng(7)
    probs = [_make_problem(rng, n, table, cfg) for n in (6, 9, 4)]
    pe = PlacementEngine(table, cfg)
    fe = FleetEngine(table, cfg)
    fp = fe.solve(probs)
    singles = [pe.solve(p) for p in probs]
    for single, plan in zip(singles, fp.plans):
        assert _identical(single.assignment, plan.assignment)
    new_rhos = [p.rho * rng.uniform(0.2, 4.0, p.n) for p in probs]
    migs, fleet = fe.reoptimize(fp.plans, new_rhos, months_held=2.0)
    for single, mig, rho in zip(singles, migs, new_rhos):
        ref = pe.reoptimize(single, rho, months_held=2.0)
        assert np.array_equal(ref.moved, mig.moved)
        assert ref.migration_cents == mig.migration_cents
        assert ref.penalty_cents == mig.penalty_cents
        assert ref.plan.report.total_cents == mig.plan.report.total_cents


def test_fleet_engine_shared_provider_cap_couples_tenants():
    """fleet_provider_capacity_gb: a provider's global capacity binds the
    fleet total, not each tenant separately."""
    az = azure_table()
    table = multi_cloud_table([ProviderCostTable("alpha", az),
                               ProviderCostTable("beta", az)])
    cfg = ScopeConfig(schemes=("none", "lz4"))
    rng = np.random.default_rng(8)
    probs = [_make_problem(rng, n, table, cfg, K=2) for n in (5, 8, 6)]
    fe0 = FleetEngine(table, cfg)
    base = fe0.solve(probs)
    prov = np.asarray(table.provider_of_tier, int)
    use_p = np.zeros(2)
    for p, plan in zip(probs, base.plans):
        tier = plan.assignment.tier.astype(int)
        np.add.at(use_p, prov[tier], plan.stored_gb)
    big = int(use_p.argmax())
    name = table.provider_names[big]
    fe = FleetEngine(table, cfg,
                     fleet_provider_capacity_gb={name: 0.6 * use_p[big]})
    assert fe.coupled
    fp = fe.solve(probs)
    assert fp.fleet.feasible
    got = np.zeros(2)
    for p, plan in zip(probs, fp.plans):
        tier = plan.assignment.tier.astype(int)
        np.add.at(got, prov[tier], plan.stored_gb)
    assert got[big] <= 0.6 * use_p[big] + 1e-9
    assert fp.total_cents >= base.total_cents - 1e-9


def test_fleet_engine_validates_provider_names():
    table = azure_table()
    cfg = ScopeConfig()
    with pytest.raises(ValueError, match="MultiCloudCostTable"):
        FleetEngine(table, cfg, fleet_provider_capacity_gb={"x": 1.0})


def test_fleet_engine_mesh_single_device_matches_unsharded():
    """mesh= with one device takes the plain jitted path — same results."""
    import jax
    from jax.sharding import Mesh
    fleet = _ragged_fleet(seed=10, Ns=(5, 7, 2))
    base = capacitated_assign_batch([x[0] for x in fleet],
                                    [x[1] for x in fleet],
                                    [x[2] for x in fleet],
                                    [x[3] for x in fleet])
    mesh = Mesh(np.array(jax.devices()[:1]), ("tenants",))
    got = capacitated_assign_batch([x[0] for x in fleet],
                                   [x[1] for x in fleet],
                                   [x[2] for x in fleet],
                                   [x[3] for x in fleet], mesh=mesh)
    for a, b in zip(base.assignments, got.assignments):
        assert _identical(a, b)


# ------------------------------------------------------------ fleet daemon
def test_fleet_daemon_infinite_budget_matches_independent_daemons():
    """Acceptance pin: a fleet daemon cycle with unbounded budget is
    bit-identical to T independent batch-mode daemons."""
    table = azure_table()
    cfg = ScopeConfig(schemes=("none", "lz4"))
    rng = np.random.default_rng(11)
    probs = [_make_problem(rng, n, table, cfg, K=2) for n in (6, 9, 4)]
    pe = PlacementEngine(table, cfg)
    fe = FleetEngine(table, cfg)
    singles = [pe.solve(p) for p in probs]
    fleet_daemon = ReoptimizationDaemon(fe, plans=[pe.solve(p)
                                                   for p in probs])
    daemons = [ReoptimizationDaemon(pe, plan=s) for s in singles]
    for cycle in range(4):
        rhos = [p.rho * rng.uniform(0.2, 4.0, p.n) for p in probs]
        rep = fleet_daemon.step(rhos)
        reps = [d.step(r) for d, r in zip(daemons, rhos)]
        assert rep.n_tenants == len(probs)
        assert rep.n_selected == sum(r.n_selected for r in reps)
        assert rep.spent_cents == pytest.approx(
            sum(r.spent_cents for r in reps), abs=1e-9)
        assert rep.steady_cents == pytest.approx(
            sum(r.steady_cents for r in reps), abs=1e-9)
        for t, d in enumerate(daemons):
            assert np.array_equal(fleet_daemon.plans[t].assignment.tier,
                                  d.plan.assignment.tier)
            assert np.array_equal(fleet_daemon.plans[t].assignment.scheme,
                                  d.plan.assignment.scheme)


def test_fleet_daemon_shared_budget_caps_whole_fleet():
    table = azure_table()
    cfg = ScopeConfig(schemes=("none", "lz4"))
    rng = np.random.default_rng(12)
    probs = [_make_problem(rng, n, table, cfg, K=2) for n in (8, 8, 8)]
    pe = PlacementEngine(table, cfg)
    fe = FleetEngine(table, cfg)
    cap = 0.5
    d = ReoptimizationDaemon(fe, plans=[pe.solve(p) for p in probs],
                             budget=MigrationBudget(cents_per_cycle=cap))
    for cycle in range(3):
        rhos = [p.rho * rng.uniform(0.1, 8.0, p.n) for p in probs]
        rep = d.step(rhos)
        assert rep.spent_cents <= cap + 1e-9
        assert rep.n_tenants == 3


def test_fleet_daemon_rejects_wrong_arguments():
    table = azure_table()
    cfg = ScopeConfig(schemes=("none", "lz4"))
    rng = np.random.default_rng(13)
    prob = _make_problem(rng, 4, table, cfg, K=2)
    pe = PlacementEngine(table, cfg)
    fe = FleetEngine(table, cfg)
    plan = pe.solve(prob)
    with pytest.raises(ValueError, match="plans="):
        ReoptimizationDaemon(fe)
    with pytest.raises(ValueError, match="plans="):
        ReoptimizationDaemon(fe, plan=plan)
    with pytest.raises(ValueError, match="fleet mode"):
        ReoptimizationDaemon(pe, plan=plan, plans=[plan])
    with pytest.raises(ValueError, match="batch-mode only"):
        ReoptimizationDaemon(fe, plans=[plan], amortize_oversized=True)


def test_chunked_scan_dispatch_preserves_bit_parity(monkeypatch):
    """Fleets larger than _FLEET_CHUNK run the lean scan in fixed-size
    chunks (one compiled shape for any T); chunk boundaries and the dummy
    pad tenants in the last chunk must not perturb a single bit."""
    from repro.core import optassign as oa
    monkeypatch.setattr(oa, "_FLEET_CHUNK", 4)   # 11 tenants -> 3 chunks
    fleet = _ragged_fleet(seed=21, Ns=(5, 9, 3, 9, 1, 8, 6, 4, 7, 2, 5))
    singles = [capacitated_assign(c, f, s, cap) for c, f, s, cap in fleet]
    batch = capacitated_assign_batch([x[0] for x in fleet],
                                     [x[1] for x in fleet],
                                     [x[2] for x in fleet],
                                     [x[3] for x in fleet])
    for single, got in zip(singles, batch.assignments):
        assert _identical(single, got)


# ------------------------------------------------------------ golden plans
def _greedy_use(cost, feas, stored):
    """Per-tier GB of the unconstrained optimum."""
    a = greedy_assign(cost, feas)
    use = np.zeros(cost.shape[1])
    np.add.at(use, a.tier, stored[np.arange(len(a.tier)), a.tier, a.scheme])
    return use


def _capped_fleet(rng, Ns, sla=False):
    """Tenants whose most-used tier is capped at 0.3-1.3 times its
    unconstrained use, so the cap binds for most and not for all.
    ``sla`` adds penalty tensors, weighed at 0.5 when drawing the caps."""
    costs, feas, stored, caps, pens = [], [], [], [], []
    for n in Ns:
        cost, f, s, _ = _tenant_instance(rng, int(n))
        pen = (rng.gamma(1.0, 2.0, cost.shape)
               * (rng.random(cost.shape) < 0.3)) if sla else 0.0
        use = _greedy_use(cost + 0.5 * pen, f, s)
        cap = np.full(4, np.inf)
        cap[int(use.argmax())] = rng.uniform(0.3, 1.3) * use.max()
        for xs, x in zip((costs, feas, stored, caps, pens),
                         (cost, f, s, cap, pen)):
            xs.append(x)
    return costs, feas, stored, caps, pens


def _golden_solve(case):
    """Solve one golden case; returns every tenant's Assignment."""
    rng = np.random.default_rng(_GOLDEN[case][0])
    if case.startswith("single"):
        cost, feas, stored, caps, pens = _capped_fleet(
            rng, [60], sla=case == "single_sla")
        kw = {}
        if case == "single_tier_and_group_caps":
            use = _greedy_use(cost[0], feas[0], stored[0])
            kw = dict(tier_groups=np.array([0, 0, 1, 1]),
                      group_capacity_gb=np.array(
                          [0.8 * use[:2].sum(), np.inf]))
        elif case == "single_sla":
            kw = dict(sla_penalty=pens[0], sla_lambda=0.5)
        return [capacitated_assign(cost[0], feas[0], stored[0], caps[0],
                                   **kw)]
    if case == "ragged_with_empty_tenant":
        costs, feas, stored, caps, _ = _capped_fleet(
            rng, (24, 40, 0, 17, 33, 8, 29))
        return capacitated_assign_batch(costs, feas, stored,
                                        caps).assignments
    Ns = rng.integers(4, 25, 70) if case == "beyond_one_chunk" \
        else rng.integers(10, 41, 12)
    costs, feas, stored, caps, pens = _capped_fleet(
        rng, Ns, sla=case == "fleet_sla")
    kw = {}
    if case == "one_tenant_unmeetable":
        caps[5] = np.full(4, 1e-6)          # below any partition's bytes
    elif case == "fleet_sla":
        kw = dict(sla_penalties=pens, sla_lambda=0.5)
    return capacitated_assign_batch(costs, feas, stored, caps,
                                    **kw).assignments


def _plan_digest(assignments):
    """SHA-1 of every tenant's tiers then every tenant's schemes, the sum
    of the feasible tenants' costs, and one feasibility flag per tenant."""
    vec = np.concatenate([np.asarray(a.tier, np.int64) for a in assignments]
                         + [np.asarray(a.scheme, np.int64)
                            for a in assignments])
    return (hashlib.sha1(vec.tobytes()).hexdigest(),
            float(sum(a.cost for a in assignments if a.feasible)),
            "".join("1" if a.feasible else "0" for a in assignments))


# case: (seed, SHA-1, cost, feasibility per tenant), read off the solver
# as it stood before its uncoupled finish was unified
_GOLDEN = {
    "single_tier_cap": (
        101, "0eccb01d9a4a60835e712d5df7e488d842237313",
        15185.208961501403, "1"),
    "single_tier_and_group_caps": (
        102, "dc1641467679185998a567a4cd0a4ad83340c675",
        26278.82356168516, "1"),
    "single_sla": (
        103, "d06d7ca5b9c5a9bccfee9ad56206c4b022a263a9",
        11790.98827177902, "1"),
    "ragged_with_empty_tenant": (
        104, "9fc224e433bc9c196bfb3bf937e2b984845b1468",
        26764.059925680922, "1" * 7),
    "one_tenant_unmeetable": (
        105, "a80b87e83d86cc3e2b9beb4537ef154d25069015",
        55136.43177521025, "111110111111"),
    "beyond_one_chunk": (
        106, "4deff400688fb716bb99329156459fcf4c6f19b3",
        158070.75267368575, "1" * 70),
    "fleet_sla": (
        107, "46cc8ad20dce0cec3434718a1f43ce69d69f0d8f",
        57046.914898598, "1" * 12),
}


@pytest.mark.parametrize("case", list(_GOLDEN))
def test_golden_plans(case):
    """Every capacitated solve lands on the plan pinned here, bit for bit:
    one tenant or a fleet, tier and group caps, an N=0 tenant, a tenant
    no repair can make feasible, a fleet past one scan chunk, SLA terms."""
    _, digest, cost, feasible = _GOLDEN[case]
    got = _plan_digest(_golden_solve(case))
    assert got[0] == digest
    assert got[1] == pytest.approx(cost, rel=1e-12)
    assert got[2] == feasible
