"""The enterprise fleet: tenants' lakes from the seed, monthly fleet
re-plans through ``FleetEngine.solve``, and the check against
``cost_ref``, the plain cost model.

Each tenant is one of the paper's Table II customers (A-D in turn). Its
datasets get log-normal sizes and a monthly read series from one of the
five access families of the paper's Figs 1-2 (decreasing, constant,
periodic, spike, cold), with Zipf popularity over the datasets — the
model of the repository's enterprise workload generator, drawn here with
whole-array numpy. A request re-plans every tenant for one month: the
reads over the next ``horizon_months`` months are the access counts.
"""

from __future__ import annotations

import dataclasses
import pathlib
import sys
from typing import Dict, List

import numpy as np

_HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(_HERE.parents[1] / "src"), str(_HERE.parent)]

import cost_ref as ref  # noqa: E402
from pricing import cost_table  # noqa: E402
from repro.core.costs import Weights  # noqa: E402
from repro.core.engine import PlacementProblem, ScopeConfig  # noqa: E402
from repro.core.fleet import FleetEngine  # noqa: E402


# ------------------------------------------------------------- generation
def lake_reads(rng: np.random.Generator, n: int, months: int,
               families: Dict[str, float], zipf: float) -> tuple:
    """(sizes are drawn by the caller) -> (N, months) monthly reads.

    base popularity ``40 * zipf_weight``; per family the mean reads of a
    dataset created in month ``c`` at age ``a = month - c``:
    decreasing ``base*exp(-lam*a)``, lam ~ U(0.15, 0.5); constant
    ``0.6*base``; periodic ``base*(0.15 + 1.7*[(a+phase) % P == 0])``,
    P in {6, 12}; spike ``3*base`` for a <= 1, then ``0.02*base``; cold
    0.02. Nothing is read before creation; reads are Poisson."""
    names = list(families)
    p = np.array([families[k] for k in names], np.float64)
    p /= p.sum()
    w = np.arange(1, n + 1, dtype=np.float64) ** -zipf
    w = w / w.sum() * n
    rng.shuffle(w)
    base = 40.0 * w
    created = rng.integers(0, max(months - 2, 1), n)
    fam = rng.choice(len(names), size=n, p=p)
    lam = rng.uniform(0.15, 0.5, n)
    period = rng.choice([6, 12], size=n)
    phase = rng.integers(0, period)
    rel = np.arange(months)[None, :] - created[:, None]
    a = np.maximum(rel, 0)
    means = {
        "decreasing": base[:, None] * np.exp(-lam[:, None] * a),
        "constant": np.broadcast_to(0.6 * base[:, None], rel.shape),
        "periodic": base[:, None] * (
            0.15 + 1.7 * ((rel + phase[:, None]) % period[:, None] == 0)),
        "spike": np.where(rel <= 1, 3.0 * base[:, None],
                          0.02 * base[:, None]),
        "cold": np.full(rel.shape, 0.02),
    }
    mean = np.choose(fam[:, None], [means[k] for k in names])
    mean = np.where(rel >= 0, mean, 0.0)
    return rng.poisson(np.maximum(mean, 0.0)).astype(np.float64)


@dataclasses.dataclass
class Tenant:
    spans: np.ndarray        # (N,) GB
    reads: np.ndarray        # (N, months)
    R: np.ndarray            # (N, K) compression ratio
    D: np.ndarray            # (N, K) decompression seconds, whole dataset


def make_fleet(config: dict, seed: int) -> List[Tenant]:
    K = len(config["schemes"])
    lo_r, hi_r = config["assumed"]["ratio_range"]
    lo_d, hi_d = config["assumed"]["decompress_s_per_gb_range"]
    custs = config["customers"]
    out = []
    for t in range(int(config["tenants"])):
        c = custs[t % len(custs)]
        rng = np.random.default_rng([seed, t])
        n = int(c["datasets"])
        spans = np.exp(rng.normal(*c["size_lognorm"], n))
        reads = lake_reads(rng, n, int(config["trace_months"]),
                           config["access_families"],
                           float(config["zipf_exponent"]))
        R = np.concatenate([np.ones((n, 1)),
                            rng.uniform(lo_r, hi_r, (n, K - 1))], 1)
        D = np.concatenate([np.zeros((n, 1)),
                            rng.uniform(lo_d, hi_d, (n, K - 1))], 1)
        out.append(Tenant(spans, reads, R, D * spans[:, None]))
    return out


def fleet_cap(config: dict, fleet: List[Tenant], month: int,
              horizon: int, tier: int, quantile: float) -> float:
    """The mix's cap on ``tier`` (GB): the given quantile of the tenants'
    unconstrained use of that tier (from the reference's exact plan, so
    the program does not set its own cap). It binds for the tenants above
    that quantile."""
    L = len(config["pricing"]["tiers"])
    uses = []
    for t in fleet:
        rho = t.reads[:, month:month + horizon].sum(1)
        cost = ref.cost_tensor(t.spans, rho, t.R, t.D, config["pricing"],
                               config["weights"], horizon)
        uses.append(ref.usage(t.spans, t.R, *ref.argmin_plan(cost),
                              L)[tier])
    return float(np.quantile(uses, quantile))


# ------------------------------------------------------------------- cell
@dataclasses.dataclass
class Answer:
    """What one fleet re-plan produced, per tenant."""

    tier: List[np.ndarray]
    scheme: List[np.ndarray]
    cost: np.ndarray         # (T,) the plan's objective
    feasible: np.ndarray     # (T,)
    bill: np.ndarray         # (T, 4) storage, read, decomp, total cents


class FleetCell:
    def __init__(self, config: dict, mix: dict, seed: int, rec):
        self.config = config
        self.horizon = int(mix["horizon_months"])
        self.pool = [int(m) for m in mix["replan_months"]]
        self.fleet = make_fleet(config, seed)
        self.table = cost_table(config["pricing"])
        L = self.table.num_tiers
        self.cap = None
        if mix["cap"] is not None:
            self.cap_tier = int(mix["cap"]["tier"])
            self.cap_gb = fleet_cap(config, self.fleet, self.pool[0],
                                    self.horizon, self.cap_tier,
                                    float(mix["cap"]["quantile"]))
            self.cap = np.full(L, np.inf)
            self.cap[self.cap_tier] = self.cap_gb
        w = config["weights"]
        cfg = ScopeConfig(schemes=tuple(config["schemes"]),
                          months=float(self.horizon), capacity_gb=self.cap,
                          weights=Weights(w["alpha"], w["beta"], w["gamma"]))
        self.problems = {m: [PlacementProblem(
            spans_gb=t.spans, rho=self.rho(t, m),
            current_tier=np.full(len(t.spans), -1), R=t.R, D=t.D,
            schemes=cfg.schemes, table=self.table, cfg=cfg)
            for t in self.fleet] for m in self.pool}
        self.engine = FleetEngine(self.table, cfg)
        self.engine.assign_batch = rec.wrap("AssignStage",
                                            self.engine.assign_batch)
        self.engine.engine.billing = rec.wrap("BillingStage",
                                              self.engine.engine.billing)

    def rho(self, t: Tenant, month: int) -> np.ndarray:
        return t.reads[:, month:month + self.horizon].sum(1)

    def serve(self, month: int) -> Answer:
        plan = self.engine.solve(self.problems[month])
        return Answer(
            tier=[np.asarray(p.assignment.tier, np.int8) for p in plan.plans],
            scheme=[np.asarray(p.assignment.scheme, np.int8)
                    for p in plan.plans],
            cost=np.array([p.assignment.cost for p in plan.plans]),
            feasible=np.array([p.assignment.feasible for p in plan.plans]),
            bill=np.array([[p.report.storage_cents, p.report.read_cents,
                            p.report.decomp_cents, p.report.total_cents]
                           for p in plan.plans]))

    def units(self, ans: Answer) -> dict:
        return {"plans": 1, "tenants": len(ans.tier)}

    # ---------------------------------------------------------------- check
    def reference(self, month: int, dtype=np.float64) -> list:
        """Per tenant: (cost tensor, best cost, binding, ref tier, scheme)."""
        out = []
        for t in self.fleet:
            rho = self.rho(t, month)
            cost = ref.cost_tensor(t.spans, rho, t.R, t.D,
                                   self.config["pricing"],
                                   self.config["weights"], self.horizon,
                                   dtype=dtype)
            tier, scheme = ref.argmin_plan(cost)
            best, binding = ref.plan_cost(cost, tier, scheme), False
            if self.cap is not None:
                use = ref.usage(t.spans, t.R, tier, scheme, len(self.cap))
                if use[self.cap_tier] > self.cap_gb:
                    best, tier, scheme = ref.capped_solve(
                        cost, t.spans, t.R, self.cap_tier, self.cap_gb,
                        dtype=dtype)
                    binding = True
            out.append((cost, best, binding, tier, scheme))
        return out

    def readings(self, month: int, ans: Answer, refs: list) -> dict:
        """The compared numbers for one answer: the worst tenant of each."""
        pricing = self.config["pricing"]
        r = {"plan_gap": 0.0, "bill_gap": 0.0}
        if self.cap is not None:
            r.update(cap_excess=0.0, move_gain=0.0)
        if len(ans.tier) != len(self.fleet):
            return {k: float("inf") for k in r}
        for t, (ten, (cost, best, binding, _, _)) in enumerate(
                zip(self.fleet, refs)):
            tier = np.asarray(ans.tier[t], np.int64)
            scheme = np.asarray(ans.scheme[t], np.int64)
            if tier.shape != ten.spans.shape or not ans.feasible[t]:
                return {k: float("inf") for k in r}
            got = ref.plan_cost(cost, tier, scheme)
            if binding:
                r["move_gain"] = max(r["move_gain"], ref.move_gain(
                    cost, ten.spans, ten.R, tier, scheme, self.cap_tier,
                    self.cap_gb))
                best = got          # the cap binds: no exact optimum here
            # the reported objective is the least there is (where no cap
            # binds), and is what the chosen cells cost
            r["plan_gap"] = max(r["plan_gap"], abs(ans.cost[t] - best)
                                / abs(best), abs(got - best) / abs(best))
            if self.cap is not None:
                use = ref.usage(ten.spans, ten.R, tier, scheme,
                                len(self.cap))[self.cap_tier]
                r["cap_excess"] = max(r["cap_excess"],
                                      (use - self.cap_gb) / self.cap_gb)
            b = ref.bill(ten.spans, self.rho(ten, month), ten.R, ten.D, tier,
                         scheme, pricing, self.horizon)
            want = np.array([b["storage"], b["read"], b["decomp"],
                             b["total"]])
            rel = np.abs(ans.bill[t] - want) / np.maximum(np.abs(want),
                                                          1e-12)
            r["bill_gap"] = max(r["bill_gap"], float(rel.max()))
        return r

    def control(self, j: int, served: Answer) -> Answer:
        """The reference in the program's place for pool request ``j``,
        one precision down: the solve in bfloat16 (the program's device
        solve is float32), the plan's objective and bill in float32 (the
        program's are float64). ``served`` is not read."""
        import ml_dtypes
        month = self.pool[j]
        tiers, schemes, bills, objective = [], [], [], []
        for t, (cost, _, _, tier, scheme) in zip(
                self.fleet, self.reference(month, dtype=ml_dtypes.bfloat16)):
            b = ref.bill(t.spans, self.rho(t, month), t.R, t.D, tier, scheme,
                         self.config["pricing"], self.horizon,
                         dtype=np.float32)
            tiers.append(tier)
            schemes.append(scheme)
            objective.append(ref.plan_cost(ref.cost_tensor(
                t.spans, self.rho(t, month), t.R, t.D,
                self.config["pricing"], self.config["weights"], self.horizon,
                dtype=np.float32), tier, scheme))
            bills.append([b["storage"], b["read"], b["decomp"], b["total"]])
        T = len(self.fleet)
        return Answer(tiers, schemes, np.array(objective), np.ones(T, bool),
                      np.array(bills))

    def check(self, answers) -> list:
        """``[(name, worst reading, limit)]`` over every answer."""
        for name in ("engine", "problems"):     # the program's state goes
            self.__dict__.pop(name, None)
        limits = self.config["limits"]
        refs = {m: self.reference(m)
                for m in sorted({self.pool[j] for j, _ in answers})}
        worst: Dict[str, float] = {}
        for j, ans in answers:
            for k, v in self.readings(self.pool[j], ans,
                                      refs[self.pool[j]]).items():
                worst[k] = max(worst.get(k, 0.0), v)
        return [(k, worst[k], limits[k]) for k in sorted(worst)]


def build(config: dict, mix: dict, seed: int, rec) -> FleetCell:
    return FleetCell(config, mix, seed, rec)
