"""The provider fleet: the enterprise fleet's tenant lakes under one
quota on one tier that every tenant draws on, re-planned monthly through
``FleetEngine.solve`` with a shared capacity row, and checked against
``provider_fleet_ref`` (the fleet-wide quota on ``cost_ref``'s model).

The tenants are ``enterprise_fleet``'s, drawn by its generator from the
configuration's ``data_seed``: the fleet and its read series are the same
for every run, since their draw sets the work of a plan (the polish's
moves) and a run's seed that drew them would change it. The run's seed
sets the order in which the pool of months is replayed. The quota is the
mix's share of the fleet's unconstrained use of the quota tier in the
mix's first re-plan month, from the reference's exact plans, so the
program does not set its own quota; it stays fixed for every month, as
bought capacity does.
"""

from __future__ import annotations

import inspect
import pathlib
import sys
from typing import Dict

import numpy as np

_HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(_HERE.parents[1] / "src"), str(_HERE.parent), str(_HERE)]

import cost_ref as ref  # noqa: E402
import provider_fleet_ref as pref  # noqa: E402
from enterprise_fleet import Answer, FleetCell  # noqa: E402
from repro.core.fleet import FleetEngine  # noqa: E402
from repro.core.optassign import capacitated_assign_batch  # noqa: E402


class ProviderCell(FleetCell):
    """``FleetCell``'s fleet, problems, serve and units, with no per-tenant
    cap; its engine couples the tenants through one shared row."""

    def __init__(self, config: dict, mix: dict, seed: int, rec):
        super().__init__(config, dict(mix, cap=None),
                         int(config["data_seed"]), rec)
        self.tier = int(mix["quota"]["tier"])
        spans, Rs = self.spans_and_ratios()
        self.quota = float(mix["quota"]["share"]) * pref.unconstrained_use(
            self.costs(self.pool[0]), spans, Rs, self.tier)
        self.pool = [int(self.pool[j]) for j in
                     np.random.default_rng(seed).permutation(len(self.pool))]
        L = self.table.num_tiers
        shared = np.full(L, np.inf)
        shared[self.tier] = self.quota
        self.engine = FleetEngine(self.table, self.engine.cfg,
                                  shared_tier_groups=np.arange(L),
                                  shared_capacity_gb=shared)
        self.engine.assign_batch = rec.wrap("AssignStage",
                                            self.engine.assign_batch)
        self.engine.engine.billing = rec.wrap("BillingStage",
                                              self.engine.engine.billing)
        # what one plan's scan reads: every tenant's datasets (not the
        # padding), L x K cells each, for the program's scan steps
        iters = inspect.signature(
            capacitated_assign_batch).parameters["iters"].default
        self.scan_shape = (sum(len(s) for s in spans), L,
                           len(config["schemes"]), int(iters))

    def spans_and_ratios(self) -> tuple:
        return [t.spans for t in self.fleet], [t.R for t in self.fleet]

    def costs(self, month: int, dtype=np.float64) -> list:
        return [ref.cost_tensor(t.spans, self.rho(t, month), t.R, t.D,
                                self.config["pricing"],
                                self.config["weights"], self.horizon,
                                dtype=dtype) for t in self.fleet]

    # ---------------------------------------------------------------- check
    def plan_costs(self, ans: Answer, costs: list):
        """(T,) float64 cost of each tenant's chosen cells, or None where
        the answer is not a whole feasible fleet plan."""
        if len(ans.tier) != len(self.fleet) or not np.all(ans.feasible):
            return None
        if any(np.shape(l) != t.spans.shape
               for l, t in zip(ans.tier, self.fleet)):
            return None
        return np.array([ref.plan_cost(c, np.asarray(l, np.int64),
                                       np.asarray(k, np.int64))
                         for c, l, k in zip(costs, ans.tier, ans.scheme)])

    def readings(self, month: int, ans: Answer, costs: list) -> dict:
        """The compared numbers for one answer, each the fleet's worst."""
        got = self.plan_costs(ans, costs)
        if got is None:
            return dict.fromkeys(self.config["limits"], float("inf"))
        tiers = [np.asarray(l, np.int64) for l in ans.tier]
        schemes = [np.asarray(k, np.int64) for k in ans.scheme]
        spans, Rs = self.spans_and_ratios()
        use = pref.fleet_use(spans, Rs, tiers, schemes, self.tier)
        bill_gap = 0.0
        for t, l, k, b in zip(self.fleet, tiers, schemes, ans.bill):
            want = ref.bill(t.spans, self.rho(t, month), t.R, t.D, l, k,
                            self.config["pricing"], self.horizon)
            want = np.array([want["storage"], want["read"], want["decomp"],
                             want["total"]])
            bill_gap = max(bill_gap, float(np.max(
                np.abs(b - want) / np.maximum(np.abs(want), 1e-12))))
        return {
            "shared_excess": (use - self.quota) / self.quota,
            "move_gain": float(pref.move_gains(costs, spans, Rs, tiers,
                                               schemes, self.tier,
                                               self.quota).max()),
            "plan_gap": float(np.max(np.abs(ans.cost - got) / np.abs(got))),
            "bill_gap": bill_gap,
        }

    def control(self, j: int, served: Answer) -> Answer:
        """The reference in the program's place for pool request ``j``,
        one precision down: the fleet solve in bfloat16 (the program's
        device solve is float32), each tenant's objective and bill in
        float32 (the program's are float64). ``served`` is not read."""
        import ml_dtypes
        month = self.pool[j]
        spans, Rs = self.spans_and_ratios()
        _, tiers, schemes = pref.fleet_solve(
            self.costs(month, dtype=ml_dtypes.bfloat16), spans, Rs,
            self.tier, self.quota, dtype=ml_dtypes.bfloat16)
        objective = [ref.plan_cost(c, l, k) for c, l, k in zip(
            self.costs(month, dtype=np.float32), tiers, schemes)]
        bills = []
        for t, l, k in zip(self.fleet, tiers, schemes):
            b = ref.bill(t.spans, self.rho(t, month), t.R, t.D, l, k,
                         self.config["pricing"], self.horizon,
                         dtype=np.float32)
            bills.append([b["storage"], b["read"], b["decomp"], b["total"]])
        return Answer(tiers, schemes, np.array(objective),
                      np.ones(len(self.fleet), bool), np.array(bills))

    def check(self, answers) -> list:
        """``[(name, worst reading, limit)]`` over every answer. Logs, per
        pooled month, the plan's gap above the fleet's Lagrangian bound:
        a reading on the plan's quality, not a check (no limit passes
        every program run and fails every control)."""
        for name in ("engine", "problems"):     # the program's state goes
            self.__dict__.pop(name, None)
        limits = self.config["limits"]
        months = sorted({self.pool[j] for j, _ in answers})
        costs = {m: self.costs(m) for m in months}
        worst: Dict[str, float] = dict.fromkeys(limits, 0.0)
        plans: Dict[int, float] = {}
        for j, ans in answers:
            m = self.pool[j]
            for k, v in self.readings(m, ans, costs[m]).items():
                worst[k] = max(worst[k], v)
            got = self.plan_costs(ans, costs[m])
            if got is not None:
                plans[m] = max(plans.get(m, -np.inf), float(got.sum()))
        spans, Rs = self.spans_and_ratios()
        for m in sorted(plans):
            bound = pref.fleet_solve(costs[m], spans, Rs, self.tier,
                                     self.quota)[0]
            print(f"provider_fleet: month {m}: plan {plans[m]!r}, fleet "
                  f"Lagrangian bound {bound!r}, gap "
                  f"{(plans[m] - bound) / abs(bound)!r}", file=sys.stderr,
                  flush=True)
        return [(k, worst[k], limits[k]) for k in sorted(worst)]


def build(config: dict, mix: dict, seed: int, rec) -> ProviderCell:
    return ProviderCell(config, mix, seed, rec)
