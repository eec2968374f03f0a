"""billing_s.fleet: seconds in BillingStage, summed over the tenants of
a fleet plan (``FleetEngine.solve`` bills each tenant), per fleet plan."""


def read(run):
    plans = run.units("plans")
    return run.rec.span_seconds("BillingStage") / plans if plans else None
