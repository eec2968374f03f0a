"""assign_s.fleet: seconds in the fleet's AssignStage
(``FleetEngine.assign_batch``: every tenant's solver inputs, the batched
solve and its host finish) per fleet plan."""


def read(run):
    plans = run.units("plans")
    return run.rec.span_seconds("AssignStage") / plans if plans else None
