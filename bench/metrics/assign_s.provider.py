"""assign_s.provider: seconds in the provider fleet's AssignStage
(``FleetEngine.assign_batch``: every tenant's solver inputs, the coupled
scan under the shared quota and its host finish: each tenant's repair,
the cross-tenant repair and the polish) per fleet plan."""


def read(run):
    plans = run.units("plans")
    return run.rec.span_seconds("AssignStage") / plans if plans else None
