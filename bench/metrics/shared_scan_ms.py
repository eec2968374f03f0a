"""shared_scan_ms: device milliseconds per fleet plan of the coupled
fleet's Lagrangian scan, from the trace: every program compiled from
``_fleet_scan_single``, the scan that carries the fleet-wide shared rows.
None when no such scan ran (the quota did not bind) or the function was
renamed."""


def read(run):
    plans = run.units("plans")
    if run.trace is None or not plans:
        return None
    s = run.trace.module_seconds("_fleet_scan_single")
    return 1e3 * s / plans if s > 0 else None
