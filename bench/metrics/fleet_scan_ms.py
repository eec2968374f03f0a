"""fleet_scan_ms: device milliseconds of the jitted fleet Lagrangian scan
per fleet plan, from the trace: every program compiled from
``_fleet_scan_plain`` or ``_fleet_scan_single``. None when no scan ran
(no cap binds) or the functions were renamed."""

SCANS = ("_fleet_scan_plain", "_fleet_scan_single")


def read(run):
    plans = run.units("plans")
    if run.trace is None or not plans:
        return None
    s = sum(run.trace.module_seconds(f) for f in SCANS)
    return 1e3 * s / plans if s > 0 else None
