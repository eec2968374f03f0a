"""shared_scan_roofline: the coupled fleet's Lagrangian scan against the
chip's roofline (%). The scan's time is the device time of every program
compiled from ``_fleet_scan_single``.

The count is the problem's, not the padded batch's: the fleet's sum N
datasets over L tiers and K schemes. Each scan step reads every cell's
float32 cost and stored bytes (8 sum(N) L K bytes) and writes each
dataset's chosen cell as int32 (4 sum(N)); a plan runs the scan's steps.
An argmin and a few adds per cell are far below the peak operations, so
the bytes bound it. The cell gives ``scan_shape`` = (sum N, L, K, steps).
"""


def least_seconds(shape, peaks) -> float:
    n, L, K, steps = shape
    return steps * (8.0 * n * L * K + 4.0 * n) / peaks["hbm_bytes_per_s"]


def read(run):
    plans = run.units("plans")
    shape = getattr(run.cell, "scan_shape", None)
    if run.trace is None or not plans or shape is None:
        return None
    t = run.trace.module_seconds("_fleet_scan_single")
    return 100.0 * plans * least_seconds(shape, run.peaks) / t if t > 0 \
        else None
