"""entropy_roofline: COMPREDICT's entropy-feature kernel (scatter
histogram plus the Pallas reduction) against the chip's roofline (%).
The kernel's time is the device time of every program compiled from
``weighted_entropy_features``.

The count is the problem's: per partition and value class (int, float,
str), one histogram pass over its V values and the reduction over its U
distinct values, reading 4 V bytes of codes and 4 U bytes of string
lengths. The histogram itself is not counted, as a kernel could keep it
on chip, and the operations (an add per value, a few per distinct value)
are far below the peak, so the bytes bound it."""


def least_seconds(shapes, peaks) -> float:
    nbytes = sum(4.0 * (v + u) for part in shapes for v, u in part.values())
    return nbytes / peaks["hbm_bytes_per_s"]


def read(run):
    pools = run.rec.counters.get("pool", [])
    shapes = getattr(run.cell, "shapes", None)
    if run.trace is None or not pools or not shapes:
        return None
    t = run.trace.module_seconds("weighted_entropy_features")
    served = [part for j in pools for part in shapes[j]]
    return 100.0 * least_seconds(served, run.peaks) / t if t > 0 else None
