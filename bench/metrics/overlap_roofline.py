"""overlap_roofline: G-PART's overlap kernel against the chip's roofline
(%). The least time is the larger of operations over peak bf16 FLOP/s and
bytes over peak HBM bandwidth; the kernel's time is the device time of
every program compiled from ``fractional_overlap_matrix``.

The count is the problem's, not the kernel's tiles: N query families
over F distinct files with M family-file memberships in all. The N x N
contraction of the families' file indicators is 2 N^2 F operations; the
bytes are the memberships (4 M), the file sizes (4 F) and spans (4 N)
read and the N x N float32 matrix written (4 N^2). At a lake's sizes the
bytes bound it."""


def least_seconds(shapes, peaks) -> float:
    flops = sum(2.0 * n * n * f for n, f, _ in shapes)
    nbytes = sum(4.0 * (m + f + n + n * n) for n, f, m in shapes)
    return max(flops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])


def read(run):
    shapes = run.rec.counters.get("overlap_shape", [])
    if run.trace is None or not shapes:
        return None
    t = run.trace.module_seconds("fractional_overlap_matrix")
    return 100.0 * least_seconds(shapes, run.peaks) / t if t > 0 else None
