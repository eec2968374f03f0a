"""idle_share.<part>: share of the traced window in which the device ran
no operation (%), averaged over the chips used. One reader for every
part: ``idle_share.lake`` moves ``plan_s``, ``idle_share.fleet`` moves
``tenants_per_s``."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share
