"""tenants_per_s: tenant plans completed per window second (tenants/s)."""


def read(run):
    return run.units("tenants") / run.window_s
