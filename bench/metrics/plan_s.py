"""plan_s: window seconds per plan completed in it (s/plan). The window
runs whole requests, so every plan counted was timed from start to end."""


def read(run):
    plans = run.units("plans")
    return run.window_s / plans if plans else None
