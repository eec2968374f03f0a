"""compress_s: seconds in CompressStage (string encoding, the entropy
features on the device, COMPREDICT's models) per plan, from the
benchmark's span around the stage."""


def read(run):
    plans = run.units("plans")
    return run.rec.span_seconds("CompressStage") / plans if plans else None
