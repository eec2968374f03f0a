"""partition_s: seconds in PartitionStage (G-PART and the partitions'
tables and bytes) per plan, from the benchmark's span around the stage."""


def read(run):
    plans = run.units("plans")
    return run.rec.span_seconds("PartitionStage") / plans if plans else None
