"""The program's own host spans (``scope:<name>``) in a CPU-traced run of
every cell: each opens inside the window, and the spans that split a
stage fit inside the benchmark's span around that stage. Run by hand
with the harness's other tests:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import pathlib
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
from test_bench import SEED, shrink  # noqa: E402

# cell -> (the benchmark's stage span, the program spans that split it)
SPLITS = {
    "tpch_lake.replan": [
        ("PartitionStage", ("gpart", "partition.materialize")),
        ("CompressStage", ("features.encode", "features.entropy"))],
    "enterprise_fleet.capped": [
        ("AssignStage", ("assign.inputs", "assign.scan", "assign.finish"))],
    "enterprise_fleet.uncapped": [
        ("AssignStage", ("assign.inputs",))],
}


def _window_seconds(path) -> dict:
    """Seconds each host span (``bench:`` or ``scope:``, by its full
    name) was open inside ``bench:window``, clipped to it and summed."""
    from jax import profiler
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for plane in profiler.ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith(("bench:", "scope:"))]
    (_, w0, w1), = [e for e in events if e[0] == "bench:window"]
    out: dict = {}
    for name, a, b in events:
        if b > w0 and a < w1:
            out[name] = out.get(name, 0.0) + (min(b, w1) - max(a, w0)) * 1e-9
    return out


@pytest.mark.parametrize("workload", sorted(SPLITS))
def test_program_spans_split_the_stage_spans(capsys, monkeypatch, tmp_path,
                                             workload):
    find = run._find_trace

    def keep(root):                    # the run deletes its trace
        path = find(root)
        return shutil.copy(path, tmp_path / path.name)
    monkeypatch.setattr(run, "_find_trace", keep)
    rc = run.main(["--workload", workload, "--seed", str(SEED),
                   "--seconds", "1.5", "--trace", "1"],
                  require_tpu=False, shrink=shrink)
    assert rc == 0
    capsys.readouterr()
    trace, = tmp_path.glob("*.xplane.pb")
    s = _window_seconds(trace)
    for stage, parts in SPLITS[workload]:
        assert all(s.get("scope:" + p, 0.0) > 0 for p in parts), (stage, s)
        assert sum(s["scope:" + p] for p in parts) <= s["bench:" + stage]
