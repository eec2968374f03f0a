"""The benchmark harness's own tests, on the CPU at tiny sizes (the
kernels in interpret mode). Outside the repository's tier-1 test paths;
run them by hand:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They drive whole runs of every cell, the same runs with the timed path
broken underneath (each must come out not correct), the control in the
program's place (likewise), the trace reduction on a synthetic and on a
recorded trace, and the roofline counts on known shapes.
"""

from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

SPEC = json.loads((run.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
SEED = 3_000_000_019          # beyond 32 signed bits, as run seeds may be


def shrink(config: dict, mix: dict) -> None:
    """Tiny sizes that a CPU test run holds."""
    if config["name"] == "tpch_lake":
        config["scale_factor"] = 0.001
        config["kernel_backend"] = "interpret"
        config["compredict"]["fit_samples"] = 24
        mix["months"], mix["queries_per_template"] = 2, 4
    else:
        config["tenants"] = 8
        mix["replan_months"] = mix["replan_months"][:3]


def run_cell(capsys, workload: str, trace: int = 0, hook=None) -> dict:
    rc = run.main(["--workload", workload, "--seed", str(SEED),
                   "--seconds", "1.5", "--trace", str(trace)],
                  require_tpu=False, shrink=shrink, build_hook=hook)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def expected(workload: str, kind: str) -> set:
    return {m["name"] for m in SPEC[kind]
            if workload in m.get("workloads", [workload])}


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_correct_with_its_end_to_end_metrics(capsys, workload):
    res = run_cell(capsys, workload)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == expected(workload, "end_to_end")
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("workload", CELLS)
def test_traced_cell_reports_its_span_metrics(capsys, workload):
    res = run_cell(capsys, workload, trace=1)
    assert res["correct"]
    spans = {m["name"] for m in SPEC["per_layer"]
             if m["source"] == "program_span"
             and workload in m["workloads"]}
    assert spans <= set(res["metrics"])
    assert set(res["metrics"]) <= expected(workload, "per_layer")
    assert res["device"]["window_s"] > 0
    assert len(res["breakdown"]["idle_gaps"]) <= 10


# ------------------------------------------------------------------- lake
SPEC_COLUMNS = {      # TPC-H v3 clause 1.4: every column of every table
    "region": "regionkey name comment",
    "nation": "nationkey name regionkey comment",
    "supplier": "suppkey name address nationkey phone acctbal comment",
    "part": "partkey name mfgr brand type size container retailprice "
            "comment",
    "partsupp": "partkey suppkey availqty supplycost comment",
    "customer": "custkey name address nationkey phone acctbal mktsegment "
                "comment",
    "orders": "orderkey custkey orderstatus totalprice orderdate "
              "orderpriority clerk shippriority comment",
    "lineitem": "orderkey partkey suppkey linenumber quantity "
                "extendedprice discount tax returnflag linestatus shipdate "
                "commitdate receiptdate shipinstruct shipmode comment",
}
TEXT_LENGTHS = {"r_comment": (31, 115), "n_comment": (31, 114),
                "s_comment": (25, 100), "p_comment": (5, 22),
                "ps_comment": (49, 198), "c_comment": (29, 116),
                "o_comment": (19, 78), "l_comment": (10, 43),
                "s_address": (10, 40), "c_address": (10, 40)}


def test_lake_has_every_tpch_column_at_its_length():
    import numpy as np
    lake_mod = run.load_module(BENCH / "configs" / "tpch_lake.py")
    lake = lake_mod.make_lake(0.001, np.random.default_rng(1))
    prefix = {"partsupp": "ps_"}
    for name, cols in SPEC_COLUMNS.items():
        want = [prefix.get(name, name[0] + "_") + c for c in cols.split()]
        assert list(lake[name].columns) == want
    assert lake["part"].num_rows == 200 and lake["orders"].num_rows == 1500
    for col, (lo, hi) in TEXT_LENGTHS.items():
        table = {"r": "region", "n": "nation", "s": "supplier",
                 "p": "part", "ps": "partsupp", "c": "customer",
                 "o": "orders", "l": "lineitem"}[col.split("_")[0]]
        n = np.char.str_len(lake[table].columns[col])
        assert lo <= n.min() and n.max() <= hi, col
    li, o = lake["lineitem"].columns, lake["orders"].columns
    assert (li["l_receiptdate"] > li["l_shipdate"]).all()
    assert np.isin(o["o_orderstatus"], ["F", "O", "P"]).all()


# ----------------------------------------------------------------- faults
def _half_batch(ans):
    """Half of the batch left out: only the first half of the tenants'
    plans, or of the lake's partitions, come back."""
    if hasattr(ans, "feasible"):
        h = len(ans.tier) // 2
        return dataclasses.replace(ans, tier=ans.tier[:h],
                                   scheme=ans.scheme[:h], cost=ans.cost[:h],
                                   feasible=ans.feasible[:h],
                                   bill=ans.bill[:h])
    h = len(ans.parts) // 2
    return dataclasses.replace(ans, parts=ans.parts[:h], X=ans.X[:h])


def _altered(ans):
    """An answer altered where it is produced: one dataset placed on the
    next tier, or one file dropped from a partition."""
    if hasattr(ans, "feasible"):
        tier = [t.copy() for t in ans.tier]
        tier[0][0] = (tier[0][0] + 1) % 3
        return dataclasses.replace(ans, tier=tier)
    files, rho = ans.parts[0]
    parts = [(frozenset(sorted(files)[1:]) or files, rho + 1.0)]
    return dataclasses.replace(ans, parts=parts + ans.parts[1:])


FAULTS = {"half_batch": _half_batch, "altered": _altered}


def _stale(cell):
    """Each request answered with the answer of the request before it
    (the first with the next pool entry's)."""
    serve, last = cell.serve, []

    def stale(req):
        ans = serve(req)
        if not last:
            last.append(serve(cell.pool[(cell.pool.index(req) + 1)
                                        % len(cell.pool)]))
        prev, last[0] = last[0], ans
        return prev
    cell.serve = stale
    return cell


@pytest.mark.parametrize("fault", ["half_batch", "altered", "stale"])
@pytest.mark.parametrize("workload", CELLS)
def test_broken_timed_path_is_not_correct(capsys, workload, fault):
    def hook(cell):
        if fault == "stale":
            return _stale(cell)
        serve = cell.serve
        cell.serve = lambda req: FAULTS[fault](serve(req))
        return cell
    res = run_cell(capsys, workload, hook=hook)
    assert not res["correct"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_in_the_programs_place_is_not_correct(capsys, workload):
    def hook(cell):
        serve = cell.serve
        cell.serve = lambda req: cell.control(cell.pool.index(req),
                                              serve(req))
        return cell
    res = run_cell(capsys, workload, hook=hook)
    assert not res["correct"]
    failed = {k for k, c in res["checks"].items()
              if c["value"] > c["limit"]}
    # the control fails every number that has a precision below it; the
    # partitions are exact (G-PART's weights are float64 in both)
    assert failed == set(res["checks"]) - {"partitions_differ"}


def test_without_a_tpu_the_run_fails_and_prints_no_result(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    assert rc != 0
    assert "{" not in capsys.readouterr().out


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout


# ------------------------------------------------------------------ trace
def _ev(name, start, dur):
    return SimpleNamespace(name=name, start_ns=float(start),
                           duration_ns=float(dur))


def _plane(name, lines):
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=k, events=v) for k, v in lines.items()])


def test_trace_reduction_on_a_synthetic_trace():
    trace = run.load_module(BENCH / "trace.py")
    host = _plane("/host:CPU", {"python": [
        _ev("bench:window", 1000, 10000),
        _ev("bench:request", 1000, 9000),
        _ev("bench:AssignStage", 2000, 4000),
        _ev("not-a-span", 0, 50000)]})
    dev = _plane("/device:TPU:0", {
        "XLA Modules": [_ev("jit_scan(123)", 2500, 1500),
                        _ev("jit_scan(123)", 6000, 1000),
                        _ev("jit_other(9)", 500, 100)],
        "XLA Ops": [_ev("%fusion.1 = f32[64,4]{0,1:T(4,128)} fusion(x)",
                        2500, 1000),
                    _ev("%fusion.2 = s32[8]{0} fusion(y)", 3000, 1000),
                    _ev("%copy.3 = f32[2]{0} copy(z)", 6000, 1000)]})
    other = _plane("/device:TPU:1", {"XLA Ops": [_ev("%x = f32[1] x()",
                                                     1000, 10000)]})
    red = trace.reduce_planes([host, dev, other], devices=[0])
    assert red.window_s == pytest.approx(10000e-9)
    assert red.busy_s == pytest.approx(2500e-9)          # [2500,4000) + 1000
    assert red.idle_share == pytest.approx(0.75)
    assert red.module_seconds("scan") == pytest.approx(2500e-9)
    assert red.module_seconds("other") == 0.0            # before the window
    assert red.op_s == pytest.approx({"jit_scan/fusion.1 f32[64,4]": 1000e-9,
                                      "jit_scan/fusion.2 s32[8]": 1000e-9,
                                      "jit_scan/copy.3 f32[2]": 1000e-9})
    # gaps [1000,2500) and [7000,11000) have their middles in the request
    # alone; [4000,6000) has its middle in AssignStage [2000,6000)
    assert red.idle_s == pytest.approx({"request": 5500e-9,
                                        "AssignStage": 2000e-9})


RECORDED = BENCH / "tests" / "data" / "fleet_uncapped.xplane.pb"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_trace_reduction_on_a_recorded_chip_trace():
    trace = run.load_module(BENCH / "trace.py")
    red = trace.reduce(RECORDED, devices=[0])
    assert 0 < red.busy_s < red.window_s
    assert red.module_seconds("_greedy_jax_batch") > 0
    assert sum(red.idle_s.values()) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)
    assert {"AssignStage", "BillingStage"} & set(red.idle_s)


# --------------------------------------------------------------- rooflines
def test_overlap_count_on_known_shapes():
    m = run.load_module(BENCH / "metrics" / "overlap_roofline.py")
    peaks = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e9}
    # 2 N^2 F = 2*100*100*50 = 1e6 ops -> 1e-6 s; bytes 4*(M+F+N+N^2) =
    # 4*(400+50+100+10000) = 42200 -> 4.22e-5 s: bytes bound it
    assert m.least_seconds([(100, 50, 400)], peaks) == pytest.approx(4.22e-5)
    # a wide lake: N=1000, F=1e6 -> 2e12 ops, 2 s; bytes ~4.0e6 -> 4 ms
    assert m.least_seconds([(1000, 10**6, 10**6)], peaks) == \
        pytest.approx(2.0)


def test_entropy_count_on_known_shapes():
    m = run.load_module(BENCH / "metrics" / "entropy_roofline.py")
    shapes = [{"int": (1000, 10), "str": (500, 490)}, {"float": (250, 250)}]
    # 4 bytes per value and per distinct value: 4*(1010+990+500) = 10000
    assert m.least_seconds(shapes, {"hbm_bytes_per_s": 1e4}) == \
        pytest.approx(1.0)


def test_metric_files_cover_benchmark_json():
    names = {m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]}
    files = {run.metric_file(n) for n in names}
    assert all(f.exists() for f in files)
    assert files == set((BENCH / "metrics").glob("*.py"))
    for w in SPEC["workloads"]:
        assert (BENCH / "traffic" / f"{w['config']}.{w['traffic']}.json"
                ).exists()
    for c in SPEC["configs"]:
        assert (run.ROOT / c["file"]).exists()
        assert (BENCH / "configs" / f"{c['name']}.py").exists()
