#!/usr/bin/env python3
"""Run one benchmark cell once, in one process, on the chip it finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are named in the
repository's ``BENCHMARK.json``. Everything that belongs to one of them
sits in a file of its own, found by name:

* ``bench/configs/<config>.json``  the deployment as it is run;
* ``bench/configs/<config>.py``    builds it from the seed, serves one
  request through the program's public entry, and checks the answers
  against ``bench/configs/<config>_ref.py``, the plain reference;
* ``bench/traffic/<config>.<mix>.json``  the traffic mix's parameters;
* ``bench/metrics/<metric>.py``    reads one metric from the run; a
  quantity split by the end-to-end metric it moves (``<q>.<part>``) is
  read by ``<q>.py`` where it has no file of its own.

Set-up (JAX start, the deployment, warming every pooled request) is timed
as ``setup_s``. The window then serves the pool in a closed loop, one
client, each request blocking on its result, for ``--seconds`` seconds
and on to the end of the pass through the pool under way.
With ``--trace 1`` the window runs under the JAX profiler and the
per-layer metrics are read from its trace; otherwise the end-to-end
metrics are reported. The last line of standard output is one JSON
object; the numbers that decided ``correct`` are also the last lines of
standard error.

The run fails (non-zero exit, no result line) when the first JAX device
is not a TPU or there are fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
# the compile cache sits at a fixed path inside the checkout: the path is
# part of the cache key, so only the first run of a checkout compiles
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def load_module(path: pathlib.Path):
    """Import one file of the benchmark by its path (names may hold dots)."""
    name = "bench_" + path.stem.replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Check:
    """One number that decides ``correct``: it passes when value <= limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


class Recorder:
    """Host spans and counters of one run.

    A span is ``(name, t0, t1)`` on ``time.perf_counter``; while the window
    is traced each span is also a ``jax.profiler.TraceAnnotation`` named
    ``bench:<name>``, so it shares the device trace's clock."""

    def __init__(self):
        self.spans: List[tuple] = []
        self.counters: Dict[str, list] = {}
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation("bench:" + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.spans.append((name, t0, time.perf_counter()))
            if ann is not None:
                ann.__exit__(None, None, None)

    def wrap(self, name: str, fn: Callable) -> Callable:
        """``fn`` with every call recorded as a span ``name``."""
        def run(*a, **kw):
            with self.span(name):
                return fn(*a, **kw)
        return run

    def count(self, name: str, value) -> None:
        self.counters.setdefault(name, []).append(value)

    def span_seconds(self, name: str) -> float:
        return sum(t1 - t0 for n, t0, t1 in self.spans if n == name)

    def reset(self) -> None:
        self.spans.clear()
        self.counters.clear()


class CompileCounter:
    """Counts programs lowered and compiled, and the seconds compiling,
    from ``jax.monitoring`` events. A lowering happens on every jit cache
    miss, whether or not the persistent cache then holds the binary."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.lowered = 0
        self.compiled = 0
        self.compile_s = 0.0
        self.cache_hits = 0

    def install(self) -> None:
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == self.LOWER:
            self.lowered += 1
        elif event == self.COMPILE:
            self.compiled += 1
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@dataclasses.dataclass
class Run:
    """What a metric reader sees: the window's requests, spans and
    counters, the set-up time, the cell (checked: what its reference
    learned), the trace reduction (traced runs) and the device peaks."""

    workload: str
    setup_s: float
    window_s: float
    requests: List[dict]
    rec: Recorder
    cell: object
    trace: Optional[object] = None
    peaks: Optional[dict] = None

    def units(self, key: str) -> float:
        """Work of one kind (``plans``, ``tenants``) completed in the
        window."""
        return float(sum(r["units"].get(key, 0) for r in self.requests
                         if r["ok"]))


def metric_file(name: str) -> pathlib.Path:
    """The reader of metric ``name``: its own file, else that of the
    quantity it splits (``idle_share.lake`` -> ``idle_share``)."""
    own = BENCH / "metrics" / f"{name}.py"
    return own if own.exists() else BENCH / "metrics" / (
        name.split(".")[0] + ".py")


def _peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def _find_trace(root: pathlib.Path) -> pathlib.Path:
    found = sorted(root.glob("**/*.xplane.pb"))
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {root}")
    return found[-1]


def serve_window(cell, seconds: float, rec: Recorder, log) -> tuple:
    """The closed loop: the pool in order, again and again, until
    ``seconds`` have passed and the pass through the pool under way is
    complete, so that every run serves whole passes, the same work
    whatever order its seed gave the pool. Returns (requests, answers,
    window seconds)."""
    requests, answers = [], []
    pool = cell.pool
    w0 = time.perf_counter()
    i = 0
    while time.perf_counter() - w0 < seconds or i % len(pool):
        j = i % len(pool)
        t0 = time.perf_counter()
        try:
            with rec.span("request"):
                ans = cell.serve(pool[j])
            units, ok = cell.units(ans), True
            answers.append((j, ans))
        except Exception:  # a failed request is counted, not fatal
            log(traceback.format_exc())
            units, ok = {}, False
        requests.append({"pool": j, "t0": t0 - w0,
                         "t1": time.perf_counter() - w0, "units": units,
                         "ok": ok})
        i += 1
    return requests, answers, time.perf_counter() - w0


def main(argv=None, *, require_tpu: bool = True,
         shrink: Optional[Callable] = None,
         build_hook: Optional[Callable] = None) -> int:
    """Run one cell. ``require_tpu=False``, ``shrink`` (which may change
    the configuration and mix before the build) and ``build_hook`` (which
    may replace the built cell) exist for the harness's own tests only."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        log(f"run: no workload {args.workload!r} in BENCHMARK.json")
        return 2
    wl = cells[args.workload]
    conf = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    mix = json.loads((BENCH / "traffic"
                      / f"{wl['config']}.{wl['traffic']}.json").read_text())
    chips = int(wl["chips"])
    if shrink is not None:
        shrink(config, mix)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        log(f"run: needs a TPU, found {dev.platform!r}")
        return 1
    if len(devices) < chips:
        log(f"run: {args.workload} needs {chips} chips, found {len(devices)}")
        return 1
    used = devices[:chips]
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # cache every program, however fast it compiled: set-up stays steady
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = CompileCounter()
    compiles.install()

    peaks = None
    if args.trace:
        table = json.loads((BENCH / "peaks.json").read_text())["devices"]
        if dev.device_kind not in table and require_tpu:
            log(f"run: no peaks for device kind {dev.device_kind!r} in "
                "bench/peaks.json")
            return 1
        peaks = table.get(dev.device_kind)

    rec = Recorder()
    cfg_mod = load_module(BENCH / "configs" / f"{wl['config']}.py")
    cell = cfg_mod.build(config, mix, args.seed, rec)
    if build_hook is not None:
        cell = build_hook(cell)
    for req in cell.pool:                 # warm every shape the window uses
        cell.serve(req)
    rec.reset()
    lowered0 = compiles.lowered
    setup_s = time.perf_counter() - T_START

    trace = None
    if args.trace:
        from jax import profiler
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = profiler.ProfileOptions()
        opts.python_tracer_level = 0
        profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        rec.annotate = True
        with rec.span("window"):
            requests, answers, window_s = serve_window(
                cell, args.seconds, rec, log)
        rec.annotate = False
        profiler.stop_trace()
    else:
        requests, answers, window_s = serve_window(cell, args.seconds, rec,
                                                   log)
    window_lowered = compiles.lowered - lowered0
    peak = _peak_bytes(used)
    print(f"window: {len(requests)} requests in {window_s:.3f} s, "
          f"{window_lowered} programs compiled in the window, peak device "
          f"bytes {peak}; set-up {setup_s:.3f} s with {compiles.compiled} "
          f"compiles ({compiles.compile_s:.3f} s), {compiles.cache_hits} "
          f"persistent-cache hits", flush=True)

    if args.trace:
        trace_mod = load_module(BENCH / "trace.py")
        trace = trace_mod.reduce(_find_trace(TRACE_DIR),
                                 devices=[d.id for d in used])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    # the program's state goes before the reference runs
    attempted = len(requests)
    failed = sum(1 for r in requests if not r["ok"])
    checks = [Check(n, float(v), float(lim)) for n, v, lim in
              cell.check(answers)]
    correct = (failed == 0 and attempted > 0 and bool(checks)
               and all(c.ok for c in checks))

    run = Run(args.workload, setup_s, window_s, requests, rec, cell, trace,
              peaks)
    names = ([m["name"] for m in spec["end_to_end"]
              if args.workload in m.get("workloads", [args.workload])]
             if not args.trace else
             [m["name"] for m in spec["per_layer"]
              if args.workload in m.get("workloads", [args.workload])])
    units = {m["name"]: m["unit"]
             for m in spec["end_to_end"] + spec["per_layer"]}
    metrics = {}
    for name in names:
        value = load_module(metric_file(name)).read(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(used), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        result["breakdown"] = {"device_ops": trace.top_ops(10),
                               "idle_gaps": trace.top_idle(10)}
    # JSON has no infinity: a number that could not be read (a missing or
    # infeasible answer) is reported as the largest float
    result["checks"] = {c.name: {"value": min(c.value, sys.float_info.max),
                                 "limit": c.limit} for c in checks}
    for c in checks:
        log(f"check {c.name}: {c.value!r} <= {c.limit!r} "
            f"{'ok' if c.ok else 'FAILED'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
