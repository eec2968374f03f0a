#!/usr/bin/env python3
"""Run the SCOPe planner's main path once on a TPU and check every result.

    python chip_smoke.py              # phases tpch + fleet on one chip
    python chip_smoke.py --chips 4    # the sharded paths on four chips,
                                      # each against the same work on one

Phase ``tpch`` generates a TPC-H lake (``SCALE_ROWS`` lineitem rows) and
the paper's 440-query log, fits a COMPREDICT model and runs
``PlacementEngine.run`` with the Pallas G-PART overlap kernel and the
Pallas entropy-feature kernel, then a capacitated solve, a drift
``reoptimize`` and one budget-capped daemon cycle. Phase ``fleet`` solves
256 ragged tenants in one batched dispatch against the per-tenant loop.
``--chips 4`` runs only the tenant-sharded fleet scan (T=1024, shared
capacity rows) and the row-sharded overlap matrix, each against one
device.

Every check prints one line; any failure raises, so the process exits
non-zero and prints no result. So does a host whose first JAX device is
not a TPU: nothing falls back to the CPU or to interpret mode. The last
line of a passing run is ``{"ok": true, "device": {...}}``.

The persistent compile cache lives in ``$JAX_COMPILATION_CACHE_DIR`` when
that is set, else in ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import compat  # noqa: E402
from repro.core import datapart  # noqa: E402
from repro.core.compredict import (CompressionPredictor,  # noqa: E402
                                   extract_features_batch, query_samples)
from repro.core.costs import azure_table  # noqa: E402
from repro.core.daemon import (MigrationBudget,  # noqa: E402
                               ReoptimizationDaemon)
from repro.core.engine import PlacementEngine, ScopeConfig  # noqa: E402
from repro.core.optassign import (capacitated_assign,  # noqa: E402
                                  capacitated_assign_batch,
                                  capacitated_assign_ref)
from repro.data import tpch  # noqa: E402
from repro.data.tables import encode_dtype_classes  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.entropy_features import (byte_entropy,  # noqa: E402
                                            weighted_entropy_features)
from repro.kernels.overlap import fractional_overlap_matrix  # noqa: E402
from repro.storage.codecs import available_schemes, codec_by_name  # noqa: E402

from benchmarks.bench_fleet import _fleet  # noqa: E402

# TPC-H SF 1 (6M lineitem rows). On one v5e host the host side dominates:
# string encoding, the partition tables and the numpy reference features.
SCALE_ROWS = 6_000_000
# The four-chip phase only needs enough query families to split the
# overlap matrix's rows four ways: SF 0.1 has 217.
SHARDED_SCALE_ROWS = 600_000
QUERIES_PER_TEMPLATE = 20      # x 22 templates = the paper's 440 queries
FIT_SAMPLES = 88               # query-result samples COMPREDICT learns from
FLEET_TENANTS, SHARDED_TENANTS, MEAN_N = 256, 1024, 24
FEATURE_RTOL = 1e-4

_compile = {"secs": 0.0, "hits": 0, "misses": 0}


def _on_event(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        _compile["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _compile["misses"] += 1


def _on_duration(event, secs, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _compile["secs"] += secs


def _setup_compile_cache() -> str:
    """Use ``$JAX_COMPILATION_CACHE_DIR`` when set (jax reads it itself),
    else the fixed ``<checkout>/.jax_cache``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    return path


class _Span:
    """Wall and compile seconds of one step, printed when it ends."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.c0 = _compile["secs"]
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.wall = time.perf_counter() - self.t0
        if exc[0] is None:
            print(f"time {self.name}: wall {self.wall:.3f} s, compile "
                  f"{_compile['secs'] - self.c0:.3f} s", flush=True)


def _timed_stage(name: str, fn):
    def run(*a, **kw):
        with _Span(f"stage {name}"):
            return fn(*a, **kw)
    return run


def check(name: str, ok: bool, detail: str) -> None:
    if not ok:
        raise AssertionError(f"check {name} FAILED: {detail}")
    print(f"check {name}: ok ({detail})", flush=True)


def _canonical(parts):
    return sorted((tuple(sorted(p.files)), p.rho) for p in parts)


def _tier_use(tier, stored_gb, n_tiers):
    return np.bincount(np.asarray(tier, int), weights=stored_gb,
                       minlength=n_tiers)


def _has_kernel(jitted, *args, **kw) -> bool:
    return "tpu_custom_call" in jitted.lower(*args, **kw).compile().as_text()


# ------------------------------------------------------------------ tpch
class _KeptFeatures(CompressionPredictor):
    """Keeps the tables, class codes and feature matrix of CompressStage's
    own ``predict_matrix`` call, so the checks read what the engine
    computed instead of a second pass."""

    def features(self, tables, layout, **kw):
        self.tables, self.sizes = tables, kw["sizes"]
        with _Span("encode_dtype_classes"):
            self.encoded = encode_dtype_classes(tables, kw.get("sources"))
        with _Span(f"features {kw['feature_backend']}"):
            self.X = super().features(tables, layout, encoded=self.encoded,
                                      **kw)
        return self.X


def phase_tpch(seed: int) -> None:
    print(f"phase tpch: scale_rows={SCALE_ROWS} (TPC-H SF "
          f"{SCALE_ROWS / 6_000_000:g})", flush=True)
    with _Span("generate+queries+families"):
        db = tpch.generate(scale_rows=SCALE_ROWS, seed=seed)
        queries = tpch.generate_queries(
            db, n_per_template=QUERIES_PER_TEMPLATE, seed=seed + 1)
        parts, file_rows = tpch.partitions_from_queries(db, queries)
    index = datapart.PartitionIndex.from_partitions(parts)
    codes, file_sizes, spans = index.padded_codes()
    print(f"shape queries={len(queries)} families={index.n} "
          f"files={index.n_files} overlap codes={codes.shape}", flush=True)

    schemes = available_schemes()
    with _Span("fit COMPREDICT"):
        samples = query_samples(queries, db.tables, max_rows=4000)
        step = max(len(samples) // FIT_SAMPLES, 1)
        samples = samples[::step][:FIT_SAMPLES]
        pred = _KeptFeatures(model_name="SVR").fit(
            samples, layouts=("col",),
            codecs=[codec_by_name(s) for s in schemes if s != "none"])
    table = azure_table()
    cfg = ScopeConfig(schemes=schemes, tier_whitelist=(0, 1, 2),
                      predictor=pred, partition_backend="pallas",
                      feature_backend="pallas")
    engine = PlacementEngine(table, cfg)
    for stage in ("partition", "compress", "assign", "billing"):
        setattr(engine, stage, _timed_stage(stage, getattr(engine, stage)))
    with _Span("PlacementEngine.run"):
        plan0 = engine.run(parts, file_rows)
    problem = plan0.problem
    print(f"shape partitions={problem.n} schemes={len(schemes)} "
          f"tiers={table.num_tiers}", flush=True)

    # G-PART through the overlap kernel == the exact numpy candidate join
    med = float(np.median([p.span for p in parts]))
    with _Span("reference g_part numpy"):
        ref_parts = datapart.g_part(parts, s_thresh=cfg.s_thresh_mult * med,
                                    rho_c=cfg.rho_c, rho_c_abs=cfg.rho_c_abs,
                                    backend="numpy")
    check("gpart_identical_to_numpy",
          _canonical(problem.partitions) == _canonical(ref_parts),
          f"{len(ref_parts)} partitions from {index.n} families")
    check("overlap_is_compiled_kernel",
          _has_kernel(fractional_overlap_matrix, codes, file_sizes, spans),
          "tpu_custom_call in compiled HLO")

    # the engine's entropy features (the kernel) vs the numpy feature loop
    for d, cc in pred.encoded.items():
        print(f"shape class={d} codes={cc.codes.shape} "
              f"vocab={cc.lengths.shape[1]}", flush=True)
    X_dev = pred.X
    with _Span("features numpy reference"):
        X_np = extract_features_batch(pred.tables, "col", pred.feature_kind,
                                      "numpy", sizes=pred.sizes)
    big = np.abs(X_np) > 1e-6
    rel = float((np.abs(X_dev - X_np)[big] / np.abs(X_np)[big]).max())
    check("features_match_numpy",
          np.allclose(X_dev, X_np, rtol=FEATURE_RTOL, atol=1e-6),
          f"max relative error {rel:.3e} <= {FEATURE_RTOL:g}, "
          f"X {X_dev.shape}")
    cc = max(pred.encoded.values(), key=lambda c: c.codes.size)
    check("entropy_is_compiled_kernel",
          _has_kernel(weighted_entropy_features, cc.codes, cc.n_valid,
                      cc.n_rows, cc.n_cols, cc.lengths),
          f"tpu_custom_call in compiled HLO, codes {cc.codes.shape}")
    payload = np.frombuffer(max(problem.raw_bytes, key=len)[:1 << 20],
                            np.uint8)
    hist, ent = byte_entropy(payload)
    counts = np.bincount(payload, minlength=256)
    p = counts[counts > 0] / payload.size
    ent_np = float(-(p * np.log2(p)).sum())
    check("byte_entropy_matches_numpy",
          np.array_equal(np.asarray(hist), counts)
          and abs(float(ent) - ent_np) <= 1e-5 * ent_np,
          f"{payload.size} bytes, {float(ent):.6f} vs {ent_np:.6f} bits/B")

    # capacitated solve: cap the hottest tier below its greedy usage
    L = table.num_tiers
    use0 = _tier_use(plan0.assignment.tier, plan0.stored_gb, L)
    hot = int(use0.argmax())
    cap = np.full(L, np.inf)
    cap[hot] = 0.8 * use0[hot]
    cfg_c = dataclasses.replace(cfg, capacity_gb=cap)
    engine_c = PlacementEngine(table, cfg_c)
    problem_c = dataclasses.replace(problem, cfg=cfg_c)
    with _Span("capacitated solve"):
        plan = engine_c.solve(problem_c)
    use = _tier_use(plan.assignment.tier, plan.stored_gb, L)
    check("assignment_feasible_under_caps",
          plan.assignment.feasible and bool((use <= cap + 1e-9).all()),
          f"tier {hot} cap {cap[hot]:.6g} GB, use {use[hot]:.6g} GB")
    cost, feas, stored, capv, _, _ = engine_c.assign.solver_inputs(problem_c)
    with _Span("reference capacitated_assign_ref"):
        ref = capacitated_assign_ref(cost, feas, stored, capv)
    check("assignment_no_worse_than_ref",
          plan.assignment.cost <= ref.cost + 1e-9 * abs(ref.cost),
          f"cost {plan.assignment.cost:.9g} vs ref {ref.cost:.9g}")

    # drift re-optimization and one budget-capped daemon cycle
    # access rates permuted across partitions: hot data cools, cold warms
    rng = np.random.default_rng(seed + 2)
    drifted = problem.rho[rng.permutation(problem.n)]
    with _Span("reoptimize"):
        mig = engine_c.reoptimize(plan, drifted, months_held=1.0)
    use_m = _tier_use(mig.plan.assignment.tier, mig.plan.stored_gb, L)
    check("reoptimize_feasible_under_caps",
          mig.plan.assignment.feasible and bool((use_m <= cap + 1e-9).all()),
          f"{mig.n_candidates} candidate moves, "
          f"{mig.total_move_cents:.6g} cents")
    budget = MigrationBudget(cents_per_cycle=0.5 * mig.total_move_cents)
    daemon = ReoptimizationDaemon(engine_c, plan=plan, budget=budget)
    with _Span("daemon cycle"):
        rep = daemon.step(drifted, months=1.0)
    check("daemon_spend_within_cap",
          rep.spent_cents <= budget.cents_per_cycle + 1e-9,
          f"spent {rep.spent_cents:.6g} of {budget.cents_per_cycle:.6g} "
          f"cents, {rep.n_selected} of {rep.n_candidates} moves")


# ----------------------------------------------------------------- fleet
def phase_fleet(seed: int) -> None:
    T = FLEET_TENANTS
    print(f"phase fleet: T={T} tenants, mean N={MEAN_N}", flush=True)
    fleet = _fleet(T, MEAN_N, seed=seed + T)
    with _Span("per-tenant capacitated_assign loop"):
        singles = [capacitated_assign(c, f, s, cap) for c, f, s, cap in fleet]
    with _Span("capacitated_assign_batch"):
        batch = capacitated_assign_batch(*map(list, zip(*fleet)))
    same = all(np.array_equal(a.tier, b.tier)
               and np.array_equal(a.scheme, b.scheme) and a.cost == b.cost
               for a, b in zip(singles, batch.assignments))
    check("fleet_equals_per_tenant_loop", same,
          f"{T} tenants: tier, scheme and cost identical")


# ------------------------------------------------------------- four chips
def _shared_fleet(seed: int):
    """bench_fleet tenants (each with a binding cap of its own) plus one
    fleet-wide cap at 70% of the fleet's greedy use of its busiest tier.
    Returns the tenants, the shared caps and that greedy use."""
    fleet = _fleet(SHARDED_TENANTS, MEAN_N, seed=seed)
    L, K = fleet[0][2].shape[1:]
    use = np.zeros(L)
    for c, f, s, _ in fleet:
        cell = np.where(f, c, np.inf).reshape(c.shape[0], -1).argmin(1)
        use += _tier_use(cell // K, s.reshape(s.shape[0], -1)[
            np.arange(c.shape[0]), cell], L)
    scap = np.full(L, np.inf)
    busiest = int(use.argmax())
    scap[busiest] = 0.7 * use[busiest]
    return fleet, scap, use


def _peak_bytes(devices):
    return [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devices]


def phase_sharded(seed: int) -> None:
    devices = jax.devices()
    mesh = compat.make_mesh((len(devices),), ("tenants",))
    print(f"phase sharded: mesh {dict(mesh.shape)}", flush=True)
    before = _peak_bytes(devices)

    fleet, scap, greedy_use = _shared_fleet(seed + SHARDED_TENANTS)
    L = scap.shape[0]
    t = int(np.isfinite(scap).argmax())
    args = [list(x) for x in zip(*fleet)]
    kw = dict(shared_tier_groups=np.arange(L), shared_capacity_gb=scap)
    with _Span(f"fleet scan sharded over {len(devices)} devices"):
        sharded = capacitated_assign_batch(*args, mesh=mesh, **kw)
    after = _peak_bytes(devices)
    with _Span("fleet scan on one device"):
        single = capacitated_assign_batch(*args, **kw)
    same = all(np.array_equal(a.tier, b.tier)
               and np.array_equal(a.scheme, b.scheme) and a.cost == b.cost
               for a, b in zip(sharded.assignments, single.assignments))
    check("sharded_fleet_equals_one_device",
          same and sharded.feasible and single.feasible
          and sharded.cost == single.cost,
          f"T={SHARDED_TENANTS}, shared cap on tier {t}: greedy use "
          f"{greedy_use[t]:.6g} GB, cap {scap[t]:.6g} GB, solved use "
          f"{sharded.shared_use_gb[t]:.6g} GB")
    check("sharded_fleet_used_every_device",
          all(a > b for a, b in zip(after, before)),
          f"peak bytes per device {before} -> {after}")

    db = tpch.generate(scale_rows=SHARDED_SCALE_ROWS, seed=seed)
    parts, _ = tpch.partitions_from_queries(
        db, tpch.generate_queries(db, n_per_template=QUERIES_PER_TEMPLATE,
                                  seed=seed + 1))
    index = datapart.PartitionIndex.from_partitions(parts)
    codes, sizes, spans = index.padded_codes()
    with _Span(f"overlap matrix sharded over {len(devices)} devices"):
        w_sh = datapart._overlap_matrix_sharded(codes, sizes, spans, mesh,
                                                impl="pallas")
        w_sh.block_until_ready()
    with _Span("overlap matrix on one device"):
        w_one = np.asarray(ops.fractional_overlap_matrix(codes, sizes, spans,
                                                         impl="pallas"))
    n = index.n
    diff = float(np.abs(np.asarray(w_sh)[:n, :n] - w_one).max())
    check("sharded_overlap_equals_one_device", diff == 0.0,
          f"({n}, {n}) from codes {codes.shape}, max |diff| {diff}")
    spread = {d.id for d in w_sh.sharding.device_set}
    check("sharded_overlap_spans_devices", len(spread) == len(devices),
          f"output on devices {sorted(spread)}")
    med = float(np.median([p.span for p in parts]))
    gp = dict(s_thresh=3.0 * med, rho_c=4.0, rho_c_abs=10.0)
    with _Span("g_part with the sharded matrix"):
        merged = datapart.g_part(parts, backend="pallas", mesh=mesh, **gp)
    check("sharded_gpart_identical_to_numpy",
          _canonical(merged) == _canonical(
              datapart.g_part(parts, backend="numpy", **gp)),
          f"{len(merged)} partitions from {index.n} families")


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    cache = _setup_compile_cache()
    print(f"device {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
          f"compile cache {cache}", flush=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(args.seed)
    else:
        phase_tpch(args.seed)
        phase_fleet(args.seed)
    print(f"compile cache: {_compile['hits']} hits, {_compile['misses']} "
          f"misses, {_compile['secs']:.1f} s compiling; total wall "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
