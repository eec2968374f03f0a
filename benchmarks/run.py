"""Benchmark entry point — one module per paper table/figure + kernel
microbenches. Prints ``name,us_per_call,derived`` CSV.

    PYTHONPATH=src python -m benchmarks.run [--only tableII,fig7,...]
"""

import argparse
import sys
import time

MODULES = [
    ("tableII", "benchmarks.bench_optassign_enterprise"),
    ("tableIII", "benchmarks.bench_access_predict"),
    ("tableIV", "benchmarks.bench_optassign_baselines"),
    ("tablesV-VIII", "benchmarks.bench_compredict"),
    ("features", "benchmarks.bench_feature_backends"),
    ("fig7", "benchmarks.bench_gpart"),
    ("gpart_scale", "benchmarks.bench_gpart_scale"),
    ("tablesIX-XI", "benchmarks.bench_scope_pipeline"),
    ("reopt", "benchmarks.bench_reoptimize"),
    ("stream", "benchmarks.bench_stream"),
    ("daemon", "benchmarks.bench_daemon"),
    ("multicloud", "benchmarks.bench_multicloud"),
    ("fleet", "benchmarks.bench_fleet"),
    ("migrator", "benchmarks.bench_migrator"),
    ("forecast", "benchmarks.bench_forecast"),
    ("sla", "benchmarks.bench_sla"),
    ("kernels", "benchmarks.bench_kernels"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated tags (e.g. tableII,fig7)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    if only:
        valid = [tag for tag, _ in MODULES]
        unknown = sorted(only - set(valid))
        if unknown:
            print(f"unknown benchmark tag(s) {unknown}; "
                  f"valid tags: {', '.join(valid)}", file=sys.stderr)
            sys.exit(2)
    print("name,us_per_call,derived")
    t0 = time.time()
    failures = []
    for tag, modname in MODULES:
        if only and tag not in only:
            continue
        try:
            mod = __import__(modname, fromlist=["run"])
            mod.run()
        except Exception as e:  # noqa: BLE001 — keep the suite running
            failures.append((tag, repr(e)))
            print(f"{tag}/FAILED,0,{{\"error\": \"{e}\"}}")
    print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)
    if failures:
        print(f"# FAILURES: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
