#!/usr/bin/env python3
"""Read the compared numbers of the program and of its control, per seed.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...]

For each seed the cell is built as a run builds it, every pooled request
is served once by the program and once by the control (the reference in
the program's place, one precision down: see each cell's ``control``),
and both are read against the reference exactly as a run's check reads
its answers. One JSON line per seed: ``{"seed", "program", "control"}``.
The program's worst readings over many seeds set a limit's lower end and
the control's least readings its upper end. The benchmark's own runs do
not run this; it needs the chip, as a run does.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402


def readings(workload: str, seed: int, shrink=None) -> dict:
    """``{"program": {...}, "control": {...}}``: the worst of each number
    over the pool's requests."""
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    wl = {w["name"]: w for w in spec["workloads"]}[workload]
    conf = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    config = json.loads((run.ROOT / conf["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{wl['config']}.{wl['traffic']}"
                      ".json").read_text())
    if shrink is not None:
        shrink(config, mix)
    mod = run.load_module(BENCH / "configs" / f"{wl['config']}.py")
    cell = mod.build(config, mix, seed, run.Recorder())
    served = [(j, cell.serve(cell.pool[j])) for j in range(len(cell.pool))]
    controls = [(j, cell.control(j, a)) for j, a in served]
    out = {}
    for side, answers in (("program", served), ("control", controls)):
        out[side] = {name: value for name, value, _ in cell.check(answers)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 1
    jax.config.update("jax_compilation_cache_dir", str(run.CACHE_DIR))
    for seed in args.seeds:
        print(json.dumps(dict(seed=seed, workload=args.workload,
                              **readings(args.workload, seed))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
