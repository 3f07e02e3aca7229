#!/usr/bin/env python3
"""Readings that a cell's limits are set from: the program, and the control.

    python3 perfbench/calibrate.py --workload triad.hbm \\
        --seeds 101,102,...,112 --control-seeds 201,202,203 --seconds 2

One process on the chip, at the cell's own size: for each program seed
it builds the cell, runs a short window at the cell's own load and
checks what the timed calls produced; for each control seed it checks
the reference computed in bfloat16 in the program's place. One JSON line
per seed, then the largest program reading and the smallest control
reading of each number compared. The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def readings(workload: str, seeds, control_seeds, seconds: float, *,
             root=ROOT, require_tpu: bool = True) -> dict:
    from perfbench import harness
    from perfbench.compare import worst_of

    root = pathlib.Path(root)
    bench = harness.load_json(root / "BENCHMARK.json")
    cell_entry, cfg_entry = harness.find_cell(bench, workload)
    cfg = harness.load_json(root / cfg_entry["file"])
    ref = harness.load_module((root / cfg_entry["file"]).with_suffix(".py"))
    traffic = harness.load_json(
        root / harness.HERE / "traffic" / f"{cell_entry['traffic']}.json")
    entry = harness.load_module(
        root / harness.HERE / "entries" / f"{cfg['entry']}.py")
    harness.prepare_program(root)
    if require_tpu:
        harness.require_chips(int(cell_entry["chips"]))
    summary = {"program": {}, "control": {}}
    for kind, seed_list in (("program", seeds), ("control", control_seeds)):
        for seed in seed_list:
            cell = entry.build(cfg, ref, traffic, seed)
            if kind == "program":
                harness.warm_up(cell.calls)
                harness.run_passes(cell.calls, int(traffic["reps"]), seconds)
            checks, failed = cell.check(use_control=(kind == "control"))
            del cell
            gc.collect()
            row = {"workload": workload, "kind": kind, "seed": seed,
                   "failed_rungs": failed,
                   "check": {n: v for n, v, _ in checks}}
            print(json.dumps(row), flush=True)
            agg = summary[kind]
            for n, v, _ in checks:
                if n not in agg:
                    agg[n] = v
                elif kind == "program":
                    agg[n] = worst_of(agg[n], v)
                else:
                    agg[n] = min(agg[n], v)
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    seeds = [int(s) for s in args.seeds.split(",")]
    control = [int(s) for s in args.control_seeds.split(",")]
    summary = readings(args.workload, seeds, control, args.seconds)
    print(json.dumps({"workload": args.workload, "lower": summary["program"],
                      "upper": summary["control"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
