#!/usr/bin/env python3
"""The readings a cell's limits are set from (PERF.md, section 2): the
program's compared numbers on many seeds and the control's on a few, in one
process on the cell's chips.

    python benchmarks/tools/readings.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3,... --control-seeds 1,2,3 [--control fp8]

The control is the reference itself computed in the nearest precision below
the one the configuration states (fp8 operands for bf16 compute), compared
with the reference proper exactly as the program is. Training's readings need
no window (``--seconds 0``); serving's need one long enough to finish the
mix's longest requests.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import device, spec  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seconds", type=float, default=0.0)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--control", default="fp8")
    args = parser.parse_args()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}

    cell = spec.load_cell(args.workload)
    device.configure_compile_cache()
    dev = device.require_chips(cell.chips)
    driver = importlib.import_module(f"benchmarks.harness.{cell.kind}")
    program, control = {}, {}
    for seed in seeds:
        t0 = time.monotonic()
        result = driver.run(
            cell, seed, args.seconds, False, time.monotonic(), dev,
            control=args.control if seed in control_seeds else None)
        for row in result["checks"]:
            if row["limit"] > 0:
                program.setdefault(row["check"], {})[seed] = row["value"]
        for name, value in (result["control"] or {}).items():
            control.setdefault(name, {})[seed] = value
        print(f"# seed {seed}: {time.monotonic() - t0:.1f} s", flush=True)
        del result
        gc.collect()
    summary = {"workload": cell.name, "control_precision": args.control}
    for name, by_seed in program.items():
        summary[name] = {
            "program": by_seed, "program_largest": max(by_seed.values()),
            "control": control.get(name, {}),
            "control_smallest": min(control[name].values())
            if control.get(name) else None}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
