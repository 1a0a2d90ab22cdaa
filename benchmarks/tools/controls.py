#!/usr/bin/env python3
"""Several controls on one served sample (PR 41): one run of a serving cell,
then the reference at each of the named lower precisions (or altered rules:
a reference names its own, as ``reference/kimi_linear.py``'s ``bf16_decay``,
``bf16_state`` and ``no_delta``) over the very requests the run checked.
For each control, two numbers over the served positions: ``gap``, what
``tools/readings.py --control`` reads (by how much the control's first
tokens trail the float32 reference's best logit, at worst: 0 where it moves
no token off the first place), and ``shift``, the widest distance of one of
its logits from the float32 reference's, which also sees a control too
gentle to move a token. One engine run and one float32 pass of the
reference serve them all.

    python benchmarks/tools/controls.py --workload <cell> --seconds <s> \\
        --seed <n> --controls bf16,fp8,bf16_state,no_delta
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402

from benchmarks.harness import check, device, serve, spec  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seconds", type=float, default=30.0)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--controls", required=True)
    args = parser.parse_args()
    controls = [c for c in args.controls.split(",") if c]

    cell = spec.load_cell(args.workload)
    if cell.kind != "serve":
        parser.error("a serving cell")
    device.configure_compile_cache()
    dev = device.require_chips(cell.chips)
    scored = serve.widest_gap_of_sample
    kept = {}

    def keeping(ref, params, n_heads, sample, pad_to, **kw):
        kept.update(args=(ref, params, n_heads, sample), pad_to=pad_to)
        return scored(ref, params, n_heads, sample, pad_to, **kw)

    serve.widest_gap_of_sample = keeping
    try:
        result = serve.run(cell, args.seed, args.seconds, False,
                           time.monotonic(), dev)
    finally:
        serve.widest_gap_of_sample = scored
    summary = {"workload": cell.name, "seed": args.seed,
               "correct": result["correct"],
               "program": {row["check"]: row["value"]
                           for row in result["checks"] if row["limit"] > 0},
               "controls": {}}
    ref, params, n_heads, sample = kept["args"]
    for control in controls:
        t0 = time.monotonic()
        gap = shift = 0.0
        for rec in sample:
            seq = list(rec.request.prompt) + list(rec.tokens)
            rows = slice(len(rec.request.prompt) - 1, len(seq) - 1)
            exact, low = (np.asarray(ref.teacher_forced_logits(
                params, seq, n_heads=n_heads, precision=precision,
                pad_to=kept["pad_to"]))[rows] for precision in ("f32",
                                                                control))
            gap = max(gap, check.widest_logit_gap(
                exact, low.argmax(axis=-1).tolist()))
            shift = max(shift, float(np.abs(low - exact).max()))
        summary["controls"][control] = {"gap": gap, "shift": shift}
        print(f"# control {control}: gap {gap} shift {shift} in "
              f"{time.monotonic() - t0:.1f} s", flush=True)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
