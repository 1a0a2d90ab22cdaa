#!/usr/bin/env python3
"""One run of one cell of the benchmark (see ``benchmarks/README.md``):

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the cell's chips. It exits non-zero, and prints no
result line, when JAX reports no TPU, another number of chips than the cell
asks for, or a device kind that the benchmark's peak table lacks. Otherwise
the last line of its output is the result: ``correct``, ``attempted``,
``failed``, ``metrics`` (``--trace 0``: end-to-end; ``--trace 1``:
per-layer), ``device``, traced ``breakdown``, and last ``checks``: each
number that decided ``correct`` beside its limit (also the last lines of
standard error).
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()  # set-up counts from here, imports included

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import device, spec  # noqa: E402


def units_of(manifest: dict) -> dict:
    return {m["name"]: m["unit"]
            for m in manifest["end_to_end"] + manifest["per_layer"]}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if args.seed < 0:
        parser.error("--seed must not be negative")

    with open(spec.MANIFEST) as f:
        manifest = json.load(f)
    cell = spec.load_cell(args.workload, manifest=manifest)
    cache_dir = device.configure_compile_cache()
    dev = device.require_chips(cell.chips)  # exits where there is no chip
    print(f"# {cell.name}: seed {args.seed}, {args.seconds} s, trace "
          f"{args.trace}, compile cache {cache_dir}", flush=True)

    driver = importlib.import_module(f"benchmarks.harness.{cell.kind}")
    result = driver.run(cell, args.seed, args.seconds, bool(args.trace),
                        T_PROCESS, dev)

    units = units_of(manifest)
    if args.trace:
        ctx = result["layer_context"]
        values = {}
        for name in cell.per_layer:
            value = spec.load_module("layer_metrics", name).read(ctx)
            if value is not None:  # nothing to read: left out of the line
                values[name] = value
        tr = ctx["trace"]
        dev["busy_s"], dev["window_s"] = tr["busy_s"], tr["window_s"]
    else:
        values = {name: result["values"].get(name)
                  for name in cell.end_to_end}
        missing = [k for k, v in values.items() if v is None]
        if missing:
            print(f"benchmark: no value for {missing}", file=sys.stderr)
            return 1
    import jax

    print(f"# memory_stats of chip 0: {jax.devices()[0].memory_stats()}",
          flush=True)
    dev["memory_peak_bytes"] = result["memory_peak_bytes"]
    line = {
        "correct": result["correct"], "attempted": result["attempted"],
        "failed": result["failed"],
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in values.items()},
        "device": dev,
    }
    if args.trace:
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    # every number that decided ``correct`` beside its limit: last in the
    # line and last on standard error, which is what the driver keeps of a
    # run that is not correct
    line["checks"] = {
        row["check"]: {"value": row["value"] if math.isfinite(row["value"])
                       else str(row["value"]),  # NaN is not JSON
                       "limit": row["limit"]}
        for row in result["checks"]}
    for name, row in line["checks"].items():
        print(f"check {name}: {row['value']} (limit {row['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
