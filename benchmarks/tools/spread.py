#!/usr/bin/env python3
"""Medians and spreads of runs kept by ``chip_try.sh``: for each tag given,
every end-to-end metric's median and its spread as the contract defines it
(IQR over median, ``statistics.quantiles(n=4)``).

    python benchmarks/tools/spread.py chiprun_out/s1.gpt2-medium.train chiprun_out/s2...
"""
from __future__ import annotations

import glob
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import stats  # noqa: E402


def main() -> int:
    for tag in sys.argv[1:]:
        rows = []
        for path in sorted(glob.glob(tag + ".*.t0.out")):
            with open(path) as f:
                lines = [ln for ln in f if ln.startswith('{"correct"')]
            if lines:
                rows.append(json.loads(lines[-1]))
        print(f"{tag}: {len(rows)} runs, "
              f"{sum(r['correct'] for r in rows)} correct")
        for name in (rows[0]["metrics"] if rows else ()):
            values = [r["metrics"][name]["value"] for r in rows]
            print(f"  {name}: median {statistics.median(values):.6g} "
                  f"spread {stats.quartile_spread(values):.5f} {values}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
