#!/usr/bin/env python3
"""What ``harness/scopes.py`` reads in the trace a cell's last ``--trace 1``
run left under ``benchmarks/.trace/<cell>/``: device seconds per scope and
part per step, the largest unscoped operations, how much of the step the
named buckets and the pool copies cover, and the host's idle share of a
decode step. One JSON line; ``{}`` fields are null where the trace holds
nothing to read (a parent commit's trace has no scopes or annotations).

    python benchmarks/tools/scopes_report.py <cell>
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import scopes, spec  # noqa: E402


def main() -> int:
    cell = spec.load_cell(sys.argv[1])
    out = {"cell": cell.name, "step": scopes.STEP_SPAN[cell.kind],
           "scopes": None, "decode_host": None}
    path = scopes.trace_path(cell.name)
    if path is not None:
        parsed = scopes.load(path)
        r = scopes.reduce_scopes(parsed, out["step"],
                                 scopes.pool_shapes(cell.config))
        if r is not None:
            named = sum(v for k, v in r["buckets"].items()
                        if k != scopes.UNSCOPED)
            r["buckets_sum_s"] = sum(r["buckets"].values())
            r["named_share"] = named / r["busy_s"]
            r["named_and_pool_copy_share"] = (
                named + r["parts"]["pool_copy"]) / r["busy_s"]
            out["scopes"] = r
        if cell.kind == "serve":
            out["decode_host"] = scopes.decode_host_idle(parsed)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
