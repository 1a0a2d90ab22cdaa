#!/usr/bin/env python3
"""Chaos conductor CLI for the self-healing serving fleet.

Drives the seeded scenario catalog in serving/chaos.py — kill -9
mid-decode, wedged scheduler, supervisor+replica double fault, poison
pill, KV-warm failover, deadline storm — and reports the invariant
audit for each: zero lost accepted requests, bit-identical recovered
outputs, zero leaked KV blocks, bounded MTTR. Exit 0 iff every scenario
passed (docs/serving.md "Self-healing" for the catalog).

Usage:
    python tools/chaosfleet.py --list
    python tools/chaosfleet.py                       # the full catalog
    python tools/chaosfleet.py --scenario kill_replica_mid_decode
    python tools/chaosfleet.py --seed 7 --json
    python tools/chaosfleet.py --selftest            # tier-1 smoke

Importable: ``main(argv) -> int`` (tests/test_self_healing.py calls it);
``run_scenarios()`` in serving/chaos.py for in-process use.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# the conductor is a CPU tool: force the host platform before jax loads
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _print_result(res) -> None:
    mark = "PASS" if res.passed else "FAIL"
    print(f"[{mark}] {res.scenario} "
          f"(seed={res.seed} {res.duration_s:.1f}s "
          f"mttr_max={res.mttr_max_s:.2f}s)")
    for c in res.checks:
        flag = "ok  " if c.ok else "FAIL"
        line = f"    {flag} {c.name}"
        if c.detail and not c.ok:
            line += f": {c.detail}"
        print(line)


def main(argv=None) -> int:
    from determined_clone_tpu.serving.chaos import SCENARIOS, run_scenarios

    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--scenario", action="append", default=None,
                        help="scenario name (repeatable; default: all)")
    parser.add_argument("--seed", type=int, default=0,
                        help="FaultPlan + workload seed")
    parser.add_argument("--requests", type=int, default=6,
                        help="concurrent requests per scenario workload")
    parser.add_argument("--mttr-budget", type=float, default=30.0,
                        help="max seconds a replica replacement may take")
    parser.add_argument("--json", action="store_true",
                        help="machine-readable results on stdout")
    parser.add_argument("--list", action="store_true",
                        help="print the scenario catalog and exit")
    parser.add_argument("--selftest", action="store_true",
                        help="tier-1 smoke: the acceptance scenario "
                             "(kill -9 mid-decode) with a small workload")
    args = parser.parse_args(argv)

    if args.list:
        for name, fn in SCENARIOS.items():
            doc = (fn.__doc__ or "").strip().splitlines()[0]
            print(f"{name:28s} {doc}")
        return 0

    names = args.scenario
    requests = args.requests
    if args.selftest:
        names = ["kill_replica_mid_decode"]
        requests = min(requests, 4)

    try:
        results = run_scenarios(names, seed=args.seed,
                                mttr_budget_s=args.mttr_budget,
                                requests=requests)
    except KeyError as exc:
        print(f"error: {exc.args[0]}", file=sys.stderr)
        return 2

    if args.json:
        print(json.dumps([r.to_dict() for r in results], indent=2))
    else:
        for res in results:
            _print_result(res)
        n_pass = sum(r.passed for r in results)
        print(f"{n_pass}/{len(results)} scenarios passed")
    return 0 if all(r.passed for r in results) else 1


if __name__ == "__main__":
    sys.exit(main())
