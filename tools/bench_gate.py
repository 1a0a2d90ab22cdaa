#!/usr/bin/env python3
"""Bench regression gate — compare two BENCH rounds.

First enforcement of ROADMAP item 5's "every perf PR must move MFU or
tokens/sec": given the previous and the new bench result, fail (exit 1)
when

- the new round's throughput (samples/sec/chip) dropped more than the
  tolerance (default -5%) against the old round on the *same platform*
  (platform changed, e.g. TPU came back → throughput compare is skipped
  with a warning, not failed: cross-platform numbers are incomparable);
- the new round has a null ``mfu`` — the analytic FLOPs engine makes the
  field unconditional, so null means the accounting regressed.

Accepts either the raw bench.py JSON line or the driver's ``BENCH_rN.json``
wrapper ({"n", "cmd", "rc", "tail"}), where the result is the last JSON
object with a "metric" key inside ``tail``.

Usage:
    python tools/bench_gate.py OLD.json NEW.json [--tolerance -0.05]
    python tools/bench_gate.py            # two newest BENCH_r*.json in cwd
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Any, Dict, Optional, Tuple

DEFAULT_TOLERANCE = -0.05


def _last_metric_line(text: str) -> Optional[Dict[str, Any]]:
    result = None
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "metric" in obj:
            result = obj
    return result


def load_bench(path: str) -> Dict[str, Any]:
    with open(path) as f:
        obj = json.load(f)
    if isinstance(obj, dict) and "metric" in obj:
        return obj
    if isinstance(obj, dict) and "tail" in obj:
        inner = _last_metric_line(str(obj["tail"]))
        if inner is not None:
            return inner
        raise ValueError(f"{path}: wrapper 'tail' holds no bench result line")
    raise ValueError(f"{path}: neither a bench result nor a BENCH_rN wrapper")


def newest_rounds(directory: str = ".") -> Tuple[str, str]:
    rounds = []
    for path in glob.glob(os.path.join(directory, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if m:
            rounds.append((int(m.group(1)), path))
    rounds.sort()
    if len(rounds) < 2:
        raise ValueError(
            f"need two BENCH_r*.json rounds in {directory!r}, "
            f"found {len(rounds)}")
    return rounds[-2][1], rounds[-1][1]


# Optional detail sections that come and go with the environment (time
# budget, master build availability). A round missing one that the
# previous round carried is a skip-with-note, never a gate failure — the
# headline throughput/mfu checks below are the contract.
OPTIONAL_SECTIONS = ("control_plane", "checkpoint_io", "pipeline",
                     "mnist_cnn", "xla", "goodput",
                     "serving", "serving_fleet", "exec_cache", "multichip",
                     "tsdb", "recovery", "kv_hierarchy")


def _section_notes(old_detail: Dict[str, Any], new_detail: Dict[str, Any],
                   report: list) -> None:
    for name in OPTIONAL_SECTIONS:
        if old_detail.get(name) is not None and new_detail.get(name) is None:
            report.append(
                f"note: section {name!r} present in the previous round is "
                f"missing in the new one; compare skipped")


def _control_plane_lines(old_detail: Dict[str, Any],
                         new_detail: Dict[str, Any], report: list) -> None:
    """Advisory control-plane reporting (tools/loadgen.py section): the
    numbers land in the report so regressions are visible in BENCH
    history, but only a round that errored where the previous one
    succeeded warrants a WARN — the synthetic load shares the box with
    the bench itself, so absolute latency is too noisy to hard-gate."""
    cp_new = new_detail.get("control_plane")
    if not isinstance(cp_new, dict):
        return
    if cp_new.get("error"):
        report.append(f"WARN: control_plane errored: {cp_new['error']}")
        return
    s2r = cp_new.get("submit_to_running_s") or {}

    def _f(v: Any) -> str:
        return f"{v:.3f}" if isinstance(v, (int, float)) else "null"

    report.append(
        f"ok: control_plane {cp_new.get('completed')}/{cp_new.get('trials')} "
        f"trials: {cp_new.get('submits_per_sec')} submits/s, "
        f"{cp_new.get('decisions_per_sec')} decisions/s, "
        f"submit→running p50={_f(s2r.get('p50'))}s p99={_f(s2r.get('p99'))}s, "
        f"peak queue {cp_new.get('peak_queue_depth')}")
    cp_old = old_detail.get("control_plane")
    if (isinstance(cp_old, dict) and not cp_old.get("error")
            and isinstance(s2r.get("p99"), (int, float))):
        old_p99 = (cp_old.get("submit_to_running_s") or {}).get("p99")
        if isinstance(old_p99, (int, float)) and old_p99 > 0 \
                and s2r["p99"] > 2.0 * old_p99:
            report.append(
                f"WARN: control_plane submit→running p99 "
                f"{old_p99:.3f}s → {s2r['p99']:.3f}s (>2x)")


def _xla_lines(old_detail: Dict[str, Any],
               new_detail: Dict[str, Any], report: list) -> None:
    """Advisory XLA-section reporting: compile time and measured MFU land
    in the report so drift is visible in BENCH history, with WARNs on a
    compile-time blowup (>2x — what ROADMAP item 4's executable cache is
    meant to erase) or a measured-MFU drop beyond the throughput
    tolerance. Advisory-only: compile time shares the box with everything
    else, and a fingerprint change legitimately resets both numbers."""
    xla_new = new_detail.get("xla")
    if not isinstance(xla_new, dict):
        return
    ct = xla_new.get("compile_time_s")
    mm = xla_new.get("measured_mfu")
    fp = xla_new.get("fingerprint")
    report.append(
        f"ok: xla compile={ct}s measured_mfu={mm} "
        f"program={fp or '?'} peak_mem={xla_new.get('peak_memory_bytes')}")
    xla_old = old_detail.get("xla")
    if not isinstance(xla_old, dict):
        return
    same_program = fp and xla_old.get("fingerprint") == fp
    old_ct = xla_old.get("compile_time_s")
    if (isinstance(old_ct, (int, float)) and old_ct > 0
            and isinstance(ct, (int, float)) and ct > 2.0 * old_ct):
        note = "" if same_program else " (program fingerprint changed)"
        report.append(
            f"WARN: xla compile time {old_ct:.3f}s → {ct:.3f}s (>2x){note}")
    old_mm = xla_old.get("measured_mfu")
    if (same_program and isinstance(old_mm, (int, float)) and old_mm > 0
            and isinstance(mm, (int, float))
            and mm / old_mm - 1.0 < DEFAULT_TOLERANCE):
        report.append(
            f"WARN: measured MFU {old_mm:.6f} → {mm:.6f} on the same "
            f"program fingerprint ({mm / old_mm - 1.0:+.1%})")


def _goodput_lines(old_detail: Dict[str, Any],
                   new_detail: Dict[str, Any], report: list) -> None:
    """Advisory goodput-section reporting (telemetry/goodput.py, measured
    on a real trainer mini-run inside bench): the fraction lands in the
    report so badput drift is visible in BENCH history. WARNs when the
    section errored, when the conservation invariant broke (the ledger
    over-counted — a wiring bug, not an environment mood), when the
    fraction is null, or when it dropped more than 10 points against the
    previous round. Advisory-only: the mini-run shares the box with the
    bench ladder, so absolute goodput is noisy; the enforced contract is
    the tier-1 conservation test."""
    gp_new = new_detail.get("goodput")
    if not isinstance(gp_new, dict):
        return
    if gp_new.get("error"):
        report.append(f"WARN: goodput errored: {gp_new['error']}")
        return
    frac = gp_new.get("goodput_fraction")
    if not gp_new.get("conservation_ok", False):
        report.append(
            "WARN: goodput conservation violated "
            f"(error_fraction={gp_new.get('conservation_error_fraction')})")
    if not isinstance(frac, (int, float)):
        report.append("WARN: goodput_fraction is null")
        return
    cats = gp_new.get("categories") or {}
    badput = sorted(((c, s) for c, s in cats.items()
                     if c != "productive" and isinstance(s, (int, float))),
                    key=lambda kv: -kv[1])[:2]
    bad_s = " ".join(f"{c}={s:.2f}s" for c, s in badput)
    report.append(
        f"ok: goodput fraction={frac:.4f} over {gp_new.get('wall_s')}s "
        f"(top badput: {bad_s or 'none'})")
    gp_old = old_detail.get("goodput")
    if isinstance(gp_old, dict):
        old_frac = gp_old.get("goodput_fraction")
        if (isinstance(old_frac, (int, float))
                and frac < old_frac - 0.10):
            report.append(
                f"WARN: goodput fraction {old_frac:.4f} → {frac:.4f} "
                f"(dropped more than 10 points)")


def _serving_lines(old_detail: Dict[str, Any],
                   new_detail: Dict[str, Any], report: list) -> None:
    """Advisory serving-section reporting (serving/engine.py measured by
    bench's latency-vs-load sweep): tokens/sec and p50/p99 at the highest
    offered load land in the report, with WARNs when the section errored,
    when continuous batching stopped beating the static run-to-completion
    baseline (continuous_over_static < 1 — the whole point of the
    scheduler), or when tokens/sec dropped / p99 grew more than 10%
    against the previous round at the same offered load. Advisory-only:
    the tiny-model sweep shares the box with the bench ladder; the
    enforced contracts are the tier-1 parity and compile-discipline
    tests."""
    sv_new = new_detail.get("serving")
    if not isinstance(sv_new, dict):
        return
    if sv_new.get("error"):
        report.append(f"WARN: serving errored: {sv_new['error']}")
        return
    points = [p for p in (sv_new.get("load_points") or [])
              if isinstance(p, dict)]
    if not points:
        report.append("WARN: serving section has no load points")
        return
    top = points[-1]
    report.append(
        f"ok: serving {len(points)} load points, top "
        f"{top.get('offered_rps')} req/s: {top.get('tokens_per_sec')} tok/s, "
        f"p50={top.get('p50_total_s')}s p99={top.get('p99_total_s')}s, "
        f"programs {sv_new.get('programs_compiled')}/"
        f"{sv_new.get('program_budget')}")
    ratio = sv_new.get("continuous_over_static")
    if isinstance(ratio, (int, float)) and ratio < 1.0:
        report.append(
            f"WARN: continuous batching no longer beats static "
            f"run-to-completion (continuous_over_static={ratio})")
    # observability lane (docs/observability.md "Request tracing & SLOs"):
    # per-request tracing must stay near-free at top load, and the round's
    # simulated-clock SLO verdict must not be burning its fast windows
    overhead = sv_new.get("tracing_overhead")
    if not isinstance(overhead, (int, float)):
        report.append("WARN: tracing_overhead is null — the traced/"
                      "untraced A/B did not run")
    elif overhead > 0.02:
        report.append(
            f"WARN: tracing overhead {overhead:.1%} > 2% at top load "
            f"({top.get('tokens_per_sec')} → "
            f"{sv_new.get('traced_tokens_per_sec')} tok/s traced)")
    else:
        report.append(f"ok: tracing overhead {overhead:.1%} at top load")
    slo = sv_new.get("slo")
    if not isinstance(slo, dict) or slo.get("verdict") is None:
        report.append("WARN: serving SLO verdict is null")
    elif slo.get("burning_fast"):
        report.append(
            f"WARN: serving SLO fast windows burning "
            f"(verdict={slo.get('verdict')}, 5m latency burn "
            f"{slo.get('latency_burn_5m')}x over threshold "
            f"{slo.get('latency_threshold_s')}s)")
    else:
        report.append(
            f"ok: serving SLO verdict {slo.get('verdict')} "
            f"(latency threshold {slo.get('latency_threshold_s')}s)")
    sv_old = old_detail.get("serving")
    if not isinstance(sv_old, dict) or sv_old.get("error"):
        sv_old = {}
    _serving_optimized_lines(sv_old, sv_new, report)
    if not sv_old:
        return
    old_by_rate = {p.get("offered_rps"): p
                   for p in (sv_old.get("load_points") or [])
                   if isinstance(p, dict)}
    for p in points:
        q = old_by_rate.get(p.get("offered_rps"))
        if not isinstance(q, dict):
            continue
        rate = p.get("offered_rps")
        tps_old, tps_new = q.get("tokens_per_sec"), p.get("tokens_per_sec")
        if (isinstance(tps_old, (int, float)) and tps_old > 0
                and isinstance(tps_new, (int, float))
                and tps_new / tps_old - 1.0 < -0.10):
            report.append(
                f"WARN: serving tokens/sec at {rate} req/s "
                f"{tps_old} → {tps_new} ({tps_new / tps_old - 1.0:+.1%})")
        p99_old, p99_new = q.get("p99_total_s"), p.get("p99_total_s")
        if (isinstance(p99_old, (int, float)) and p99_old > 0
                and isinstance(p99_new, (int, float))
                and p99_new / p99_old - 1.0 > 0.10):
            report.append(
                f"WARN: serving p99 at {rate} req/s "
                f"{p99_old}s → {p99_new}s ({p99_new / p99_old - 1.0:+.1%})")


def _serving_optimized_lines(sv_old: Dict[str, Any],
                             sv_new: Dict[str, Any], report: list) -> None:
    """The raw-speed lane (prefix sharing + speculative decoding +
    chunked prefill, docs/serving.md): report the optimized engine's
    top-load tokens/sec and its ratio over the features-off baseline
    measured in the SAME round, and WARN when

    - the speculative acceptance rate is null or below 0.3 (the draft
      is wasting more verify work than it saves — time to retrain or
      shrink it),
    - the prefix-cache hit rate regressed vs the previous round (the
      hashing/eviction path stopped matching what it used to), or
    - p99 at the top offered load grew more than 2x vs the previous
      round (chunked prefill exists precisely to keep tail latency flat
      under load — a 2x jump means long prompts are blocking decode
      again).

    Old rounds without the optimized section skip the cross-round
    checks (the section landed with the raw-speed PR)."""
    opt_new = sv_new.get("optimized")
    if not isinstance(opt_new, dict):
        return
    pts = [p for p in (opt_new.get("load_points") or [])
           if isinstance(p, dict)]
    top = pts[-1] if pts else {}
    acc = opt_new.get("acceptance_rate")
    hit_rate = opt_new.get("prefix_hit_rate")
    report.append(
        f"ok: serving-optimized top {top.get('offered_rps')} req/s: "
        f"{top.get('tokens_per_sec')} tok/s "
        f"({sv_new.get('optimized_over_baseline')}x baseline), "
        f"acceptance={acc}, prefix_hit_rate={hit_rate}, programs "
        f"{opt_new.get('programs_compiled')}/"
        f"{opt_new.get('program_budget')}")
    if not isinstance(acc, (int, float)):
        report.append(
            "WARN: speculative acceptance rate is null with speculation "
            "enabled — the verify path banked no decisions")
    elif acc < 0.3:
        report.append(
            f"WARN: speculative acceptance rate {acc} < 0.3 — the draft "
            f"wastes more verify work than it saves")
    opt_old = sv_old.get("optimized")
    if not isinstance(opt_old, dict):
        return
    hit_old = opt_old.get("prefix_hit_rate")
    if (isinstance(hit_old, (int, float))
            and isinstance(hit_rate, (int, float))
            and hit_rate < hit_old - 0.05):
        report.append(
            f"WARN: prefix-cache hit rate {hit_old} → {hit_rate} "
            f"(regressed — hashing or eviction path changed behavior)")
    old_pts = [p for p in (opt_old.get("load_points") or [])
               if isinstance(p, dict)]
    if old_pts and pts:
        p99_old = old_pts[-1].get("p99_total_s")
        p99_new = top.get("p99_total_s")
        if (isinstance(p99_old, (int, float)) and p99_old > 0
                and isinstance(p99_new, (int, float))
                and p99_new / p99_old > 2.0):
            report.append(
                f"WARN: optimized p99 at top load {p99_old}s → {p99_new}s "
                f"(more than 2x — chunked prefill is no longer keeping "
                f"tail latency flat)")


def _serving_fleet_lines(old_detail: Dict[str, Any],
                         new_detail: Dict[str, Any], report: list) -> None:
    """Advisory fleet-section reporting (serving/fleet.py measured by
    bench's replica-scaling ladder): aggregate tokens/sec at 1/2/4
    replicas plus the mid-burst blue-green rollout. WARNs when the
    section errored, when throughput stopped scaling monotonically with
    replica count, when 2 replicas deliver under 1.6x of 1 (the paced
    engines should land ~2x — below 1.6x the router or the drain path is
    eating the gain), or when the rollout dropped requests / broke
    greedy version parity. Advisory-only: the ladder shares the box with
    the bench itself; the enforced contracts are the tier-1 fleet
    tests."""
    sf_new = new_detail.get("serving_fleet")
    if not isinstance(sf_new, dict):
        return
    if sf_new.get("error"):
        report.append(f"WARN: serving_fleet errored: {sf_new['error']}")
        return
    points = [p for p in (sf_new.get("points") or [])
              if isinstance(p, dict)]
    if not points:
        report.append("WARN: serving_fleet section has no points")
        return
    ladder = " ".join(
        f"{p.get('replicas')}x={p.get('tokens_per_sec')}tok/s"
        f"(p99={p.get('p99_total_s')}s)" for p in points)
    report.append(
        f"ok: serving_fleet {ladder}, speedup@2={sf_new.get('speedup_2')} "
        f"@4={sf_new.get('speedup_4')}")
    if not sf_new.get("monotonic", False):
        report.append(
            "WARN: serving_fleet tokens/sec is not monotonic in replica "
            "count — adding replicas should add capacity")
    sp2 = sf_new.get("speedup_2")
    if isinstance(sp2, (int, float)) and sp2 < 1.6:
        report.append(
            f"WARN: serving_fleet 2-replica speedup {sp2} < 1.6x")
    ro = sf_new.get("rollout")
    if isinstance(ro, dict):
        failed = ro.get("failed")
        if isinstance(failed, (int, float)) and failed > 0:
            report.append(
                f"WARN: blue-green rollout dropped {failed} requests "
                f"(the drain protocol promises zero)")
        if not ro.get("parity_ok", False):
            report.append(
                "WARN: blue-green rollout broke greedy version parity "
                "(a response mixed old and new params)")
        else:
            report.append(
                f"ok: rollout under load: {ro.get('failed')} failed, "
                f"{ro.get('old_version_responses')} old / "
                f"{ro.get('new_version_responses')} new responses, "
                f"{ro.get('rollout_duration_s')}s")


def _exec_cache_lines(old_detail: Dict[str, Any],
                      new_detail: Dict[str, Any], report: list) -> None:
    """Advisory executable-cache reporting (storage/exec_cache.py via
    bench's cold/warm replica-start A/B): WARNs when the section
    errored, when the warm leg hit rate is zero (every program
    recompiled — the persistent cache did nothing), when any warm
    program fell back to a plain compile, when the warm leg's greedy
    tokens diverged from the cold leg's (a deserialized executable must
    be the same program, so the same bits), or when the warm replica
    start regressed more than 2x against the previous round. Advisory
    only: wall-times share the box with the bench; the enforced
    contracts are the tier-1 exec-cache tests."""
    ec_new = new_detail.get("exec_cache")
    if not isinstance(ec_new, dict):
        return
    if ec_new.get("error"):
        report.append(f"WARN: exec_cache errored: {ec_new['error']}")
        return
    report.append(
        f"ok: exec_cache cold {ec_new.get('cold_replica_start_s')}s → warm "
        f"{ec_new.get('warm_replica_start_s')}s "
        f"({ec_new.get('speedup')}x), {ec_new.get('exec_cache_hits')} hits/"
        f"{ec_new.get('exec_cache_misses')} misses, saved "
        f"{ec_new.get('compile_time_saved_s')}s of compile")
    rate = ec_new.get("warm_hit_rate")
    if isinstance(rate, (int, float)) and rate <= 0:
        report.append(
            "WARN: exec_cache warm leg hit rate is 0 — every program "
            "recompiled; the persistent cache is not being consulted")
    fallbacks = ec_new.get("fallback_compiles")
    if isinstance(fallbacks, (int, float)) and fallbacks > 0:
        report.append(
            f"WARN: exec_cache warm leg fell back to plain compile "
            f"{fallbacks} time(s) — a cached executable failed to "
            f"load or dispatch")
    if ec_new.get("tokens_match") is False:
        report.append(
            "WARN: exec_cache warm-leg greedy tokens diverged from the "
            "cold leg — a deserialized executable produced different bits")
    ec_old = old_detail.get("exec_cache")
    warm_new = ec_new.get("warm_replica_start_s")
    warm_old = (ec_old.get("warm_replica_start_s")
                if isinstance(ec_old, dict) else None)
    if (isinstance(warm_old, (int, float)) and warm_old > 0
            and isinstance(warm_new, (int, float))
            and warm_new > 2.0 * warm_old):
        report.append(
            f"WARN: exec_cache warm replica start regressed "
            f"{warm_old}s → {warm_new}s (>2x) — deserialization or "
            f"blob-store reads got slower")


def _multichip_lines(old_detail: Dict[str, Any],
                     new_detail: Dict[str, Any], report: list) -> bool:
    """Multichip scaling-lane gate (parallel/scaling_bench.py via bench's
    ``multichip`` section, one artifact per simulated mesh size). Unlike
    the advisory sections this one ENFORCES: per-axis scaling efficiency
    dropping more than 5 points against the previous round on the same
    mesh size fails the gate — the simulated mesh timeshares one host, so
    the absolute numbers are pessimistic but *stable*, and a 5-point drop
    means the sharded program itself got worse (more collective volume,
    lost overlap), which real ICI will amplify. Collective-structure
    drift on an unchanged program fingerprint stays an advisory WARN: new
    collectives can be a legitimate partitioner change, but it is exactly
    what to look at first when the efficiency line fails.

    Returns False when the gate should fail, True otherwise."""
    mc_new = new_detail.get("multichip")
    if not isinstance(mc_new, dict):
        return True
    if mc_new.get("error"):
        report.append(f"WARN: multichip errored: {mc_new['error']}")
        return True
    ok = True
    mc_old = old_detail.get("multichip")
    old_runs = (mc_old.get("runs") or {}) if isinstance(mc_old, dict) else {}
    for size, run in sorted((mc_new.get("runs") or {}).items(),
                            key=lambda kv: int(kv[0])
                            if str(kv[0]).isdigit() else 0):
        if not isinstance(run, dict):
            continue
        if run.get("error"):
            report.append(f"WARN: multichip[{size}] errored: {run['error']}")
            continue
        if run.get("schema_errors"):
            report.append(f"WARN: multichip[{size}] artifact failed schema "
                          f"validation: {run['schema_errors']}")
        meshes = run.get("meshes") or {}
        effs = " ".join(
            f"{ax}={m.get('scaling_efficiency'):.3f}"
            if isinstance(m.get("scaling_efficiency"), (int, float))
            else f"{ax}=null"
            for ax, m in sorted(meshes.items()) if isinstance(m, dict))
        report.append(f"ok: multichip {size} devices: {effs}")
        old_run = old_runs.get(size)
        old_meshes = (old_run.get("meshes") or {}) \
            if isinstance(old_run, dict) else {}
        for axis, m in sorted(meshes.items()):
            if not isinstance(m, dict):
                continue
            eff = m.get("scaling_efficiency")
            old_m = old_meshes.get(axis)
            if not isinstance(old_m, dict):
                continue
            old_eff = old_m.get("scaling_efficiency")
            if (isinstance(old_eff, (int, float))
                    and isinstance(eff, (int, float))
                    and eff < old_eff - 0.05):
                ok = False
                report.append(
                    f"FAIL: multichip {size}-device {axis} scaling "
                    f"efficiency {old_eff:.3f} → {eff:.3f} (dropped more "
                    f"than 5 points — the sharded program regressed)")
            fp_new, fp_old = m.get("program_fingerprint"), \
                old_m.get("program_fingerprint")
            coll_new = (m.get("collectives") or {}).get("fingerprint")
            coll_old = (old_m.get("collectives") or {}).get("fingerprint")
            if (fp_new and fp_new == fp_old
                    and coll_new and coll_old and coll_new != coll_old):
                report.append(
                    f"WARN: multichip {size}-device {axis} collective "
                    f"structure drifted on an unchanged program "
                    f"({coll_old} → {coll_new}) — the partitioner is "
                    f"emitting different collectives for the same trace")
    return ok


def _tsdb_lines(old_detail: Dict[str, Any],
                new_detail: Dict[str, Any], report: list) -> None:
    """Advisory time-series-layer reporting (telemetry/tsdb.py measured
    by bench's synthetic scrape soak): WARNs when the section errored,
    when the scrape+store+rule-evaluation duty cycle exceeds 2% of the
    scrape period (the scrape loop shares the master process — it must
    stay invisible next to request handling), or when the store ended
    the soak over its memory budget (eviction stopped keeping up).
    Advisory-only: wall-times share the box with the bench; the
    enforced contracts are the tier-1 TSDB tests."""
    ts_new = new_detail.get("tsdb")
    if not isinstance(ts_new, dict):
        return
    if ts_new.get("error"):
        report.append(f"WARN: tsdb errored: {ts_new['error']}")
        return
    duty = ts_new.get("duty_fraction")
    duty_s = f"{duty:.3%}" if isinstance(duty, (int, float)) else "null"
    report.append(
        f"ok: tsdb {ts_new.get('series')} series, "
        f"{ts_new.get('samples_per_scrape')} samples/scrape, "
        f"scrape {ts_new.get('scrape_ms')}ms → duty {duty_s} of the "
        f"{ts_new.get('scrape_period_s')}s period")
    if not isinstance(duty, (int, float)):
        report.append("WARN: tsdb duty_fraction is null — the scrape "
                      "soak banked no timing")
    elif duty > 0.02:
        report.append(
            f"WARN: tsdb scrape duty cycle {duty:.2%} > 2% of the "
            f"scrape period — storing the cluster view is crowding "
            f"the master")
    if ts_new.get("within_budget") is False:
        report.append(
            f"WARN: tsdb ended the soak over its memory budget "
            f"({ts_new.get('bytes_estimate')} > "
            f"{ts_new.get('memory_budget_bytes')} bytes) — eviction "
            f"is not keeping up with series churn")


def _recovery_lines(old_detail: Dict[str, Any],
                    new_detail: Dict[str, Any], report: list) -> None:
    """Advisory self-healing reporting (serving/supervisor.py measured
    by bench's fault-storm section): WARNs when the section errored,
    when any leg lost an accepted request or left a ledger entry open
    (exactly-once failover is the tentpole contract), when KV blocks
    leaked through a crash teardown, when MTTR blew the budget, or when
    the supervised leg recovered to under half the clean leg's
    throughput. Advisory-only: the enforced contracts are the chaos
    lane's scenario asserts (tests/test_self_healing.py)."""
    rec = new_detail.get("recovery")
    if not isinstance(rec, dict):
        return
    if rec.get("error"):
        report.append(f"WARN: recovery errored: {rec['error']}")
        return
    healed = rec.get("supervised") or {}
    frac = rec.get("recovered_throughput_fraction")
    report.append(
        f"ok: recovery supervised leg {healed.get('completed')}/"
        f"{rec.get('requests')} completed, p99 {healed.get('p99_s')}s, "
        f"mttr {healed.get('mttr_s')}s, "
        f"{healed.get('replacements')} replacement(s), "
        f"throughput x{frac} of clean")
    budget = float(rec.get("mttr_budget_s") or 30.0)
    for leg_name in ("clean", "unsupervised", "supervised"):
        leg = rec.get(leg_name)
        if not isinstance(leg, dict):
            continue
        lost = int(leg.get("lost") or 0)
        open_n = int(leg.get("open_ledger_entries") or 0)
        if lost or open_n:
            report.append(
                f"WARN: recovery {leg_name} leg lost {lost} request(s) "
                f"({open_n} ledger entries left open) — exactly-once "
                f"failover dropped accepted work")
        leaked = int(leg.get("leaked_blocks") or 0)
        if leaked:
            report.append(
                f"WARN: recovery {leg_name} leg leaked {leaked} KV "
                f"block(s) — a crash teardown dropped refs")
        mttr = leg.get("mttr_s")
        if isinstance(mttr, (int, float)) and mttr > budget:
            report.append(
                f"WARN: recovery {leg_name} leg MTTR {mttr}s > "
                f"{budget}s budget — replacement warm-start regressed")
    if isinstance(frac, (int, float)) and frac < 0.5:
        report.append(
            f"WARN: recovery supervised throughput only x{frac} of the "
            f"clean run — self-healing is not restoring capacity")


def _kv_hierarchy_lines(old_detail: Dict[str, Any],
                        new_detail: Dict[str, Any], report: list) -> None:
    """Advisory KV-memory-hierarchy reporting (serving/kv_store.py
    measured by bench's Zipf A/B + restart leg): WARNs when the section
    errored, when the tiered leg's fleet-wide prefix hit rate fell more
    than 0.05 below the prefix-cache-only baseline (the tier should
    only ever add hits), when the tiered p99 regressed more than 2x
    against the baseline leg of the SAME round (both legs share the
    box, so cross-round wall-time compares are noise), or when the
    mid-burst replacement replica promoted nothing from the tier (a
    cold restart — the hierarchy's whole point is the warm one).
    Advisory-only: the enforced contracts are tests/test_kv_store.py
    and the kv_warm_failover chaos scenario."""
    kv = new_detail.get("kv_hierarchy")
    if not isinstance(kv, dict):
        return
    if kv.get("error"):
        report.append(f"WARN: kv_hierarchy errored: {kv['error']}")
        return
    restart = kv.get("restart") or {}
    report.append(
        f"ok: kv_hierarchy prefix hit rate "
        f"{kv.get('baseline_prefix_hit_rate')} → "
        f"{kv.get('tiered_prefix_hit_rate')} with tier "
        f"(tier hit rate {kv.get('kv_tier_hit_rate')}), restart promoted "
        f"{restart.get('kv_promoted_blocks')} block(s) from the tier")
    base_rate = kv.get("baseline_prefix_hit_rate")
    tier_rate = kv.get("tiered_prefix_hit_rate")
    if (isinstance(base_rate, (int, float))
            and isinstance(tier_rate, (int, float))
            and tier_rate < base_rate - 0.05):
        report.append(
            f"WARN: kv_hierarchy tiered prefix hit rate {tier_rate} fell "
            f"more than 0.05 below the baseline {base_rate} — promotion "
            f"or affinity routing is losing coverage it should add")
    base_p99 = kv.get("baseline_p99_s")
    tier_p99 = kv.get("tiered_p99_s")
    if (isinstance(base_p99, (int, float)) and base_p99 > 0
            and isinstance(tier_p99, (int, float))
            and tier_p99 > 2.0 * base_p99):
        report.append(
            f"WARN: kv_hierarchy tiered p99 {tier_p99}s > 2x baseline "
            f"{base_p99}s — tier lookups/promotion are stalling the "
            f"admission path")
    if restart and not kv.get("restart_warm"):
        report.append(
            "WARN: kv_hierarchy restarted replica promoted 0 blocks from "
            "the tier — the mid-burst replacement came up cold")
    errs = int(kv.get("tiered_errors") or 0)
    if errs:
        report.append(
            f"WARN: kv_hierarchy tiered leg failed {errs} request(s)")


def gate(old: Dict[str, Any], new: Dict[str, Any], *,
         tolerance: float = DEFAULT_TOLERANCE,
         allow_null_mfu: bool = False) -> Tuple[bool, list]:
    """Returns (ok, report_lines)."""
    report = []
    ok = True
    old_detail = old.get("detail") or {}
    new_detail = new.get("detail") or {}
    old_plat = old_detail.get("platform", "")
    new_plat = new_detail.get("platform", "")
    old_v = float(old.get("value") or 0.0)
    new_v = float(new.get("value") or 0.0)

    if new_detail.get("mfu") is None:
        if allow_null_mfu:
            report.append("WARN: new round has mfu=null (allowed by flag)")
        else:
            ok = False
            report.append(
                "FAIL: new round has mfu=null — the analytic FLOPs engine "
                "must always produce one (check mfu_peak_assumed wiring)")
    else:
        report.append(
            f"ok: mfu={new_detail['mfu']} "
            f"(peak {new_detail.get('mfu_peak_assumed', '?')})")

    if old_plat and new_plat and old_plat != new_plat:
        report.append(
            f"WARN: platform changed {old_plat!r} → {new_plat!r}; "
            f"throughput compare skipped (numbers not comparable)")
    elif old_v <= 0:
        report.append(
            "WARN: old round banked no throughput; compare skipped")
    elif new_v <= 0:
        ok = False
        report.append(
            f"FAIL: new round banked no throughput (old: {old_v:.3f})")
    else:
        delta = new_v / old_v - 1.0
        line = (f"throughput {old_v:.3f} → {new_v:.3f} samples/sec/chip "
                f"({delta:+.1%}, tolerance {tolerance:+.1%})")
        if delta < tolerance:
            ok = False
            report.append(f"FAIL: {line}")
        else:
            report.append(f"ok: {line}")
    _section_notes(old_detail, new_detail, report)
    _control_plane_lines(old_detail, new_detail, report)
    _xla_lines(old_detail, new_detail, report)
    _goodput_lines(old_detail, new_detail, report)
    _serving_lines(old_detail, new_detail, report)
    _serving_fleet_lines(old_detail, new_detail, report)
    _exec_cache_lines(old_detail, new_detail, report)
    _tsdb_lines(old_detail, new_detail, report)
    _recovery_lines(old_detail, new_detail, report)
    _kv_hierarchy_lines(old_detail, new_detail, report)
    ok = _multichip_lines(old_detail, new_detail, report) and ok
    return ok, report


# the gate's report lines are prefix-tagged prose; --json re-emits them
# as one structured object per line without touching the text format
_LINE_LEVELS = (("ok: ", "ok"), ("WARN: ", "warn"), ("FAIL: ", "fail"),
                ("note: ", "note"))
_SECTION_WORDS = set(OPTIONAL_SECTIONS) | {"serving-optimized", "rollout",
                                           "throughput", "tracing", "mfu"}


def report_line_to_json(line: str) -> Dict[str, Any]:
    """One report line → {"level", "section", "message"}. The section is
    recovered from the line's leading word (every section helper starts
    its lines with the section name); headline checks that carry no
    section name are tagged "headline"."""
    level, msg = "info", line
    for prefix, lvl in _LINE_LEVELS:
        if line.startswith(prefix):
            level, msg = lvl, line[len(prefix):]
            break
    word = msg.split(None, 1)[0] if msg.split() else ""
    word = word.split("[")[0].split("=")[0].rstrip(":,")
    if word == "section":
        m = re.search(r"section '([^']+)'", msg)
        section = m.group(1) if m else "headline"
    elif word in _SECTION_WORDS:
        section = word
    else:
        section = "headline"
    return {"level": level, "section": section, "message": msg}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("old", nargs="?", default=None,
                        help="previous round (BENCH_rN.json or raw result)")
    parser.add_argument("new", nargs="?", default=None,
                        help="new round")
    parser.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                        help="max allowed relative throughput change, "
                             "negative = allowed drop (default -0.05)")
    parser.add_argument("--allow-null-mfu", action="store_true",
                        help="demote the null-mfu failure to a warning")
    parser.add_argument("--json", action="store_true",
                        help="emit one JSON object per report line "
                             "({level, section, message}) instead of text")
    args = parser.parse_args(argv)

    try:
        if args.old is None or args.new is None:
            old_path, new_path = newest_rounds()
            if args.json:
                print(json.dumps({"level": "info", "section": "gate",
                                  "message": f"auto-selected rounds: "
                                             f"{old_path} → {new_path}"}))
            else:
                print(f"auto-selected rounds: {old_path} → {new_path}")
        else:
            old_path, new_path = args.old, args.new
        old = load_bench(old_path)
        new = load_bench(new_path)
    except (OSError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    ok, report = gate(old, new, tolerance=args.tolerance,
                      allow_null_mfu=args.allow_null_mfu)
    if args.json:
        for line in report:
            print(json.dumps(report_line_to_json(line)))
        print(json.dumps({"level": "verdict", "section": "gate",
                          "message": "PASS" if ok else "FAIL", "ok": ok}))
    else:
        for line in report:
            print(line)
        print("bench gate: " + ("PASS" if ok else "FAIL"))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
