"""Bench result-schema regression test: a REAL bench child run must emit
the fields the regression gate (tools/bench_gate.py) and the BENCH history
depend on — non-null analytic ``mfu``, its labeled denominator, and the
XLA section (compile time, HLO fingerprint, measured MFU, peak memory)
added by the observability issue. A schema drift here silently turns the
gate advisory, so it is pinned by running the actual child, not a mock.

The child is killed right after it banks the first rung's result line (the
mha/mnist/pipeline extras are budget-dependent and not schema-load-bearing),
keeping the test inside the tier-1 lane.
"""
import json
import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


@pytest.fixture(scope="module")
def bench_result():
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
    }
    proc = subprocess.Popen(
        [sys.executable, BENCH, "--child"], env=env, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL)
    result = None
    deadline = time.monotonic() + 300
    try:
        for line in proc.stdout:
            if time.monotonic() > deadline:
                break
            line = line.strip()
            if not line.startswith("{"):
                continue
            obj = json.loads(line)
            if "metric" in obj:
                result = obj
                break
    finally:
        try:
            proc.send_signal(signal.SIGKILL)
        except OSError:
            pass
        proc.wait(timeout=30)
    assert result is not None, "bench child banked no result line"
    return result


def test_headline_fields(bench_result):
    assert bench_result["metric"] == "gpt_train_throughput"
    assert bench_result["value"] > 0
    assert bench_result["unit"] == "samples/sec/chip"


def test_analytic_mfu_never_null(bench_result):
    """The bench gate hard-fails on mfu=null; the analytic engine must
    produce one on every platform, with the denominator labeled."""
    detail = bench_result["detail"]
    assert detail["mfu"] is not None and detail["mfu"] > 0
    assert isinstance(detail["mfu_peak_assumed"], str)
    assert ":" in detail["mfu_peak_assumed"]  # "<label>:<peak flops>"
    assert detail["flops_per_step"] > 0


def test_xla_section_schema(bench_result):
    """The XLA section: every field the gate's _xla_lines reads, non-null
    on the CPU lane (the lane that always runs)."""
    xla = bench_result["detail"]["xla"]
    assert xla["compile_time_s"] > 0
    assert isinstance(xla["fingerprint"], str)
    assert len(xla["fingerprint"]) == 16
    assert xla["program_flops"] > 0
    assert xla["program_bytes_accessed"] > 0
    assert xla["measured_flops_per_sec"] > 0
    assert 0 < xla["measured_mfu"] < 1
    assert xla["peak_memory_bytes"] > 0
    assert xla["memory_device_count"] >= 1
    # median-of-repeats ran (the r03->r04 noise fix): spread recorded
    assert xla["timing_spread"] is None or xla["timing_spread"] >= 1.0


def test_goodput_section_schema(bench_result):
    """The goodput section (telemetry/goodput.py, measured on a real
    trainer mini-run inside the bench child): the acceptance criterion is
    a non-null fraction with the conservation invariant holding — a null
    here means the ledger fell out of the bench wiring."""
    gp = bench_result["detail"]["goodput"]
    assert gp.get("error") is None
    assert gp["goodput_fraction"] is not None
    assert 0 <= gp["goodput_fraction"] <= 1
    assert gp["wall_s"] > 0
    assert gp["conservation_ok"] is True
    assert gp["conservation_error_fraction"] <= 0.01
    cats = gp["categories"]
    assert cats["productive"] > 0
    assert cats["checkpoint_save"] > 0  # the mini-run commits at batch 8


def test_serving_section_schema(bench_result):
    """The serving section (serving/engine.py measured by bench's
    latency-vs-load sweep): non-null tokens/sec and p50/p99 at >= 3
    offered loads, continuous batching beating the static
    run-to-completion baseline at the highest load in the same run, and
    the compile count inside the bucket budget — the serving-lane
    acceptance criteria, pinned against the real child."""
    sv = bench_result["detail"]["serving"]
    assert sv.get("error") is None, sv
    points = sv["load_points"]
    assert len(points) >= 3
    rates = [p["offered_rps"] for p in points]
    assert rates == sorted(rates) and len(set(rates)) == len(rates)
    for p in points:
        assert p["tokens_per_sec"] > 0
        assert p["p50_total_s"] > 0
        assert p["p99_total_s"] >= p["p50_total_s"]
        assert p["completed"] == sv["requests"]
    assert sv["static"]["tokens_per_sec"] > 0
    # the point of continuous batching — same programs, same pool, same
    # request set; only the scheduling policy differs
    assert sv["continuous_over_static"] > 1.0, sv
    assert 0 < sv["programs_compiled"] <= sv["program_budget"]
    assert sv["serving_mfu"] > 0
    assert ":" in sv["mfu_peak_assumed"]
    # tracing A/B at top load: the overhead estimate must be measured
    # (non-null) and sane; the <2% budget itself is the gate's advisory
    assert isinstance(sv["tracing_overhead"], float)
    assert sv["tracing_overhead"] < 0.5
    assert sv["traced_tokens_per_sec"] > 0
    # the simulated-clock SLO replay of the measured latency distribution
    slo = sv["slo"]
    assert slo["verdict"] in ("ok", "slow_burn", "fast_burn", "no_data")
    assert slo["latency_threshold_s"] > 0
    assert isinstance(slo["burning_fast"], bool)


def test_tsdb_section_schema(bench_result):
    """The tsdb section (telemetry/tsdb.py measured by bench's synthetic
    scrape soak): the acceptance criterion is a scrape+store+rule-eval
    duty cycle under 2% of the scrape period with the store inside its
    memory budget — a null here means the soak fell out of the wiring."""
    ts = bench_result["detail"]["tsdb"]
    assert ts.get("error") is None, ts
    assert ts["series"] > 0
    assert ts["samples_per_scrape"] > 0
    assert ts["dump_ms"] > 0
    assert ts["scrape_ms"] > 0
    assert ts["scrape_period_s"] > 0
    assert 0 < ts["duty_fraction"] < 0.02
    assert ts["bytes_estimate"] > 0
    assert ts["within_budget"] is True


def test_gate_accepts_fresh_round(bench_result):
    """The regression gate passes a round against itself and prints the
    advisory xla + goodput lines — wiring proof that gate and schema
    agree."""
    from tools.bench_gate import gate

    ok, report = gate(bench_result, bench_result)
    assert ok, report
    assert any(line.startswith("ok: xla compile=") for line in report)
    assert any(line.startswith("ok: goodput fraction=") for line in report)
    assert any(line.startswith("ok: serving ") for line in report)
    assert any(line.startswith("ok: tsdb ") for line in report)
    warns = [line for line in report if line.startswith("WARN:")]
    assert not warns, warns


def test_gate_report_lines_convert_to_json(bench_result):
    """--json is a faithful re-encoding: every text report line maps to
    one {level, section, message} record, with the section recovered
    from the line itself (the contract CI dashboards consume)."""
    from tools.bench_gate import gate, report_line_to_json

    _, report = gate(bench_result, bench_result)
    for line in report:
        rec = report_line_to_json(line)
        assert rec["level"] in ("ok", "warn", "fail", "note", "info")
        assert rec["message"] and rec["message"] in line
    by_section = {report_line_to_json(line)["section"]
                  for line in report}
    assert {"throughput", "xla", "goodput", "serving", "tsdb"} <= \
        by_section
    # spot-check the three prefix levels and the section-note form
    assert report_line_to_json("FAIL: mfu missing")["level"] == "fail"
    assert report_line_to_json(
        "WARN: tsdb errored: boom") == {
            "level": "warn", "section": "tsdb",
            "message": "tsdb errored: boom"}
    note = report_line_to_json(
        "note: section 'exec_cache' present in the previous round is "
        "missing in the new one; compare skipped")
    assert note == {"level": "note", "section": "exec_cache",
                    "message": note["message"]}


def test_gate_enforces_bench_history(tmp_path):
    """The throughput compare is ENFORCED, not advisory: the two newest
    BENCH rounds of a history must gate clean at the -5% tolerance, and a
    newest round past it must fail. The history is written here, in the
    driver's wrapper format (``{"n", "cmd", "rc", "tail"}``): the rounds
    the repo once carried at its root were host-CPU timings of a toy and
    are gone. mfu=null is allowed, as pre-analytic-engine rounds had it."""
    from tools.bench_gate import gate, load_bench, newest_rounds

    def write_round(n, value):
        line = {"metric": "gpt_train_throughput", "value": value,
                "unit": "samples/sec/chip",
                "detail": {"platform": "cpu", "mfu": None}}
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps({
            "n": n, "cmd": "python bench.py", "rc": 0,
            "tail": "noise\n" + json.dumps(line) + "\n"}))

    with pytest.raises(ValueError):
        newest_rounds(str(tmp_path))  # no history: nothing to compare
    for n, value in ((3, 43.089), (4, 38.909), (5, 40.589)):
        write_round(n, value)
    old_path, new_path = newest_rounds(str(tmp_path))
    assert (os.path.basename(old_path), os.path.basename(new_path)) == (
        "BENCH_r04.json", "BENCH_r05.json")
    ok, report = gate(load_bench(old_path), load_bench(new_path),
                      allow_null_mfu=True)
    assert ok, "\n".join(report)
    write_round(6, 36.0)  # -11% against round 5
    old_path, new_path = newest_rounds(str(tmp_path))
    ok, report = gate(load_bench(old_path), load_bench(new_path),
                      allow_null_mfu=True)
    assert not ok and any(line.startswith("FAIL:") for line in report)
