"""XLA-level telemetry tests (telemetry/xla.py): explicit compile capture,
fingerprint stability, and the median/MAD step-time anomaly
detector — plus the Prometheus round-trip of every new
metric family."""

import jax
import jax.numpy as jnp

from determined_clone_tpu.telemetry import (
    MetricsRegistry,
    Tracer,
    parse_prometheus_text,
)
from determined_clone_tpu.telemetry.xla import (
    StepTimeAnomalyDetector,
    aot_compile,
    fingerprint_stablehlo,
)


# ---------------------------------------------------------------------------
# aot_compile: capture, fingerprint, fallback
# ---------------------------------------------------------------------------

class TestAotCompile:
    def test_capture_and_execution_equivalence(self):
        fn = jax.jit(lambda x: (x * 2.0 + 1.0).sum())
        x = jnp.arange(8.0)
        wrapped, record = aot_compile(fn, (x,), program="probe")
        assert record is not None
        assert record.program == "probe"
        assert len(record.fingerprint) == 64  # sha256 hex
        assert record.lower_seconds >= 0.0
        assert record.compile_seconds > 0.0
        # the AOT executable computes the same thing as the jit path
        assert float(wrapped(x)) == float(fn(x))
        # CPU cost model reports per-execution FLOPs (bench relies on it)
        assert record.flops is not None and record.flops > 0
        d = record.as_dict()
        assert d["fingerprint"] == record.fingerprint
        assert None not in d.values()

    def test_fingerprint_stable_across_captures(self):
        """Same program -> same fingerprint (the executable-cache key);
        a different program -> a different one."""
        x = jnp.arange(8.0)
        _, rec_a = aot_compile(jax.jit(lambda v: (v * 2.0).sum()), (x,))
        _, rec_b = aot_compile(jax.jit(lambda v: (v * 2.0).sum()), (x,))
        _, rec_c = aot_compile(jax.jit(lambda v: (v * 3.0).sum()), (x,))
        assert rec_a.fingerprint == rec_b.fingerprint
        assert rec_a.fingerprint != rec_c.fingerprint

    def test_shape_mismatch_falls_back_to_jit(self):
        fn = jax.jit(lambda x: x.sum())
        wrapped, record = aot_compile(fn, (jnp.ones((4,)),))
        assert record is not None
        # a remainder-shaped batch goes through the original jit wrapper
        assert float(wrapped(jnp.ones((3,)))) == 3.0

    def test_non_jitted_callable_degrades_to_noop(self):
        def plain(x):
            return x + 1  # no .lower(): capture must hand it back as-is

        wrapped, record = aot_compile(plain, (1.0,))
        assert wrapped is plain
        assert record is None

    def test_fingerprint_helper_is_sha256(self):
        fp = fingerprint_stablehlo("module @foo {}")
        assert len(fp) == 64
        assert fp == fingerprint_stablehlo("module @foo {}")
        assert fp != fingerprint_stablehlo("module @bar {}")

    def test_export_lands_in_registry_and_tracer(self):
        reg = MetricsRegistry()
        tr = Tracer()
        fn = jax.jit(lambda x: (x @ x).sum())
        wrapped, record = aot_compile(
            fn, (jnp.ones((8, 8)),), program="train_step",
            registry=reg, tracer=tr)
        assert record is not None
        assert reg.counter("xla_compiles_total").value == 1
        spans = [e for e in tr.events() if e["name"] == "xla_compile"]
        assert len(spans) == 1
        assert spans[0]["args"]["program"] == "train_step"
        assert spans[0]["args"]["fingerprint"] == record.fingerprint[:16]


# ---------------------------------------------------------------------------
# Step-time anomaly detector: median/MAD, exactly-once, no self-masking
# ---------------------------------------------------------------------------

class TestAnomalyDetector:
    def test_single_spike_fires_exactly_once(self):
        reg = MetricsRegistry()
        det = StepTimeAnomalyDetector(reg, window=32, threshold=5.0,
                                      min_samples=8)
        flagged = []
        # steady baseline with mild jitter, one 50x straggler at index 20
        for i in range(40):
            dur = 0.5 if i == 20 else 0.010 + 0.0001 * (i % 3)
            flagged.append(det.observe(dur))
        assert flagged.count(True) == 1
        assert flagged[20] is True
        assert det.anomalies == 1
        assert reg.counter("step_time_anomalies_total").value == 1
        ev = det.events[0]
        assert ev["duration_s"] == 0.5
        assert ev["step_index"] == 21  # 1-based position in the stream
        assert ev["limit_s"] < 0.5

    def test_anomaly_not_admitted_so_next_one_still_fires(self):
        """detect-then-admit would raise the baseline after the first
        straggler and mask the second; the window must hold pre-anomaly
        history only."""
        det = StepTimeAnomalyDetector(window=32, threshold=5.0,
                                      min_samples=8)
        for _ in range(16):
            det.observe(0.010)
        assert det.observe(0.5) is True
        assert 0.5 not in det.window
        for _ in range(4):
            det.observe(0.010)
        assert det.observe(0.5) is True
        assert det.anomalies == 2

    def test_warmup_never_flags(self):
        det = StepTimeAnomalyDetector(min_samples=16)
        # compile + cache-warm steps are wildly slow; all inside warmup
        assert not any(det.observe(d) for d in [5.0, 2.0] + [0.01] * 13)

    def test_rel_floor_absorbs_scheduler_jitter(self):
        """An idle-CPU baseline has MAD ~= 0; without the relative floor a
        1.2x scheduler blip would count as 'infinitely many sigmas'."""
        det = StepTimeAnomalyDetector(window=32, threshold=5.0,
                                      min_samples=8, rel_floor=0.05)
        for _ in range(16):
            det.observe(0.010)  # identical durations: MAD == 0
        assert det.observe(0.012) is False  # +20%: jitter, not a straggler
        assert det.observe(0.10) is True    # 10x: a straggler

    def test_instant_event_reaches_tracer(self):
        tr = Tracer()
        det = StepTimeAnomalyDetector(tracer=tr, window=32, min_samples=8)
        for _ in range(10):
            det.observe(0.01)
        det.observe(1.0)
        evs = [e for e in tr.events() if e["name"] == "step_time_anomaly"]
        assert len(evs) == 1 and evs[0]["ph"] == "i"
        assert det.summary()["anomalies"] == 1
        assert det.summary()["recent_events"][0]["duration_s"] == 1.0


# ---------------------------------------------------------------------------
# Prometheus round-trip: every new family survives dump -> parse
# ---------------------------------------------------------------------------

def test_new_families_round_trip_through_prometheus_text():
    reg = MetricsRegistry()
    tr = Tracer()
    aot_compile(jax.jit(lambda x: (x * 2.0).sum()), (jnp.ones((8,)),),
                program="train_step", registry=reg, tracer=tr)
    det = StepTimeAnomalyDetector(reg, window=32, min_samples=8)
    for _ in range(10):
        det.observe(0.01)
    det.observe(1.0)
    reg.counter("flight_records_dropped",
                "flight-recorder records lost to write errors").inc(2)

    parsed = parse_prometheus_text(reg.dump())
    by_name = {}
    for name, labels, value in parsed["samples"]:
        by_name.setdefault(name, []).append((labels, value))
    for family in ("xla_compiles_total", "xla_compile_seconds",
                   "xla_program_flops", "xla_program_bytes_accessed",
                   "step_time_anomalies_total", "flight_records_dropped"):
        assert family in by_name, f"{family} missing from exposition"
    assert by_name["step_time_anomalies_total"][0][1] == 1
    assert by_name["flight_records_dropped"][0][1] == 2
    # labeled families carry {program, fingerprint} through the text format
    labels, _ = by_name["xla_compile_seconds"][0]
    assert labels["program"] == "train_step"
    assert len(labels["fingerprint"]) == 16
