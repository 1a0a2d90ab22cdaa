"""Flight-recorder tests: the crash black box (telemetry/flight.py).

Fast unit tests cover the segment ring mechanics (rotation, bound,
read-back, torn-line tolerance, write-fault drop policy); the slow chaos
test kill -9s a real training subprocess and proves `dct debug flight`
recovers the final pre-kill steps as a valid Chrome trace — the property
the whole module exists for.
"""
import json
import os
import subprocess
import sys

import pytest

from determined_clone_tpu import faults
from determined_clone_tpu.telemetry import (
    FlightRecorder,
    MetricsRegistry,
    Telemetry,
    flight_summary,
    flight_to_chrome_trace,
    read_flight,
    validate_chrome_trace,
)
from determined_clone_tpu.telemetry.flight import _segment_paths

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spans(directory):
    return [r for r in read_flight(str(directory)) if r.get("kind") == "span"]


# ---------------------------------------------------------------------------
# Segment ring mechanics
# ---------------------------------------------------------------------------

class TestSegmentRing:
    def test_rotation_and_ring_bound(self, tmp_path):
        rec = FlightRecorder(str(tmp_path), segment_events=4, max_segments=3)
        for i in range(40):
            rec.record_span({"name": "step", "ts_us": float(i),
                             "dur_us": 1.0, "tid": 1, "tname": "t",
                             "depth": 0})
        rec.close()
        paths = _segment_paths(str(tmp_path))
        assert 1 <= len(paths) <= 3
        # filenames strictly increasing and the OLDEST were deleted: after
        # 40 records at 4/segment the surviving ring starts well past 1
        seqs = [int(os.path.basename(p).split("-")[1].split(".")[0])
                for p in paths]
        assert seqs == sorted(seqs)
        assert seqs[0] > 1
        # every surviving record is still readable, newest included
        spans = _spans(tmp_path)
        assert spans and spans[-1]["ts_us"] == 39.0

    def test_read_back_and_summary(self, tmp_path):
        rec = FlightRecorder(str(tmp_path), segment_events=64)
        rec.record_span({"name": "train_dispatch", "ts_us": 0.0,
                         "dur_us": 5.0, "tid": 1, "tname": "t", "depth": 0})
        rec.record_span({"name": "dataload_wait", "ts_us": 6.0,
                         "dur_us": 1.0, "tid": 1, "tname": "t", "depth": 0})
        rec.record_metrics({"batches_trained": {"value": 8.0}},
                           batches_trained=8)
        rec.close()
        s = flight_summary(str(tmp_path))
        assert s["segments"] == 1
        assert s["spans"] == 2
        assert s["metric_snapshots"] == 1
        assert s["span_names"] == {"train_dispatch": 1, "dataload_wait": 1}
        assert s["last_batches_trained"] == 8
        assert s["last_snapshot"]["batches_trained"]["value"] == 8.0

    def test_resume_appends_after_restart(self, tmp_path):
        """A restart leg must append new segments, not clobber the
        previous leg's evidence (the crash being debugged happened there)."""
        leg1 = FlightRecorder(str(tmp_path), segment_events=64)
        leg1.record_span({"name": "before_crash", "ts_us": 0.0,
                          "dur_us": 1.0, "tid": 1, "tname": "t", "depth": 0})
        leg1.close()
        leg2 = FlightRecorder(str(tmp_path), segment_events=64)
        leg2.record_span({"name": "after_restart", "ts_us": 0.0,
                          "dur_us": 1.0, "tid": 1, "tname": "t", "depth": 0})
        leg2.close()
        names = [r["name"] for r in _spans(tmp_path)]
        assert names == ["before_crash", "after_restart"]
        assert flight_summary(str(tmp_path))["segments"] == 2

    def test_torn_final_line_skipped(self, tmp_path):
        """A kill mid-write leaves a partial JSON line at the tail; the
        reader must skip it and keep everything before it."""
        rec = FlightRecorder(str(tmp_path), segment_events=64)
        for i in range(3):
            rec.record_span({"name": f"s{i}", "ts_us": float(i),
                             "dur_us": 1.0, "tid": 1, "tname": "t",
                             "depth": 0})
        rec.close()
        path = _segment_paths(str(tmp_path))[-1]
        with open(path, "a") as f:
            f.write('{"kind": "span", "name": "torn')  # no newline, no close
        names = [r["name"] for r in _spans(tmp_path)]
        assert names == ["s0", "s1", "s2"]

    def test_kill9_durability_no_close(self, tmp_path):
        """Line buffering means records written before an os._exit-style
        death are on disk without any close()/flush() having run."""
        rec = FlightRecorder(str(tmp_path), segment_events=64)
        rec.record_span({"name": "last_words", "ts_us": 0.0, "dur_us": 1.0,
                         "tid": 1, "tname": "t", "depth": 0})
        # no close(): read through the filesystem as a post-mortem would
        assert [r["name"] for r in _spans(tmp_path)] == ["last_words"]


# ---------------------------------------------------------------------------
# Failure policy: a write error drops the record, never raises
# ---------------------------------------------------------------------------

class TestWriteFaults:
    def test_injected_write_error_drops_and_counts(self, tmp_path):
        reg = MetricsRegistry()
        rec = FlightRecorder(str(tmp_path), segment_events=64, registry=reg)
        with faults.plan_active({"rules": [
                {"point": "flight.write", "action": "error", "exc": "io",
                 "nth": 2, "times": 1}]}):
            rec.record_span({"name": "ok1", "ts_us": 0.0, "dur_us": 1.0,
                             "tid": 1, "tname": "t", "depth": 0})
            rec.record_span({"name": "lost", "ts_us": 1.0, "dur_us": 1.0,
                             "tid": 1, "tname": "t", "depth": 0})  # dropped
            rec.record_span({"name": "ok2", "ts_us": 2.0, "dur_us": 1.0,
                             "tid": 1, "tname": "t", "depth": 0})
        rec.close()
        assert rec.records_dropped == 1
        assert reg.counter("flight_records_dropped").value == 1
        assert [r["name"] for r in _spans(tmp_path)] == ["ok1", "ok2"]

    def test_unserializable_record_dropped(self, tmp_path):
        rec = FlightRecorder(str(tmp_path), segment_events=64)
        rec.record_span({"name": "bad", "payload": {1, 2, 3},
                         "cycle": None})
        # sets stringify via default=str — build a real cycle instead
        cyc = {}
        cyc["self"] = cyc
        rec.record_span(cyc)
        rec.close()
        assert rec.records_dropped == 1  # only the cycle is unserializable


# ---------------------------------------------------------------------------
# Telemetry integration: tracer sink + identity -> valid Chrome trace
# ---------------------------------------------------------------------------

class TestFlightTrace:
    def test_tracer_sink_to_valid_chrome_trace(self, tmp_path):
        tel = Telemetry(enabled=True, trace_id="exp-1",
                        process_name="trial-1")
        tel.attach_flight(FlightRecorder(str(tmp_path), segment_events=64))
        with tel.tracer.span("train_dispatch", step=0):
            pass
        tel.tracer.instant("step_time_anomaly", duration_s=0.5)
        tel.close()
        trace = flight_to_chrome_trace(str(tmp_path))
        assert validate_chrome_trace(trace) == []
        names = {e["name"] for e in trace["traceEvents"]}
        assert "train_dispatch" in names
        assert "step_time_anomaly" in names
        assert trace["otherData"]["source"] == "flight_recorder"

    def test_sink_sees_records_past_tracer_cap(self, tmp_path):
        """The in-memory ring keeps the HEAD; the black box must keep the
        TAIL — records past max_events still reach the flight sink."""
        tel = Telemetry(enabled=True, max_events=4)
        tel.attach_flight(FlightRecorder(str(tmp_path), segment_events=64))
        for i in range(10):
            with tel.tracer.span("step", i=i):
                pass
        tel.close()
        assert len(tel.tracer.events()) == 4  # in-memory capped
        spans = _spans(tmp_path)
        assert len(spans) == 10  # black box got them all
        assert spans[-1]["args"] == {"i": 9}


# ---------------------------------------------------------------------------
# kill -9 mid-training: the black box survives and the CLI reads it
# ---------------------------------------------------------------------------

FLIGHT_CHAOS_RUNNER = '''
import json, os, sys
sys.path.insert(0, {repo!r})
from determined_clone_tpu.utils.host_steering import steer_to_host_cpu
steer_to_host_cpu(8)
import jax
sys.path.insert(0, {testdir!r})
from test_fault_tolerance import DriftTrial, drift_config
from determined_clone_tpu import core
from determined_clone_tpu.config import ExperimentConfig
from determined_clone_tpu.parallel import MeshSpec, make_mesh
from determined_clone_tpu.training import Trainer, TrialContext

cfg = ExperimentConfig.from_dict(drift_config({storage!r}, batches=24))
mesh = make_mesh(MeshSpec(dp=1), jax.devices()[:1])
with core.init(config=cfg, trial_id=1) as cctx:
    ctx = TrialContext(config=cfg, hparams={{}}, core=cctx, mesh=mesh)
    result = Trainer(DriftTrial(ctx)).fit()
print("COMPLETED", result["batches_trained"])
'''


@pytest.mark.slow
def test_kill9_leaves_readable_flight_ring(tmp_path):
    """A subprocess trial with DCT_FLIGHT_DIR set is hard-killed mid-run
    (os._exit via an `exit` fault: no atexit, no flushes — kill -9
    semantics). The flight ring on disk must still hold the final pre-kill
    train_dispatch spans, and `dct debug flight` must merge it into a
    Chrome trace that passes structural validation — the post-mortem
    acceptance criterion of the observability issue."""
    storage = tmp_path / "ckpts"
    storage.mkdir()
    flight_dir = tmp_path / "flight"
    script = tmp_path / "chaos_run.py"
    script.write_text(FLIGHT_CHAOS_RUNNER.format(
        repo=REPO, testdir=os.path.join(REPO, "tests"),
        storage=str(storage)))
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "DCT_FLIGHT_DIR": str(flight_dir),
        # die right after the 13th step completes: the spans for steps
        # 1-13 are already through the sink when the process vanishes
        "DCT_FAULT_PLAN": json.dumps({"rules": [
            {"point": "training.post_step", "action": "exit",
             "nth": 13, "exit_code": 137}]}),
    }
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=420)
    assert proc.returncode == 137, proc.stdout + proc.stderr
    assert "COMPLETED" not in proc.stdout

    # the ring survived the un-flushed death and holds the hot-loop spans
    summary = flight_summary(str(flight_dir))
    assert summary["segments"] >= 1
    dispatches = summary["span_names"].get("train_dispatch", 0)
    assert dispatches >= 10, summary["span_names"]

    trace = flight_to_chrome_trace(str(flight_dir))
    assert validate_chrome_trace(trace) == []
    assert any(e["name"] == "train_dispatch"
               for e in trace["traceEvents"])

    # the operator-facing path: `dct debug flight DIR -o trace.json`
    from determined_clone_tpu.cli.cli import main as cli_main
    out = tmp_path / "postmortem.json"
    rc = cli_main(["debug", "flight", str(flight_dir), "-o", str(out)])
    assert rc == 0
    written = json.loads(out.read_text())
    assert validate_chrome_trace(written) == []
    assert any(e["name"] == "train_dispatch"
               for e in written["traceEvents"])
