"""Goodput ledger suite (telemetry/goodput.py, docs/observability.md).

Three layers, mirroring the ledger's own structure:

- unit: span→category bucketing, the compile-dedupe rule, anomaly
  overhang, explicit notes, and the conservation invariant (including a
  fabricated overcount — the only way to violate it);
- durability: the per-leg journal's kill -9 contract (line-buffered
  writes, torn-final-line tolerance, leg-number resume) and the
  restart-leg merge, where the dead time between legs must land in
  ``restart_backoff``, never as missing wall-clock;
- end-to-end: a real Trainer run must balance its books within the 1%
  tolerance (the ISSUE's enforced acceptance criterion), and a seeded
  kill -9 chaos run's merged lifetime account must attribute the
  injected restart to restart badput (@slow — the chaos lane).
"""
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from determined_clone_tpu import core, faults
from determined_clone_tpu.config import ExperimentConfig
from determined_clone_tpu.parallel import MeshSpec, make_mesh
from determined_clone_tpu.telemetry import telemetry_from_config
from determined_clone_tpu.telemetry.goodput import (
    CATEGORIES,
    RESTART_CATEGORIES,
    GoodputLedger,
    check_conservation,
    format_goodput,
    merge_goodput,
    read_goodput,
)
from determined_clone_tpu.telemetry.metrics import MetricsRegistry
from determined_clone_tpu.training import JaxTrial, Trainer, TrialContext

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The lane contract (run_tests.sh): with the telemetry plane switched
# off, every goodput test skips instead of failing — the ledger only
# exists when telemetry does.
pytestmark = pytest.mark.skipif(
    os.environ.get("DCT_TELEMETRY_DISABLED") == "1",
    reason="telemetry plane disabled (DCT_TELEMETRY_DISABLED=1)")


@pytest.fixture(autouse=True)
def clean_fault_state(monkeypatch):
    monkeypatch.delenv("DCT_FAULT_PLAN", raising=False)
    monkeypatch.delenv("DCT_GOODPUT_DIR", raising=False)
    monkeypatch.delenv("DCT_QUEUE_WAIT_S", raising=False)
    faults.reset()
    yield
    faults.reset()


def span(name, dur_s, *, depth=0, tid=0, **args):
    return {"name": name, "ts_us": 0.0, "dur_us": dur_s * 1e6,
            "tid": tid, "tname": "consumer", "depth": depth,
            "args": args}


def instant(name, **args):
    return {"name": name, "ph": "i", "ts_us": 0.0, "dur_us": 0.0,
            "tid": 0, "tname": "consumer", "depth": 1, "args": args}


# ---------------------------------------------------------------------------
# ledger unit behaviour
# ---------------------------------------------------------------------------

def test_span_bucketing_and_conservation():
    led = GoodputLedger(trial_id=3)
    led.observe_span(span("train_dispatch", 0.5, step=1))
    led.observe_span(span("dataload_wait", 0.2))
    led.observe_span(span("host_sync", 0.1))
    led.observe_span(span("validate", 0.3))
    led.observe_span(span("checkpoint_save", 0.4))
    # nested + producer-lane + unknown spans must NOT contribute
    led.observe_span(span("eval_dispatch", 9.0, depth=1))
    led.observe_span(span("storage_upload", 9.0, depth=1))
    led.observe_span(span("produce_batch", 9.0, tid=1))  # unmapped name
    snap = led.snapshot()
    cats = snap["categories"]
    assert cats["productive"] == pytest.approx(0.5)
    assert cats["data_wait"] == pytest.approx(0.2)
    assert cats["host_sync"] == pytest.approx(0.1)
    assert cats["validation"] == pytest.approx(0.3)
    assert cats["checkpoint_save"] == pytest.approx(0.4)
    assert set(cats) == set(CATEGORIES)
    # attributed (1.5s) exceeds the microseconds of real wall-clock this
    # test took — snapshot still balances because wall is measured, and
    # the fabricated history shows up as overcount, which conservation
    # rejects: the books can't invent time
    assert snap["overcount_s"] > 0
    assert not check_conservation(snap)["ok"]


def test_unattributed_is_the_remainder_and_books_balance():
    led = GoodputLedger()
    time.sleep(0.05)
    led.observe_span(span("train_dispatch", 0.01))
    snap = led.snapshot()
    cats = snap["categories"]
    assert cats["unattributed"] > 0
    assert sum(cats.values()) == pytest.approx(snap["wall_s"], rel=1e-6)
    res = check_conservation(snap)
    assert res["ok"] and res["error_fraction"] < 0.01
    assert snap["goodput_fraction"] == pytest.approx(
        cats["productive"] / snap["wall_s"])


def test_compile_dedupe_rules():
    """The wrap_jit contract: a compiled dispatch span and its synthesized
    same-interval xla_compile record are ONE interval — the dispatch is
    re-bucketed to compile, the synthesized record ignored; only the
    explicit AOT capture counts directly."""
    led = GoodputLedger()
    led.observe_span(span("train_dispatch", 0.8, compiled=True))
    led.observe_span(span("xla_compile", 0.8))          # synthesized twin
    led.observe_span(span("xla_compile", 0.3, explicit=True))  # AOT
    cats = led.snapshot()["categories"]
    assert cats["productive"] == 0.0
    assert cats["compile"] == pytest.approx(1.1)


def test_anomaly_overhang_moves_out_of_productive():
    led = GoodputLedger()
    led.observe_span(span("train_dispatch", 0.10))
    led.observe_span(span("train_dispatch", 0.55))  # the straggler
    led.observe_span(instant("step_time_anomaly",
                             duration_s=0.55, median_s=0.10, step=2))
    cats = led.snapshot()["categories"]
    assert cats["anomaly_overhang"] == pytest.approx(0.45)
    assert cats["productive"] == pytest.approx(0.20)
    # malformed / non-positive overhang instants are ignored
    led.observe_span(instant("step_time_anomaly", duration_s=0.05,
                             median_s=0.10))
    led.observe_span(instant("step_time_anomaly", duration_s="nan?"))
    assert led.snapshot()["categories"]["anomaly_overhang"] == \
        pytest.approx(0.45)


def test_anomaly_overhang_clamps_to_available_productive():
    led = GoodputLedger()
    led.observe_span(span("train_dispatch", 0.1))
    led.observe_span(instant("step_time_anomaly",
                             duration_s=5.0, median_s=0.5))
    cats = led.snapshot()["categories"]
    # moving more than productive holds would create negative time
    assert cats["productive"] == 0.0
    assert cats["anomaly_overhang"] == pytest.approx(0.1)


def test_note_validates_category_and_pre_wall_extends_wall():
    led = GoodputLedger()
    with pytest.raises(ValueError):
        led.note("coffee_break", 1.0)
    with pytest.raises(ValueError):
        led.note("unattributed", 1.0)  # remainder is computed, not noted
    epoch_before = led.snapshot()["wall_epoch_start"]
    led.note("queue_wait", 2.5, pre_wall=True)
    snap = led.snapshot()
    # queue wait predates the ledger: it extends the accountable wall so
    # conservation still balances, and shifts the epoch anchor back so
    # the merged-leg timeline stays gap-correct
    assert snap["wall_s"] > 2.5
    assert snap["categories"]["queue_wait"] == pytest.approx(2.5)
    assert snap["wall_epoch_start"] == pytest.approx(epoch_before - 2.5,
                                                     abs=0.05)
    assert check_conservation(snap)["ok"]


def test_publish_metrics_lands_gauges():
    reg = MetricsRegistry()
    led = GoodputLedger(registry=reg, trial_id=9)
    led.observe_span(span("train_dispatch", 0.01))
    snap = led.publish_metrics()
    dump = reg.dump()
    assert "goodput_seconds_total" in dump
    assert 'category="productive"' in dump
    assert "goodput_wall_seconds" in dump
    assert "goodput_fraction" in dump
    assert snap["trial_id"] == 9


# ---------------------------------------------------------------------------
# journal durability + merge
# ---------------------------------------------------------------------------

def test_journal_write_read_roundtrip_and_meta(tmp_path):
    led = GoodputLedger(trial_id=7)
    led.attach_journal(str(tmp_path))
    led.observe_span(span("train_dispatch", 0.02))
    led.publish_metrics()
    led.observe_span(span("train_dispatch", 0.03))
    led.close()
    files = [n for n in os.listdir(tmp_path) if n.endswith(".jsonl")]
    assert files == ["goodput-trial00007-leg00001.jsonl"]
    lines = (tmp_path / files[0]).read_text().splitlines()
    meta = json.loads(lines[0])
    assert meta["kind"] == "meta" and meta["trial_id"] == 7
    assert meta["leg"] == 1
    recs = list(read_goodput(str(tmp_path)))
    assert len(recs) == 1
    # cumulative: the reader takes the LAST snapshot (close's final line)
    assert recs[0]["categories"]["productive"] == pytest.approx(0.05)
    assert recs[0]["trial_id"] == 7 and recs[0]["leg"] == 1


def test_journal_resumes_leg_numbering(tmp_path):
    for expected_leg in (1, 2, 3):
        led = GoodputLedger(trial_id=4)
        led.attach_journal(str(tmp_path))
        led.publish_metrics()
        assert led.journal.leg == expected_leg
        led.close()
    # a different trial starts its own leg sequence in the same dir
    other = GoodputLedger(trial_id=5)
    other.attach_journal(str(tmp_path))
    other.publish_metrics()
    assert other.journal.leg == 1
    other.close()
    legs = sorted((r["trial_id"], r["leg"])
                  for r in read_goodput(str(tmp_path)))
    assert legs == [(4, 1), (4, 2), (4, 3), (5, 1)]


def test_reader_tolerates_torn_final_line(tmp_path):
    led = GoodputLedger(trial_id=2)
    led.attach_journal(str(tmp_path))
    led.observe_span(span("train_dispatch", 0.04))
    led.publish_metrics()
    led.close()
    path = tmp_path / "goodput-trial00002-leg00001.jsonl"
    with open(path, "a") as f:
        f.write('{"kind": "goodput", "wall_s": 99.0, "catego')  # mid-crash
    recs = list(read_goodput(str(tmp_path)))
    assert len(recs) == 1
    assert recs[0]["wall_s"] != 99.0  # the torn line never surfaced


def test_journal_write_fault_drops_and_counts(tmp_path):
    reg = MetricsRegistry()
    led = GoodputLedger(registry=reg, trial_id=1)
    led.attach_journal(str(tmp_path))
    with faults.plan_active({"rules": [
            {"point": "goodput.write", "nth": 1, "times": 1}]}):
        led.publish_metrics()   # injected write error: dropped, not raised
        led.publish_metrics()   # plan exhausted: lands
    assert led.journal.records_dropped == 1
    assert reg.counter("goodput_records_dropped").value == 1
    assert len(list(read_goodput(str(tmp_path)))) == 1


def hand_leg(trial, leg, start, wall, **cats):
    """Write a synthetic journal leg: categories + computed remainder."""
    categories = {c: 0.0 for c in CATEGORIES}
    categories.update(cats)
    categories["unattributed"] = max(
        0.0, wall - sum(v for k, v in categories.items()
                        if k != "unattributed"))
    return {"kind": "goodput", "trial_id": trial, "leg": leg,
            "wall_s": wall, "wall_epoch_start": start,
            "wall_epoch": start + wall, "categories": categories,
            "overcount_s": 0.0,
            "goodput_fraction": categories["productive"] / wall}


def write_leg(directory, rec):
    path = os.path.join(
        directory, f"goodput-trial{rec['trial_id']:05d}"
                   f"-leg{rec['leg']:05d}.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "meta"}) + "\n")
        f.write(json.dumps(rec) + "\n")


def test_merge_attributes_inter_leg_gap_to_restart_backoff(tmp_path):
    # leg 1: 0→10s; gap of 6s (backoff + respawn); leg 2: 16→46s
    write_leg(str(tmp_path), hand_leg(7, 1, 1000.0, 10.0,
                                      productive=8.0, compile=1.0))
    write_leg(str(tmp_path), hand_leg(7, 2, 1016.0, 30.0,
                                      productive=24.0, restore_replay=3.0))
    merged = merge_goodput(str(tmp_path))
    acct = merged[7]
    assert acct["legs"] == 2
    assert acct["wall_s"] == pytest.approx(46.0)  # 10 + 6 gap + 30
    cats = acct["categories"]
    assert cats["restart_backoff"] == pytest.approx(6.0)
    assert cats["productive"] == pytest.approx(32.0)
    assert cats["restore_replay"] == pytest.approx(3.0)
    # the merged account balances too: no second went missing
    assert sum(cats.values()) == pytest.approx(acct["wall_s"])
    assert acct["goodput_fraction"] == pytest.approx(32.0 / 46.0)
    assert acct["conservation_ok"]
    text = format_goodput(merged)
    assert "trial 7" in text and "restart_backoff" in text


def test_merge_flags_violated_leg_and_ignores_clock_skew(tmp_path):
    bad = hand_leg(3, 1, 1000.0, 5.0, productive=4.0)
    bad["categories"]["productive"] = 9.0  # cook the books: overcount
    write_leg(str(tmp_path), bad)
    # leg 2 starts BEFORE leg 1 ended (clock skew): gap clamps to 0
    write_leg(str(tmp_path), hand_leg(3, 2, 1003.0, 5.0, productive=4.0))
    acct = merge_goodput(str(tmp_path))[3]
    assert not acct["conservation_ok"]
    assert acct["categories"]["restart_backoff"] == 0.0
    assert acct["wall_s"] == pytest.approx(10.0)


# ---------------------------------------------------------------------------
# wiring: telemetry_from_config, env contracts, aggregator, master, CLI
# ---------------------------------------------------------------------------

def obs_config(**extra):
    return {"observability": {"enabled": True, **extra}}


def test_telemetry_wires_ledger_as_tracer_sink():
    tel = telemetry_from_config(obs_config())
    try:
        assert tel.goodput is not None
        with tel.tracer.span("train_dispatch", step=1):
            time.sleep(0.01)
        assert tel.goodput.snapshot()["categories"]["productive"] > 0
    finally:
        tel.close()


def test_goodput_dir_env_force_enables_and_journals(tmp_path, monkeypatch):
    monkeypatch.setenv("DCT_GOODPUT_DIR", str(tmp_path))
    tel = telemetry_from_config({})  # observability NOT enabled in config
    try:
        assert tel is not None and tel.goodput is not None
        tel.goodput.set_identity(trial_id=11)
        tel.publish(None, 4)
    finally:
        tel.close()
    recs = list(read_goodput(str(tmp_path)))
    assert [r["trial_id"] for r in recs] == [11]


def test_queue_wait_env_contract(monkeypatch):
    monkeypatch.setenv("DCT_QUEUE_WAIT_S", "1.75")
    tel = telemetry_from_config(obs_config())
    try:
        snap = tel.goodput.snapshot()
        assert snap["categories"]["queue_wait"] == pytest.approx(1.75)
        assert snap["wall_s"] > 1.75  # pre-wall time extends the account
        assert check_conservation(snap)["ok"]
    finally:
        tel.close()
    # garbage values are ignored, not fatal: telemetry must never kill
    monkeypatch.setenv("DCT_QUEUE_WAIT_S", "soon")
    tel = telemetry_from_config(obs_config())
    try:
        assert tel.goodput.snapshot()["categories"]["queue_wait"] == 0.0
    finally:
        tel.close()


def test_telemetry_disabled_env_wins(monkeypatch):
    monkeypatch.setenv("DCT_TELEMETRY_DISABLED", "1")
    monkeypatch.setenv("DCT_GOODPUT_DIR", "/tmp/nope")  # force-enable loses
    assert telemetry_from_config(obs_config()) is None


def ship_trial_snapshot(agg, trial_id, *, productive, wall,
                        experiment_id=None, **extra_cats):
    reg = MetricsRegistry()
    led = GoodputLedger(registry=reg, trial_id=trial_id)
    led.note("productive", productive)
    for cat, secs in extra_cats.items():
        led.note(cat, secs)
    snap = led.publish_metrics()
    # override the gauges' measured wall with the scenario's: the rollup
    # must reproduce whatever the trial shipped, not re-derive it
    reg.gauge("goodput_wall_seconds", "").set(wall)
    reg.gauge("goodput_fraction", "").set(productive / wall)
    agg.ingest(trial_id, [{"time": 1.0, "group": "telemetry",
                           "metrics": reg.snapshot()}],
               experiment_id=experiment_id)
    return snap


def test_aggregator_rollup_is_time_weighted():
    from determined_clone_tpu.telemetry.aggregate import (
        ClusterMetricsAggregator,
    )

    agg = ClusterMetricsAggregator()
    # busy trial: 90% goodput over 100s; idle trial: 10% over 10s
    ship_trial_snapshot(agg, 1, productive=90.0, wall=100.0,
                        experiment_id=5, checkpoint_save=5.0)
    ship_trial_snapshot(agg, 2, productive=1.0, wall=10.0, experiment_id=6)
    roll = agg.goodput_rollup()
    assert set(roll["by_trial"]) == {"1", "2"}
    assert roll["by_trial"]["1"]["experiment_id"] == 5
    assert roll["by_trial"]["1"]["categories"]["checkpoint_save"] == \
        pytest.approx(5.0)
    assert roll["wall_total_s"] == pytest.approx(110.0)
    # time-weighted: (90+1)/110, NOT the 0.5 a plain average would give
    assert roll["cluster_fraction"] == pytest.approx(91.0 / 110.0)
    summary = agg.summary()
    assert summary["goodput"]["cluster_fraction"] == \
        pytest.approx(91.0 / 110.0)
    dump = agg.dump()
    assert 'dct_goodput_fraction{trial_id="1"}' in dump
    assert "dct_goodput_cluster_fraction" in dump


def test_master_goodput_route_and_cli(tmp_path, capsys):
    from determined_clone_tpu.api.inprocess import InProcessMaster
    from determined_clone_tpu.cli.cli import main as cli_main

    master = InProcessMaster()
    master.register_trial(1, 5)
    ship_trial_snapshot(master.aggregator, 1, productive=8.0, wall=10.0,
                        experiment_id=5)
    status, roll, ctype = master.handle("GET", "/api/v1/cluster/goodput")
    assert status == 200 and ctype == "application/json"
    assert roll["by_trial"]["1"]["goodput_fraction"] == pytest.approx(0.8)

    # offline CLI path: merge a journal directory (sleep past the span's
    # fabricated duration so the leg's books genuinely balance)
    led = GoodputLedger(trial_id=1)
    led.attach_journal(str(tmp_path))
    time.sleep(0.03)
    led.observe_span(span("train_dispatch", 0.02))
    led.close()
    assert cli_main(["goodput", "--dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "trial 1" in out and "productive" in out
    assert cli_main(["goodput", "--dir", str(tmp_path), "--json"]) == 0
    accounts = json.loads(capsys.readouterr().out)
    assert accounts["1"]["conservation_ok"] is True
    # empty directory: exit 1, not a stack trace
    empty = tmp_path / "empty"
    empty.mkdir()
    assert cli_main(["goodput", "--dir", str(empty)]) == 1


# ---------------------------------------------------------------------------
# end-to-end: a real trainer run balances its books (tier-1 acceptance)
# ---------------------------------------------------------------------------

class DriftTrial(JaxTrial):
    """Same shape as the fault-tolerance suite's drift trial: loss depends
    on batch content so replay mistakes would change the final params."""

    n_batches = 24

    def initial_params(self, rng):
        return {"w": jnp.zeros(())}

    def optimizer(self):
        return optax.sgd(0.05)

    def loss(self, params, batch, rng):
        target = jnp.mean(batch)
        loss = (params["w"] - target) ** 2
        return loss, {"w": params["w"]}

    def training_data(self):
        for i in range(self.n_batches):
            yield np.full((4, 1), float(i % 7), np.float32)

    def validation_data(self):
        return [np.ones((4, 1), np.float32)]

    @property
    def global_batch_size(self):
        return 4


def drift_config(storage, batches=24):
    return {
        "searcher": {"name": "single", "metric": "loss",
                     "max_length": {"batches": batches}},
        "scheduling_unit": 4,
        "min_checkpoint_period": {"batches": 8},
        "checkpoint_storage": {"type": "shared_fs",
                               "host_path": str(storage)},
        "optimizations": {"prefetch_depth": 0},
        "observability": {"enabled": True},
    }


def run_trial(storage, *, latest=None, trial_id=1):
    """One trainer leg with goodput accounting; returns the final ledger
    snapshot taken inside the core context (close() writes the journal's
    last line after this)."""
    cfg = ExperimentConfig.from_dict(drift_config(storage))
    mesh = make_mesh(MeshSpec(dp=1), jax.devices()[:1])
    with core.init(config=cfg, trial_id=trial_id) as cctx:
        ctx = TrialContext(config=cfg, hparams={}, core=cctx, mesh=mesh)
        result = Trainer(DriftTrial(ctx)).fit(latest_checkpoint=latest)
        snap = cctx.telemetry.goodput.snapshot()
    return result, snap


def test_real_trainer_run_conserves_wall_clock(tmp_path):
    """The ISSUE's enforced acceptance criterion: on a real run the
    categories sum to wall-clock within 1%, goodput_fraction is non-null,
    and the external stopwatch agrees with the ledger's wall."""
    t0 = time.perf_counter()
    result, snap = run_trial(tmp_path)
    external_wall = time.perf_counter() - t0
    assert result["batches_trained"] == 24
    res = check_conservation(snap)
    assert res["ok"], res
    assert snap["overcount_s"] == 0.0
    assert snap["goodput_fraction"] is not None
    assert snap["goodput_fraction"] > 0
    # the ledger is born inside core.init, so its wall is a subset of the
    # external measurement — it must never exceed it
    assert snap["wall_s"] <= external_wall + 0.01
    cats = snap["categories"]
    assert cats["productive"] > 0
    assert cats["checkpoint_save"] > 0      # batches 8/16/24 committed
    assert cats["restart_backoff"] == 0.0   # uninterrupted
    assert cats["restore_replay"] == 0.0


# ---------------------------------------------------------------------------
# chaos: kill -9, restart, merge — injected death is restart badput
# ---------------------------------------------------------------------------

GOODPUT_CHAOS_RUNNER = '''
import json, os, sys
sys.path.insert(0, {repo!r})
from determined_clone_tpu.utils.host_steering import steer_to_host_cpu
steer_to_host_cpu(8)
import jax
sys.path.insert(0, {testdir!r})
from test_goodput import DriftTrial, drift_config
from determined_clone_tpu import core
from determined_clone_tpu.config import ExperimentConfig
from determined_clone_tpu.parallel import MeshSpec, make_mesh
from determined_clone_tpu.training import Trainer, TrialContext

latest = os.environ.get("DCT_RESUME_FROM") or None
cfg = ExperimentConfig.from_dict(drift_config({storage!r}, batches=24))
mesh = make_mesh(MeshSpec(dp=1), jax.devices()[:1])
with core.init(config=cfg, trial_id=1) as cctx:
    ctx = TrialContext(config=cfg, hparams={{}}, core=cctx, mesh=mesh)
    result = Trainer(DriftTrial(ctx)).fit(latest_checkpoint=latest)
print("COMPLETED", result["batches_trained"])
'''


def chaos_env(goodput_dir, **extra):
    return {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "DCT_GOODPUT_DIR": str(goodput_dir),
        **extra,
    }


@pytest.mark.slow
def test_kill9_restart_legs_merge_into_restart_badput(tmp_path):
    """The full durability story: leg 1 is hard-killed on step 13 (after
    the batch-8 journal line is already on disk, line-buffered), leg 2
    resumes from the batch-8 checkpoint and completes. merge_goodput must
    fold both legs plus the dead time between them into one account whose
    books balance — the injected restart shows up as restart badput
    (restart_backoff gap + restore_replay), never as missing time — and
    whose totals match an uninterrupted baseline up to the measured
    restart overhead."""
    storage = tmp_path / "ckpts"
    storage.mkdir()
    goodput_dir = tmp_path / "goodput"
    script = tmp_path / "chaos_run.py"
    script.write_text(GOODPUT_CHAOS_RUNNER.format(
        repo=REPO, testdir=os.path.join(REPO, "tests"),
        storage=str(storage)))

    # leg 1: die on the 13th step dispatch — after the batch-8 commit and
    # its chunk-boundary journal writes, kill -9 semantics (os._exit)
    env = chaos_env(goodput_dir, DCT_FAULT_PLAN=json.dumps({"rules": [
        {"point": "training.pre_step", "action": "exit",
         "nth": 13, "exit_code": 137}]}))
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=420)
    assert proc.returncode == 137, proc.stdout + proc.stderr
    legs = list(read_goodput(str(goodput_dir)))
    assert len(legs) == 1  # the dead leg's journal survived the kill
    assert legs[0]["leg"] == 1
    assert check_conservation(legs[0])["ok"]

    # leg 2: resume from the committed batch-8 checkpoint, run to the end
    reg = core.LocalCheckpointRegistry(str(storage / "checkpoints.jsonl"))
    recs = reg.list()
    assert len(recs) == 1
    assert recs[0]["metadata"]["steps_completed"] == 8
    env = chaos_env(goodput_dir, DCT_RESUME_FROM=recs[0]["storage_id"])
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "COMPLETED 24" in proc.stdout

    # uninterrupted baseline: same script rendered with its own storage
    # and journal dir, so the two runs differ only in the injected fault
    baseline_storage = tmp_path / "baseline-ckpts"
    baseline_storage.mkdir()
    baseline_goodput = tmp_path / "baseline-goodput"
    baseline_script = tmp_path / "baseline_run.py"
    baseline_script.write_text(GOODPUT_CHAOS_RUNNER.format(
        repo=REPO, testdir=os.path.join(REPO, "tests"),
        storage=str(baseline_storage)))
    env = chaos_env(baseline_goodput)
    proc = subprocess.run([sys.executable, str(baseline_script)], env=env,
                          capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "COMPLETED 24" in proc.stdout

    merged = merge_goodput(str(goodput_dir))
    assert list(merged) == [1]
    acct = merged[1]
    assert acct["legs"] == 2
    assert acct["conservation_ok"], acct
    cats = acct["categories"]
    # every leg's books balance AND the merged ones do: nothing missing
    assert sum(cats.values()) == pytest.approx(acct["wall_s"], rel=0.01)
    # the injected death is restart badput...
    restart_badput = sum(cats[c] for c in RESTART_CATEGORIES)
    assert restart_badput > 0, cats
    assert cats["restart_backoff"] > 0  # the inter-leg dead time

    baseline = merge_goodput(str(baseline_goodput))[1]
    assert baseline["legs"] == 1
    assert baseline["conservation_ok"]
    base_cats = baseline["categories"]
    base_restart = sum(base_cats[c] for c in RESTART_CATEGORIES)
    assert base_restart == pytest.approx(0.0, abs=0.01)
    # ...and NOT unattributed: the chaos run may carry up to one extra
    # process startup of unattributed glue versus the baseline (two legs,
    # two startups), but the restart gap itself must not leak into it
    overhead = acct["wall_s"] - baseline["wall_s"]
    assert cats["unattributed"] <= (
        2.0 * base_cats["unattributed"] + 0.25 * max(overhead, 0.0) + 2.0)
    # merged productive ≈ baseline productive + the replayed batches'
    # re-training (legs trained 12 + 16 batches vs 24): generous bound
    assert cats["productive"] <= base_cats["productive"] * 2.0 + 2.0
    assert cats["productive"] >= base_cats["productive"] * 0.3
