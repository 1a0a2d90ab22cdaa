"""Chaos suite: deterministic fault injection driving the crash-consistent
checkpoint commit protocol and the unified retry/backoff layer
(docs/fault_tolerance.md).

Every scenario here is seeded — a FaultPlan's rule RNGs derive from
(seed, rule index), so a failing chaos run reproduces exactly from its
seed. The invariant under test throughout: **restore never loads a
partial checkpoint** — any save interrupted before its COMMIT marker is
refused with CheckpointCorruptError and callers fall back to the last
committed state.
"""
import json
import logging
import os
import random
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
from determined_clone_tpu.config.experiment import ConfigError
from determined_clone_tpu.core._checkpoint import (
    CheckpointCorruptError,
    validate_checkpoint_dir,
)
from determined_clone_tpu.experiment import LocalExperimentRunner
from determined_clone_tpu.parallel import MeshSpec, make_mesh
from determined_clone_tpu.storage import transfer
from determined_clone_tpu.storage.base import (
    COMMIT_FILE,
    STORAGE_IO_POLICY,
    SharedFSStorageManager,
)
from determined_clone_tpu.training import JaxTrial, Trainer, TrialContext
from determined_clone_tpu.utils import retry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def pin_sequential_pool(monkeypatch):
    """Force the shared transfer pool inline/in-order for this test.

    Fault rules that target the Nth hit of a transfer point (or mirror a
    seeded RNG draw-for-draw) need per-file order to be deterministic;
    parallel workers would race the hit counter. monkeypatch restores the
    real pool afterwards."""
    monkeypatch.setattr(transfer, "_pool",
                        transfer.TransferPool(workers=0))


@pytest.fixture(autouse=True)
def clean_fault_state(monkeypatch):
    """Every test starts with no active plan, empty plan caches, and a
    clean retry-stats table; DCT_FAULT_PLAN never leaks in from outside."""
    monkeypatch.delenv("DCT_FAULT_PLAN", raising=False)
    faults.reset()
    retry.reset_stats()
    yield
    faults.reset()
    retry.reset_stats()


# ---------------------------------------------------------------------------
# FaultPlan mechanics
# ---------------------------------------------------------------------------

def fired_pattern(plan, point, hits):
    out = []
    for _ in range(hits):
        try:
            plan.hit(point)
            out.append(False)
        except faults.FaultInjected:
            out.append(True)
    return out


def test_nth_and_times_fire_window():
    plan = faults.plan_from_dict({"rules": [
        {"point": "storage.upload", "nth": 2, "times": 2}]})
    assert fired_pattern(plan, "storage.upload", 5) == \
        [False, True, True, False, False]
    assert plan.stats() == [{"point": "storage.upload", "action": "error",
                             "hits": 5, "fires": 2}]


def test_times_zero_is_unlimited_and_glob_matches():
    plan = faults.plan_from_dict({"rules": [
        {"point": "storage.*", "nth": 1, "times": 0}]})
    assert fired_pattern(plan, "storage.download", 4) == [True] * 4
    # non-matching points never fire
    plan.hit("api.request")


def test_seeded_probability_is_reproducible():
    raw = {"seed": 42, "rules": [
        {"point": "p", "times": 0, "probability": 0.5}]}
    a = fired_pattern(faults.plan_from_dict(raw), "p", 32)
    b = fired_pattern(faults.plan_from_dict(raw), "p", 32)
    assert a == b
    assert True in a and False in a  # the coin actually flips at p=0.5


def test_injected_exception_types_map_to_retryability():
    for exc, types in [("fault", (faults.FaultInjected,)),
                       ("io", (faults.FaultInjected, OSError)),
                       ("conn", (faults.FaultInjected, ConnectionError))]:
        plan = faults.plan_from_dict({"rules": [{"point": "p", "exc": exc}]})
        with pytest.raises(types):
            plan.hit("p")
    # plain "fault" must NOT be retryable under the default policy
    plan = faults.plan_from_dict({"rules": [{"point": "p"}]})
    try:
        plan.hit("p")
    except faults.FaultInjected as e:
        assert not isinstance(e, retry.DEFAULT_RETRYABLE)


def test_point_is_noop_without_plan_and_truncate_is_separate():
    faults.point("anything.at.all")  # no plan: must be free and silent
    plan = faults.activate(faults.plan_from_dict({"rules": [
        {"point": "p", "action": "truncate", "keep_bytes": 3}]}))
    # truncate rules never raise from point(); only truncate_bytes consults
    faults.point("p")
    assert faults.truncate_bytes("p") == 3
    assert faults.truncate_bytes("p") is None  # times=1 exhausted
    faults.deactivate(plan)


def test_env_install_caches_plan_and_counters(monkeypatch, tmp_path):
    payload = json.dumps({"rules": [{"point": "p", "nth": 2}]})
    monkeypatch.setenv("DCT_FAULT_PLAN", payload)
    plan1 = faults.install_from_env()
    plan1.hit("p")  # hit 1: below nth, doesn't fire
    plan2 = faults.install_from_env()
    assert plan2 is plan1  # cached by payload: counters carried over
    with pytest.raises(faults.FaultInjected):
        plan2.hit("p")
    # a file path works too
    f = tmp_path / "plan.json"
    f.write_text(payload)
    monkeypatch.setenv("DCT_FAULT_PLAN", str(f))
    assert faults.install_from_env() is not plan1


def test_config_faults_block_roundtrip_and_validation(tmp_path):
    cfg = ExperimentConfig.from_dict({
        "searcher": {"name": "single", "metric": "loss",
                     "max_length": {"batches": 4}},
        "checkpoint_storage": {"type": "shared_fs",
                               "host_path": str(tmp_path)},
        "faults": {"seed": 7, "rules": [
            {"point": "storage.upload", "exc": "io", "times": 2}]},
    })
    assert cfg.faults.seed == 7
    d = cfg.to_dict()
    assert d["faults"]["rules"][0]["point"] == "storage.upload"
    assert ExperimentConfig.from_dict(d).faults.rules == cfg.faults.rules
    with pytest.raises(ConfigError):
        ExperimentConfig.from_dict({
            "searcher": {"name": "single", "metric": "loss",
                         "max_length": {"batches": 4}},
            "faults": {"rules": [{"point": "p", "action": "explode"}]},
        })


# ---------------------------------------------------------------------------
# unified retry/backoff
# ---------------------------------------------------------------------------

def test_backoff_sequence_without_jitter():
    p = retry.RetryPolicy(name="t", base_delay_s=0.1, multiplier=2.0,
                          max_delay_s=0.5, jitter="none")
    assert [p.backoff(f) for f in range(1, 6)] == \
        [0.1, 0.2, 0.4, 0.5, 0.5]


def test_full_jitter_draws_below_exponential_cap():
    p = retry.RetryPolicy(name="t", base_delay_s=0.1, multiplier=2.0,
                          max_delay_s=5.0)
    rng, mirror = random.Random(123), random.Random(123)
    drawn = [p.backoff(f, rng=rng) for f in range(1, 5)]
    expect = [mirror.uniform(0.0, min(5.0, 0.1 * 2.0 ** (f - 1)))
              for f in range(1, 5)]
    # each draw is mirrored exactly and bounded by its cap
    for f, (got, want) in enumerate(zip(drawn, expect), start=1):
        assert got == want
        assert 0.0 <= got <= 0.1 * 2.0 ** (f - 1)


def test_retry_call_sleeps_then_succeeds_and_records():
    p = retry.RetryPolicy(name="unit", max_attempts=4, base_delay_s=0.1,
                          jitter="none")
    calls = {"n": 0}
    sleeps = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise ConnectionError("transient")
        return "ok"

    assert retry.retry_call(flaky, policy=p, sleep=sleeps.append) == "ok"
    assert sleeps == [0.1, 0.2]
    assert retry.stats()["unit"] == 2


def test_retry_call_exhaustion_and_non_retryable():
    p = retry.RetryPolicy(name="unit", max_attempts=3, jitter="none")

    def always():
        raise TimeoutError("down")

    with pytest.raises(TimeoutError):
        retry.retry_call(always, policy=p, sleep=lambda s: None)

    calls = {"n": 0}

    def raises_value_error():
        calls["n"] += 1
        raise ValueError("not transient")

    with pytest.raises(ValueError):
        retry.retry_call(raises_value_error, policy=p,
                         sleep=lambda s: None)
    assert calls["n"] == 1  # never retried


def test_retry_call_deadline_caps_and_stops():
    p = retry.RetryPolicy(name="unit", max_attempts=100, base_delay_s=10.0,
                          jitter="none", deadline_s=0.0)

    def always():
        raise ConnectionError("down")

    t0 = time.monotonic()
    with pytest.raises(ConnectionError):
        retry.retry_call(always, policy=p)
    assert time.monotonic() - t0 < 1.0  # gave up at the deadline, no sleep


# ---------------------------------------------------------------------------
# storage: flaky uploads retry with the policy's exact backoff
# ---------------------------------------------------------------------------

def test_flaky_upload_retries_and_resumes(tmp_path, monkeypatch):
    pin_sequential_pool(monkeypatch)
    src = tmp_path / "src"
    src.mkdir()
    (src / "a.bin").write_bytes(b"aaaa")
    (src / "b.bin").write_bytes(b"bbbb")
    mgr = SharedFSStorageManager(str(tmp_path / "store"))

    sleeps = []
    monkeypatch.setattr(retry, "_sleep", sleeps.append)
    monkeypatch.setattr(retry, "_rng", random.Random(5))
    mirror = random.Random(5)

    # first file's copy fails twice (io = retryable), then all succeed
    with faults.plan_active({"rules": [
            {"point": "storage.upload", "exc": "io", "nth": 1,
             "times": 2}]}):
        mgr.upload(str(src), "ck-1")

    assert mgr.list_files("ck-1") == {"a.bin": 4, "b.bin": 4}
    # two retries, each delay drawn from the storage policy's jitter window
    assert sleeps == [STORAGE_IO_POLICY.backoff(1, rng=mirror),
                      STORAGE_IO_POLICY.backoff(2, rng=mirror)]
    assert retry.stats()["storage_io"] == 2


def test_flaky_upload_exhausts_to_caller(tmp_path, monkeypatch):
    src = tmp_path / "src"
    src.mkdir()
    (src / "a.bin").write_bytes(b"aaaa")
    mgr = SharedFSStorageManager(str(tmp_path / "store"))
    monkeypatch.setattr(retry, "_sleep", lambda s: None)
    with faults.plan_active({"rules": [
            {"point": "storage.upload", "exc": "io", "times": 0}]}):
        with pytest.raises(faults.InjectedIOError):
            mgr.upload(str(src), "ck-1")


# ---------------------------------------------------------------------------
# commit protocol
# ---------------------------------------------------------------------------

def make_core(tmp_path, trial_id=1):
    cfg = ExperimentConfig.from_dict({
        "searcher": {"name": "single", "metric": "loss",
                     "max_length": {"batches": 4}},
        "checkpoint_storage": {"type": "shared_fs",
                               "host_path": str(tmp_path)},
    })
    return core.init(config=cfg, trial_id=trial_id)


def test_upload_commits_manifest_and_marker(tmp_path):
    with make_core(tmp_path) as cctx:
        with cctx.checkpoint.store_path() as (path, holder):
            with open(os.path.join(path, "weights.bin"), "wb") as f:
                f.write(b"\x01" * 64)
        sid = holder["storage_id"]
        stored = tmp_path / sid
        assert (stored / COMMIT_FILE).exists()
        manifest = json.loads((stored / "manifest.json").read_text())
        assert manifest["storage_id"] == sid
        assert manifest["files"]["weights.bin"]["size"] == 64
        # protocol files never list themselves
        assert COMMIT_FILE not in manifest["files"]
        assert "manifest.json" not in manifest["files"]
        with cctx.checkpoint.restore_path(sid) as rpath:
            assert open(os.path.join(rpath, "weights.bin"), "rb"
                        ).read() == b"\x01" * 64
        assert cctx.checkpoint.committed_checkpoints() == [sid]


def test_uncommitted_checkpoint_is_refused(tmp_path):
    with make_core(tmp_path) as cctx:
        with cctx.checkpoint.store_path() as (path, holder):
            with open(os.path.join(path, "weights.bin"), "wb") as f:
                f.write(b"\x02" * 16)
        sid = holder["storage_id"]
        os.unlink(tmp_path / sid / COMMIT_FILE)  # simulate crash pre-commit
        with pytest.raises(CheckpointCorruptError) as ei:
            with cctx.checkpoint.restore_path(sid):
                pass
        assert "no COMMIT marker" in str(ei.value)
        assert ei.value.storage_id == sid


def test_torn_write_detected_by_manifest(tmp_path, monkeypatch):
    pin_sequential_pool(monkeypatch)
    monkeypatch.setattr(retry, "_sleep", lambda s: None)
    with make_core(tmp_path) as cctx:
        # truncate the 2nd uploaded file (manifest goes first, then data)
        with faults.plan_active({"rules": [
                {"point": "storage.upload", "action": "truncate",
                 "nth": 2, "keep_bytes": 3}]}):
            with cctx.checkpoint.store_path() as (path, holder):
                with open(os.path.join(path, "weights.bin"), "wb") as f:
                    f.write(b"\x03" * 32)
        sid = holder["storage_id"]
        # committed — but the manifest convicts the torn file on restore
        assert (tmp_path / sid / COMMIT_FILE).exists()
        with pytest.raises(CheckpointCorruptError) as ei:
            with cctx.checkpoint.restore_path(sid):
                pass
        assert "torn write" in ei.value.reason


def test_commit_fault_leaves_checkpoint_unpublished(tmp_path):
    with make_core(tmp_path) as cctx:
        with pytest.raises(faults.FaultInjected):
            with faults.plan_active({"rules": [
                    {"point": "storage.commit"}]}):
                with cctx.checkpoint.store_path() as (path, _):
                    with open(os.path.join(path, "w.bin"), "wb") as f:
                        f.write(b"\x04" * 8)
        # nothing published: restore-fallback candidates stay empty, and
        # the on-disk leftover is refused by validation
        assert cctx.checkpoint.committed_checkpoints() == []
        leftovers = SharedFSStorageManager(str(tmp_path)).list_storage_ids()
        dirs = [d for d in leftovers if (tmp_path / d / "w.bin").exists()]
        assert dirs
        with pytest.raises(CheckpointCorruptError):
            validate_checkpoint_dir(str(tmp_path / dirs[0]))


def test_validate_rejects_empty_and_accepts_legacy(tmp_path):
    legacy = tmp_path / "legacy"
    legacy.mkdir()
    (legacy / "state.bin").write_bytes(b"old")
    # pre-protocol checkpoint: nothing to verify, but not refused
    assert validate_checkpoint_dir(str(legacy)) is False
    empty = tmp_path / "empty"
    empty.mkdir()
    with pytest.raises(CheckpointCorruptError):
        validate_checkpoint_dir(str(empty))


@pytest.mark.parametrize("seed", [7, 11])
def test_interrupted_saves_never_restorable(tmp_path, monkeypatch, seed):
    """The core chaos invariant, on two seeds: under random injected
    storage failures, every checkpoint id on disk is either committed
    (and fully validates) or is refused by restore — there is no third
    state where a partial save loads."""
    pin_sequential_pool(monkeypatch)
    monkeypatch.setattr(retry, "_sleep", lambda s: None)
    with make_core(tmp_path) as cctx:
        ck = cctx.checkpoint
        # p=0.5 per copy attempt: with 4 attempts/file some files pull
        # through and some uploads die partway — the interesting mix
        with faults.plan_active({"seed": seed, "rules": [
                {"point": "storage.upload", "exc": "io", "times": 0,
                 "probability": 0.5}]}):
            outcomes = []
            for i in range(8):
                try:
                    with ck.store_path() as (path, holder):
                        for j in range(3):
                            with open(os.path.join(path, f"f{j}.bin"),
                                      "wb") as f:
                                f.write(bytes([i]) * 128)
                    outcomes.append(("ok", holder["storage_id"]))
                except OSError:
                    outcomes.append(("failed", None))
        assert {o for o, _ in outcomes} == {"ok", "failed"}, \
            f"seed {seed} produced no failure/success mix: {outcomes}"

        committed = set(ck.committed_checkpoints())
        on_disk = SharedFSStorageManager(str(tmp_path)).list_storage_ids()
        ckpt_dirs = [d for d in on_disk
                     if d != "checkpoints.jsonl" and (tmp_path / d).is_dir()]
        assert committed <= set(ckpt_dirs)
        for sid in ckpt_dirs:
            if sid in committed:
                with ck.restore_path(sid) as path:  # validates
                    assert sorted(os.listdir(path)) == \
                        ["COMMIT", "f0.bin", "f1.bin", "f2.bin",
                         "manifest.json", "metadata.json"]
            else:
                with pytest.raises(CheckpointCorruptError):
                    with ck.restore_path(sid):
                        pass


# ---------------------------------------------------------------------------
# trainer: restore falls back past a refused checkpoint
# ---------------------------------------------------------------------------

class DriftTrial(JaxTrial):
    """Loss depends on the batch content, so replay/skip mistakes after a
    restore change the final params — resume equivalence is a real check."""

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


def drift_config(tmp_path, batches=24):
    return {
        "searcher": {"name": "single", "metric": "loss",
                     "max_length": {"batches": batches}},
        "scheduling_unit": 4,
        "min_checkpoint_period": {"batches": 8},
        "checkpoint_storage": {"type": "shared_fs",
                               "host_path": str(tmp_path)},
        "optimizations": {"prefetch_depth": 0},
    }


def test_restore_falls_back_past_corrupt_checkpoint(tmp_path, caplog):
    cfg = ExperimentConfig.from_dict(drift_config(tmp_path, batches=16))
    mesh = make_mesh(MeshSpec(dp=1), jax.devices()[:1])
    with core.init(config=cfg, trial_id=1) as cctx:
        ctx = TrialContext(config=cfg, hparams={}, core=cctx, mesh=mesh)
        Trainer(DriftTrial(ctx)).fit()
        sids = cctx.checkpoint.committed_checkpoints()  # newest first
    assert len(sids) >= 2
    newest, previous = sids[0], sids[1]
    # corrupt the newest AFTER it was published (crash wouldn't publish;
    # this models storage losing the marker post-hoc — same refusal path)
    os.unlink(tmp_path / newest / COMMIT_FILE)

    cfg2 = ExperimentConfig.from_dict(drift_config(tmp_path, batches=24))
    with core.init(config=cfg2, trial_id=1) as cctx:
        ctx = TrialContext(config=cfg2, hparams={}, core=cctx, mesh=mesh)
        with caplog.at_level(
                logging.WARNING,
                logger="determined_clone_tpu.training.trainer"):
            result = Trainer(DriftTrial(ctx)).fit(latest_checkpoint=newest)
    assert result["batches_trained"] == 24
    assert any(f"checkpoint {newest} refused" in r.getMessage()
               for r in caplog.records)

    # the fallback resumed from `previous`, and the end state matches a
    # straight 24-batch run (the restore replayed the data stream right)
    baseline_dir = tmp_path / "baseline"
    baseline_dir.mkdir()
    cfg3 = ExperimentConfig.from_dict(drift_config(baseline_dir, batches=24))
    with core.init(config=cfg3, trial_id=1) as cctx:
        ctx = TrialContext(config=cfg3, hparams={}, core=cctx, mesh=mesh)
        Trainer(DriftTrial(ctx)).fit()
        base_sid = cctx.checkpoint.committed_checkpoints()[0]
        with cctx.checkpoint.restore_path(base_sid) as p:
            base_meta = json.load(open(os.path.join(p, "metadata.json")))
    assert base_meta["steps_completed"] == 24
    assert previous in sids


def test_restore_raises_when_every_candidate_corrupt(tmp_path):
    cfg = ExperimentConfig.from_dict(drift_config(tmp_path, batches=8))
    mesh = make_mesh(MeshSpec(dp=1), jax.devices()[:1])
    with core.init(config=cfg, trial_id=1) as cctx:
        ctx = TrialContext(config=cfg, hparams={}, core=cctx, mesh=mesh)
        Trainer(DriftTrial(ctx)).fit()
        sids = cctx.checkpoint.committed_checkpoints()
        for sid in sids:
            os.unlink(tmp_path / sid / COMMIT_FILE)
        ctx2 = TrialContext(config=cfg, hparams={}, core=cctx, mesh=mesh)
        with pytest.raises(CheckpointCorruptError):
            Trainer(DriftTrial(ctx2)).fit(latest_checkpoint=sids[0])


# ---------------------------------------------------------------------------
# content-addressed store: chunk faults during save are refused on restore
# ---------------------------------------------------------------------------

def cas_storage(tmp_path):
    return {"type": "cas", "chunk_size_kb": 1,
            "inner": {"type": "shared_fs", "host_path": str(tmp_path)}}


def make_cas_core(tmp_path, trial_id=1):
    cfg = ExperimentConfig.from_dict({
        "searcher": {"name": "single", "metric": "loss",
                     "max_length": {"batches": 4}},
        "checkpoint_storage": cas_storage(tmp_path),
    })
    return core.init(config=cfg, trial_id=trial_id)


def test_torn_chunk_makes_checkpoint_unrestorable(tmp_path, monkeypatch):
    pin_sequential_pool(monkeypatch)
    monkeypatch.setattr(retry, "_sleep", lambda s: None)
    with make_cas_core(tmp_path) as cctx:
        ck = cctx.checkpoint
        # truncate the 2nd chunk object as it is staged for upload;
        # chunks must differ or dedup collapses them to one upload
        with faults.plan_active({"rules": [
                {"point": "cas.chunk_upload", "action": "truncate",
                 "nth": 2, "keep_bytes": 5}]}):
            with ck.store_path() as (path, holder):
                with open(os.path.join(path, "weights.bin"), "wb") as f:
                    f.write(b"".join(bytes([i]) * 1024 for i in range(4)))
        sid = holder["storage_id"]
        # committed — the torn chunk is only convicted when restore
        # digest-checks it against the chunk manifest
        assert (tmp_path / sid / COMMIT_FILE).exists()
        with pytest.raises(CheckpointCorruptError) as ei:
            with ck.restore_path(sid):
                pass
        assert "torn chunk" in ei.value.reason
        assert ei.value.storage_id == sid


def test_dropped_chunk_makes_checkpoint_unrestorable(tmp_path, monkeypatch):
    pin_sequential_pool(monkeypatch)
    monkeypatch.setattr(retry, "_sleep", lambda s: None)
    with make_cas_core(tmp_path) as cctx:
        ck = cctx.checkpoint
        # the 1st chunk silently never reaches the backend (lost PUT)
        with faults.plan_active({"rules": [
                {"point": "cas.chunk_drop", "action": "truncate",
                 "keep_bytes": 0, "nth": 1, "times": 1}]}):
            with ck.store_path() as (path, holder):
                with open(os.path.join(path, "weights.bin"), "wb") as f:
                    f.write(b"\x07" * 3000)
        sid = holder["storage_id"]
        assert (tmp_path / sid / COMMIT_FILE).exists()
        with pytest.raises(CheckpointCorruptError) as ei:
            with ck.restore_path(sid):
                pass
        assert "missing from the chunk store" in ei.value.reason


def cas_drift_config(tmp_path, batches=24, telemetry=False):
    cfg = drift_config(tmp_path, batches)
    cfg["checkpoint_storage"] = cas_storage(tmp_path)
    if telemetry:
        cfg["observability"] = {"enabled": True}
    return cfg


def test_trainer_falls_back_past_missing_chunk_checkpoint(
        tmp_path, caplog, monkeypatch):
    """End-to-end: a committed CAS checkpoint that lost a chunk is refused
    at restore, the trainer falls back to the previous committed one, the
    fallback is counted, and training still reaches the full length."""
    from determined_clone_tpu.storage import cas as cas_mod

    pin_sequential_pool(monkeypatch)
    cfg = ExperimentConfig.from_dict(cas_drift_config(tmp_path, batches=16))
    mesh = make_mesh(MeshSpec(dp=1), jax.devices()[:1])
    with core.init(config=cfg, trial_id=1) as cctx:
        ctx = TrialContext(config=cfg, hparams={}, core=cctx, mesh=mesh)
        Trainer(DriftTrial(ctx)).fit()
        sids = cctx.checkpoint.committed_checkpoints()  # newest first
    assert len(sids) >= 2
    newest, previous = sids[0], sids[1]

    # lose a chunk only the newest checkpoint references — exactly the
    # state a `cas.chunk_drop` fault during its save leaves behind
    mgr = cas_mod.CASStorageManager(
        SharedFSStorageManager(str(tmp_path)), chunk_size=1024)
    victims = sorted(mgr._referenced_digests(newest)
                     - mgr._referenced_digests(previous))
    assert victims  # the drifted params produced at least one new chunk
    os.unlink(tmp_path / cas_mod.CHUNK_NAMESPACE
              / cas_mod.chunk_rel(victims[0]))

    cfg2 = ExperimentConfig.from_dict(
        cas_drift_config(tmp_path, batches=24, telemetry=True))
    with core.init(config=cfg2, trial_id=1) as cctx:
        ctx = TrialContext(config=cfg2, hparams={}, core=cctx, mesh=mesh)
        with caplog.at_level(
                logging.WARNING,
                logger="determined_clone_tpu.training.trainer"):
            result = Trainer(DriftTrial(ctx)).fit(latest_checkpoint=newest)
        fallbacks = cctx.telemetry.registry.counter(
            "checkpoint_restore_fallbacks").value
    assert result["batches_trained"] == 24
    assert fallbacks == 1
    assert any(f"checkpoint {newest} refused" in r.getMessage()
               for r in caplog.records)
    assert previous in sids


# ---------------------------------------------------------------------------
# experiment runner: restarts back off with jitter and are counted
# ---------------------------------------------------------------------------

def test_runner_restart_backs_off_and_counts(tmp_path, monkeypatch):
    sleeps = []
    monkeypatch.setattr(retry, "_sleep", sleeps.append)
    cfg = ExperimentConfig.from_dict({
        "searcher": {"name": "single", "metric": "loss",
                     "max_length": {"batches": 8}},
        "scheduling_unit": 4,
        "checkpoint_storage": {"type": "shared_fs",
                               "host_path": str(tmp_path)},
        "max_restarts": 2,
        "optimizations": {"prefetch_depth": 0},
        # leg 1 dies on its first step; the cached plan is exhausted by
        # leg 2, which then completes
        "faults": {"rules": [{"point": "training.pre_step",
                              "nth": 1, "times": 1}]},
    })
    mesh = make_mesh(MeshSpec(dp=1), jax.devices()[:1])
    runner = LocalExperimentRunner(cfg, DriftTrial,
                                   storage_path=str(tmp_path), mesh=mesh)
    result = runner.run()
    t = list(result.trials.values())[0]
    assert t.state == "completed"
    assert t.restarts == 1
    assert runner.registry.counter("trial_restarts_total").value == 1
    restart_sleeps = [s for s in sleeps if s > 0]
    assert len(restart_sleeps) >= 1  # the backoff actually ran
    assert all(s <= runner.restart_backoff.max_delay_s for s in sleeps)
    # the restart was snapshotted before the backoff sleep
    snap = json.loads((tmp_path / "experiment_snapshot.json").read_text())
    assert list(snap["trials"].values())[0]["restarts"] == 1


# ---------------------------------------------------------------------------
# kill -9 mid-step: resume lands on the right batch
# ---------------------------------------------------------------------------

CHAOS_RUNNER = '''
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
def test_kill9_mid_step_resumes_at_right_batch(tmp_path):
    """A subprocess trial is hard-killed (os._exit via an `exit` fault —
    no atexit, no flushes: kill -9 semantics) between the batch-8
    checkpoint and the batch-16 one. The resume must restore the batch-8
    state and land on the exact same final params as an uninterrupted
    run — proving both that the orphaned partial state is never loaded
    and that data replay after restore is off-by-none."""
    storage = tmp_path / "ckpts"
    storage.mkdir()
    script = tmp_path / "chaos_run.py"
    script.write_text(CHAOS_RUNNER.format(
        repo=REPO, testdir=os.path.join(REPO, "tests"),
        storage=str(storage)))
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        # die on the 13th step dispatch — after the batch-8 commit
        "DCT_FAULT_PLAN": json.dumps({"rules": [
            {"point": "training.pre_step", "action": "exit",
             "nth": 13, "exit_code": 137}]}),
    }
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=420)
    assert proc.returncode == 137, proc.stdout + proc.stderr
    assert "COMPLETED" not in proc.stdout

    reg = core.LocalCheckpointRegistry(str(storage / "checkpoints.jsonl"))
    recs = reg.list()
    assert len(recs) == 1  # only the batch-8 save committed before death
    sid = recs[0]["storage_id"]
    assert recs[0]["metadata"]["steps_completed"] == 8

    mesh = make_mesh(MeshSpec(dp=1), jax.devices()[:1])

    def final_w(storage_dir, latest=None):
        cfg = ExperimentConfig.from_dict(
            drift_config(storage_dir, batches=24))
        with core.init(config=cfg, trial_id=1) as cctx:
            ctx = TrialContext(config=cfg, hparams={}, core=cctx, mesh=mesh)
            result = Trainer(DriftTrial(ctx)).fit(latest_checkpoint=latest)
            assert result["batches_trained"] == 24
            newest = cctx.checkpoint.committed_checkpoints()[0]
            with cctx.checkpoint.restore_path(newest) as p:
                state = json.load(open(os.path.join(p, "metadata.json")))
                assert state["steps_completed"] == 24
            backend = cctx.train._backend
            return [r for r in backend.records
                    if r["group"] == "training"][-1]["metrics"]["w"]

    resumed = final_w(storage, latest=sid)
    baseline_dir = tmp_path / "baseline"
    baseline_dir.mkdir()
    baseline = final_w(baseline_dir)
    np.testing.assert_allclose(resumed, baseline, rtol=1e-6)


# ---------------------------------------------------------------------------
# GC: orphaned uncommitted checkpoints are swept, committed ones kept
# ---------------------------------------------------------------------------

def test_gc_sweeps_old_uncommitted_dirs(tmp_path, monkeypatch, capsys):
    from determined_clone_tpu.exec import gc_checkpoints

    base = tmp_path / "store"
    mgr = SharedFSStorageManager(str(base))
    src = tmp_path / "src"
    src.mkdir()
    (src / "w.bin").write_bytes(b"x" * 8)
    mgr.upload(str(src), "committed-1")
    mgr.commit("committed-1")
    mgr.upload(str(src), "orphan-old")
    mgr.upload(str(src), "orphan-fresh")
    # backdate the old orphan past the age floor
    old = time.time() - 7200
    for root, _, files in os.walk(base / "orphan-old"):
        for f in files:
            os.utime(os.path.join(root, f), (old, old))
    os.utime(base / "orphan-old", (old, old))

    monkeypatch.setenv("DCT_GC_STORAGE", json.dumps(
        {"type": "shared_fs", "host_path": str(base)}))
    monkeypatch.setenv("DCT_GC_UUIDS", "")
    monkeypatch.setenv("DCT_GC_SWEEP_UNCOMMITTED", "1")
    monkeypatch.setenv("DCT_GC_UNCOMMITTED_AGE_S", "3600")
    assert gc_checkpoints.main() == 0
    out = capsys.readouterr().out
    assert "swept uncommitted checkpoint orphan-old" in out
    ids = mgr.list_storage_ids()
    assert "orphan-old" not in ids
    assert "committed-1" in ids     # COMMIT marker protects it
    assert "orphan-fresh" in ids    # too young: may still be uploading


def test_gc_sweep_disabled_by_default(tmp_path, monkeypatch):
    from determined_clone_tpu.exec import gc_checkpoints

    base = tmp_path / "store"
    mgr = SharedFSStorageManager(str(base))
    src = tmp_path / "src"
    src.mkdir()
    (src / "w.bin").write_bytes(b"x")
    mgr.upload(str(src), "orphan-old")
    old = time.time() - 7200
    for root, _, files in os.walk(base / "orphan-old"):
        for f in files:
            os.utime(os.path.join(root, f), (old, old))
    monkeypatch.setenv("DCT_GC_STORAGE", json.dumps(
        {"type": "shared_fs", "host_path": str(base)}))
    monkeypatch.setenv("DCT_GC_UUIDS", "")
    monkeypatch.delenv("DCT_GC_SWEEP_UNCOMMITTED", raising=False)
    assert gc_checkpoints.main() == 0
    assert "orphan-old" in mgr.list_storage_ids()


# ---------------------------------------------------------------------------
# preemption watcher: poll failures counted + rate-limited warning
# ---------------------------------------------------------------------------

def test_preempt_poll_failures_counted_and_warned(caplog):
    from determined_clone_tpu.core._distributed import DistributedContext
    from determined_clone_tpu.core._preempt import (
        PreemptContext,
        PreemptionSource,
    )
    from determined_clone_tpu.telemetry import MetricsRegistry

    class BrokenSource(PreemptionSource):
        def poll(self):
            raise RuntimeError("source is down")

    reg = MetricsRegistry()
    with caplog.at_level(logging.WARNING,
                         logger="determined_clone_tpu.core._preempt"):
        pc = PreemptContext(DistributedContext.single(), BrokenSource(),
                            poll_interval=0.01, registry=reg).start()
        try:
            deadline = time.monotonic() + 5.0
            while pc.poll_failures < 3 and time.monotonic() < deadline:
                time.sleep(0.01)
        finally:
            pc.close()
    assert pc.poll_failures >= 3
    assert reg.counter("preempt_poll_failures").value == pc.poll_failures
    warnings = [r for r in caplog.records
                if "preemption poll failed" in r.getMessage()]
    assert len(warnings) == 1  # rate-limited: one per window, not per poll
    assert not pc.should_preempt()  # failures never read as "preempted"


# ---------------------------------------------------------------------------
# api client: transport retries + idempotency keys
# ---------------------------------------------------------------------------

def test_api_request_retries_transport_and_sends_idempotency_key(
        monkeypatch):
    import io
    import urllib.error
    import urllib.request

    from determined_clone_tpu.api.client import MasterError, MasterSession

    seen = {"bodies": [], "n": 0}

    class FakeResp(io.BytesIO):
        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

    def fake_urlopen(req, timeout=None):
        seen["n"] += 1
        seen["bodies"].append(json.loads(req.data.decode()))
        if seen["n"] < 3:
            raise urllib.error.URLError("connection refused")
        return FakeResp(b'{"ok": true}')

    monkeypatch.setattr(urllib.request, "urlopen", fake_urlopen)
    monkeypatch.setattr(retry, "_sleep", lambda s: None)
    s = MasterSession("127.0.0.1", 1, retries=3)
    out = s.post("/api/v1/trials/1/metrics", {"loss": 1.0},
                 retryable=True, idempotency_key="k-123")
    assert out == {"ok": True}
    assert seen["n"] == 3
    assert retry.stats()["api_request"] == 2
    # every attempt (original + replays) carried the same key: the master
    # dedups instead of double-counting the metric report
    assert all(b["idempotency_key"] == "k-123" for b in seen["bodies"])

    # an HTTP answer from the master is NOT a transport error: no retry
    def http_error(req, timeout=None):
        seen["n"] += 1
        raise urllib.error.HTTPError(req.full_url, 400, "bad", {},
                                     io.BytesIO(b'{"error": "nope"}'))

    seen["n"] = 0
    monkeypatch.setattr(urllib.request, "urlopen", http_error)
    with pytest.raises(MasterError):
        s.post("/x", {}, retryable=True)
    assert seen["n"] == 1
