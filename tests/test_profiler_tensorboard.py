"""Profiler + TensorBoard subsystem.

Unit tier: tfevents writer/reader round trip (CRC-verified), profiler
sampling/batching against a fake session (≈ harness/tests profiler tests).
E2E tier: experiment with profiling enabled → samples land on the master;
tfevents uploaded to storage; `det tensorboard` task serves parsed scalars
through the master proxy.
"""
import json
import os
import subprocess
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
MASTER_DIR = REPO / "determined_clone_tpu" / "master"
MASTER_BIN = MASTER_DIR / "build" / "dct-master"
AGENT_BIN = MASTER_DIR / "build" / "dct-agent"


# ---------------------------------------------------------------------------
# tfevents unit tests
# ---------------------------------------------------------------------------

def test_tfevents_round_trip(tmp_path):
    from determined_clone_tpu.tensorboard import (
        EventFileWriter,
        read_tfevents,
    )

    w = EventFileWriter(str(tmp_path))
    w.add_scalar("loss", 0.5, 1)
    w.add_scalar("loss", 0.25, 2)
    w.add_scalar("acc", 0.9, 2)
    w.close()

    events = list(read_tfevents(w.path))
    # first record is the file_version header (no scalars)
    scalars = [e for e in events if e["scalars"]]
    assert len(scalars) == 3
    assert scalars[0]["scalars"] == {"loss": 0.5}
    assert scalars[0]["step"] == 1
    assert scalars[2]["scalars"]["acc"] == pytest.approx(0.9)
    assert all(e["wall_time"] > 0 for e in scalars)


def test_tfevents_crc_detects_corruption(tmp_path):
    from determined_clone_tpu.tensorboard import (
        EventFileWriter,
        read_tfevents,
    )

    w = EventFileWriter(str(tmp_path))
    w.add_scalar("x", 1.0, 1)
    w.close()
    blob = bytearray(Path(w.path).read_bytes())
    blob[-6] ^= 0xFF  # flip a payload byte
    Path(w.path).write_bytes(bytes(blob))
    with pytest.raises(ValueError):
        list(read_tfevents(w.path))


def test_crc32c_known_vectors():
    from determined_clone_tpu.tensorboard._tfevents import crc32c

    # RFC 3720 test vectors
    assert crc32c(b"") == 0x0
    assert crc32c(b"123456789") == 0xE3069283
    assert crc32c(bytes(32)) == 0x8A9136AA


def test_tensorboard_manager_sync(tmp_path):
    from determined_clone_tpu.tensorboard import (
        TensorboardManager,
        fetch_trial_events,
        read_tfevents,
    )

    storage_raw = {"type": "shared_fs", "host_path": str(tmp_path / "store")}
    mgr = TensorboardManager.from_config(
        storage_raw, 7, 3, str(tmp_path / "logs"))
    mgr.add_scalars("training", {"loss": 1.0, "skipme": "not-a-number"}, 1)
    mgr.add_scalars("training", {"loss": 0.5}, 2)
    mgr.sync()
    mgr.close()

    fetched = fetch_trial_events(storage_raw, 7, 3, str(tmp_path / "dl"))
    assert len(fetched) == 1
    series = [e["scalars"] for e in read_tfevents(fetched[0]) if e["scalars"]]
    assert series == [{"training/loss": 1.0}, {"training/loss": 0.5}]

    # unknown trial → empty, not an exception
    assert fetch_trial_events(storage_raw, 7, 999, str(tmp_path / "dl2")) == []


# ---------------------------------------------------------------------------
# profiler unit tests
# ---------------------------------------------------------------------------

class FakeSession:
    def __init__(self):
        self.posts = []

    def post(self, path, body, retryable=False):
        self.posts.append((path, body))
        return {}


def test_profiler_collects_and_flushes():
    from determined_clone_tpu.profiler import ProfilerAgent

    session = FakeSession()
    prof = ProfilerAgent(session, 42, enabled=True, sample_system=False)
    prof.start()
    prof.record_batch_timing(10, dataloading_s=0.1, compute_s=0.9)
    prof.record({"group": "system", "cpu_util_pct": 50.0, "time": 1.0})
    prof.stop()

    assert session.posts
    path, body = session.posts[0]
    assert path == "/api/v1/trials/42/profiler"
    groups = {s["group"] for s in body["samples"]}
    assert groups == {"timing", "system"}
    timing = [s for s in body["samples"] if s["group"] == "timing"][0]
    assert timing["batches_trained"] == 10
    assert timing["compute_s"] == pytest.approx(0.9)


def test_profiler_disabled_is_inert():
    from determined_clone_tpu.profiler import ProfilerAgent

    session = FakeSession()
    prof = ProfilerAgent(session, 1, enabled=False)
    prof.start()
    prof.record({"group": "system"})
    prof.stop()
    assert session.posts == []


def test_profiler_system_sampler_produces_metrics():
    from determined_clone_tpu.profiler import ProfilerAgent, SystemMetricsThread

    session = FakeSession()
    prof = ProfilerAgent(session, 1, enabled=True, sample_system=False)
    sampler = SystemMetricsThread(prof)
    sampler.sample_once()
    time.sleep(0.05)
    sampler.sample_once()  # second sample has cpu deltas
    prof.flush()
    samples = [s for _, b in session.posts for s in b["samples"]]
    assert samples
    assert any("memory_used_gb" in s for s in samples)
    assert any("cpu_util_pct" in s for s in samples)


def test_profiler_from_config_gating():
    from determined_clone_tpu.profiler import from_config

    assert from_config(FakeSession(), 1, {}).enabled is False
    assert from_config(
        FakeSession(), 1, {"profiling": {"enabled": True}}).enabled is True


# ---------------------------------------------------------------------------
# e2e: profiler samples + tensorboard through a live cluster
# ---------------------------------------------------------------------------

TRIAL_MODULE = '''
import jax.numpy as jnp
import numpy as np
import optax

from determined_clone_tpu.training import JaxTrial


class Trial(JaxTrial):
    def initial_params(self, rng):
        return {"w": jnp.zeros(())}

    def optimizer(self):
        return optax.sgd(0.2)

    def loss(self, params, batch, rng):
        return (params["w"] - 2.0) ** 2, {}

    def training_data(self):
        for _ in range(64):
            yield np.zeros((2, 1), np.float32)

    def validation_data(self):
        return [np.zeros((2, 1), np.float32)]

    @property
    def global_batch_size(self):
        return 2
'''


def build_binaries():
    if MASTER_BIN.exists() and AGENT_BIN.exists():
        return True
    r = subprocess.run(["make", "-C", str(MASTER_DIR)], capture_output=True)
    return r.returncode == 0


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    if not build_binaries():
        pytest.skip("C++ master/agent build unavailable")
    tmp = tmp_path_factory.mktemp("proftb")
    workdir = tmp / "agent-work"
    workdir.mkdir()
    (workdir / "model_def.py").write_text(TRIAL_MODULE)

    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": str(REPO),
        "DCT_AGENT_SLOTS": "1",
        "DCT_AGENT_TOPOLOGY": "v5e-1",
    }
    master = subprocess.Popen(
        [str(MASTER_BIN), "--port", str(port), "--data-dir",
         str(tmp / "master-data")],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
    )
    agent = subprocess.Popen(
        [str(AGENT_BIN), "--master-port", str(port), "--id", "prof-agent",
         "--work-dir", str(workdir)],
        cwd=str(workdir),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
    )

    from determined_clone_tpu.api.client import MasterSession

    session = MasterSession("127.0.0.1", port, timeout=10, retries=20)
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            if session.list_agents():
                break
        except Exception:
            time.sleep(0.3)
    else:
        master.kill()
        agent.kill()
        pytest.fail("cluster did not come up")

    yield {"session": session, "tmp": tmp, "port": port}

    agent.kill()
    master.kill()
    agent.wait(timeout=10)
    master.wait(timeout=10)


def wait_for(predicate, timeout=120, interval=0.5, desc="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        result = predicate()
        if result:
            return result
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {desc}")


def test_profiling_and_tensorboard_e2e(cluster):
    session = cluster["session"]
    exp = session.create_experiment({
        "name": "prof-exp",
        "entrypoint": "model_def:Trial",
        "searcher": {"name": "single", "metric": "loss",
                     "max_length": {"batches": 6}},
        "resources": {"slots_per_trial": 1},
        "scheduling_unit": 2,
        "checkpoint_storage": {"type": "shared_fs",
                               "host_path": str(cluster["tmp"] / "ckpts")},
        "hyperparameters": {},
        "profiling": {"enabled": True},
        "max_restarts": 0,
    })
    wait_for(
        lambda: session.get_experiment(exp["id"])["experiment"]["state"]
        == "COMPLETED",
        desc="experiment completion",
    )
    trial_id = session.get_experiment(exp["id"])["trials"][0]["id"]

    # profiler samples reached the master: timing + system groups
    samples = wait_for(
        lambda: session.trial_profiler_samples(trial_id) or None,
        desc="profiler samples", timeout=30,
    )
    groups = {s.get("group") for s in samples}
    assert "timing" in groups
    timing = [s for s in samples if s.get("group") == "timing"]
    assert all("compute_s" in s and "dataloading_s" in s for s in timing)

    # tfevents shipped to checkpoint storage
    from determined_clone_tpu.tensorboard import (
        fetch_trial_events,
        read_tfevents,
    )

    storage_raw = {"type": "shared_fs",
                   "host_path": str(cluster["tmp"] / "ckpts")}
    files = fetch_trial_events(storage_raw, exp["id"], trial_id,
                               str(cluster["tmp"] / "tb-dl"))
    assert files, "no tfevents uploaded"
    tags = set()
    for path in files:
        for event in read_tfevents(path):
            tags.update(event["scalars"])
    assert "training/loss" in tags
    assert "validation/loss" in tags

    # tensorboard task serves parsed scalars through the proxy
    task = session.create_task("tensorboard", name="tb-e2e",
                               experiment_ids=[exp["id"]])
    wait_for(
        lambda: (lambda t: t if t["state"] == "RUNNING" and
                 t["proxy_address"] else None)(session.get_task(task["id"])),
        desc="tb task proxied", timeout=60,
    )
    data = session.proxy(task["id"], "/scalars")
    trial_data = data["experiments"][str(exp["id"])]["trials"][str(trial_id)]
    assert "training/loss" in trial_data["scalars"]
    assert trial_data["files"]
    session.kill_task(task["id"])
