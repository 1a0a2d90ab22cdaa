"""Multi-agent gang e2e: 2 agents, slots_per_trial=2 — the master gangs
both, the two trial processes rendezvous, bring up jax.distributed (CPU
backend), and train data-parallel over the 2-process world.

≈ the reference's distributed e2e (devcluster double.devcluster.yaml per
managed_cluster.py:16 + nightly test_distributed.py): multi-node without
real hardware via multiple agent processes on one host.
"""
import os
import subprocess
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
MASTER_DIR = REPO / "determined_clone_tpu" / "master"
MASTER_BIN = MASTER_DIR / "build" / "dct-master"
AGENT_BIN = MASTER_DIR / "build" / "dct-agent"

TRIAL_MODULE = '''
import jax
import jax.numpy as jnp
import numpy as np
import optax

from determined_clone_tpu.training import JaxTrial


class Trial(JaxTrial):
    def initial_params(self, rng):
        # prove the world really is 2 processes
        assert jax.process_count() == 2, jax.process_count()
        assert jax.device_count() >= 2
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
    tmp = tmp_path_factory.mktemp("gang")

    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    base_env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": str(REPO),
        "DCT_AGENT_TOPOLOGY": "v5e-1",
    }
    # each agent contributes 1 slot; the XLA flag is NOT forced to 8 here so
    # each process owns its own single CPU "chip" (a 2-host world)
    base_env["XLA_FLAGS"] = ""
    master = subprocess.Popen(
        [str(MASTER_BIN), "--port", str(port), "--data-dir",
         str(tmp / "master-data")],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=base_env,
    )
    agents = []
    for i in range(2):
        workdir = tmp / f"agent-{i}"
        workdir.mkdir()
        (workdir / "model_def.py").write_text(TRIAL_MODULE)
        env = {**base_env, "DCT_AGENT_SLOTS": "1"}
        agents.append(subprocess.Popen(
            [str(AGENT_BIN), "--master-port", str(port),
             "--id", f"gang-agent-{i}", "--work-dir", str(workdir)],
            cwd=str(workdir),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
        ))

    from determined_clone_tpu.api.client import MasterSession

    session = MasterSession("127.0.0.1", port, timeout=10, retries=20)
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            if len(session.list_agents()) == 2:
                break
        except Exception:
            time.sleep(0.3)
    else:
        master.kill()
        for a in agents:
            a.kill()
        pytest.fail("cluster did not come up")

    yield {"session": session, "tmp": tmp, "port": port}

    for a in agents:
        a.kill()
    master.kill()
    for a in agents:
        a.wait(timeout=10)
    master.wait(timeout=10)


def wait_for(predicate, timeout=240, interval=1.0, desc="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        result = predicate()
        if result:
            return result
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {desc}")


def test_two_agent_gang_trains(cluster):
    session = cluster["session"]
    exp = session.create_experiment({
        "name": "gang2",
        "entrypoint": "model_def:Trial",
        "searcher": {"name": "single", "metric": "loss",
                     "max_length": {"batches": 4}},
        "resources": {"slots_per_trial": 2},
        "scheduling_unit": 2,
        "checkpoint_storage": {"type": "shared_fs",
                               "host_path": str(cluster["tmp"] / "ckpts")},
        "hyperparameters": {},
        "max_restarts": 0,
    })

    def done():
        d = session.get_experiment(exp["id"])
        state = d["experiment"]["state"]
        if state == "ERRORED":
            trial = d["trials"][0]
            logs = session.task_logs(
                f"trial-{trial['id']}.0", limit=200)
            raise AssertionError(
                "gang experiment ERRORED:\n" +
                "\n".join(l.get("log", "") for l in logs[-40:]))
        return d if state == "COMPLETED" else None

    detail = wait_for(done, desc="gang completion")
    trial = detail["trials"][0]
    assert trial["state"] == "COMPLETED"

    # both ranks joined one allocation (world_size 2) and rendezvoused
    queue_done = session.get(
        f"/api/v1/allocations/trial-{trial['id']}.0/rendezvous")
    assert queue_done["world_size"] == 2
    assert len(queue_done["members"]) == 2

    # validation metrics flowed from the chief
    metrics = session.trial_metrics(trial["id"])
    val = [m for m in metrics if m.get("group") == "validation"]
    assert val and val[-1]["metrics"]["loss"] < 0.5

    # the gang admission shows up in the scheduler's control-plane
    # telemetry: a 2-reservation fit counts as one admitted gang, and the
    # full lifecycle ran (submitted → scheduled → running → completed).
    # The experiment is COMPLETED when the searcher closes; the scheduler
    # counts the allocation completed when its last rank exits, a moment
    # later.
    def allocation_completed():
        s = session.get("/api/v1/cluster/scheduler")
        return s if s["counters"]["completed"] >= 1 else None

    sched = wait_for(allocation_completed, timeout=60, interval=0.2,
                     desc="the scheduler's completed counter")
    c = sched["counters"]
    assert c["gangs_admitted"] >= 1
    assert c["submitted"] >= 1 and c["scheduled"] >= 1
    assert c["running"] >= 1
    assert "gang_wait_ticks" in c  # ticks spent waiting are tracked too
    lat = sched["latency"]["submit_to_running_seconds"]
    assert lat["count"] >= 1 and lat["p50"] > 0

    # and in the Prometheus exposition, including the per-pool gauge family
    import urllib.request

    text = urllib.request.urlopen(
        f"http://127.0.0.1:{cluster['port']}/metrics", timeout=10
    ).read().decode()
    assert "dct_master_sched_gangs_admitted_total" in text
    assert "dct_master_sched_gang_waiting" in text
