"""Log-pattern policies + checkpoint GC, e2e against master+agent.

≈ the reference's logpattern behavior (master/internal/logpattern →
trial.go:381 blocked nodes, trial.go:184 non-retryable classification) and
checkpoint GC policy (checkpoint_gc.go:27 + exec/gc_checkpoints.py:97).
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

# fails every leg after printing a recognizable poison line
FAILING_TRIAL = '''
import sys

import jax.numpy as jnp
import numpy as np
import optax

from determined_clone_tpu.training import JaxTrial


class Trial(JaxTrial):
    def initial_params(self, rng):
        print("DCT-POISON: device wedged", flush=True)
        sys.stdout.flush()
        raise RuntimeError("boom")

    def optimizer(self):
        return optax.sgd(0.1)

    def loss(self, params, batch, rng):
        return jnp.zeros(()), {}

    def training_data(self):
        yield np.zeros((2, 1), np.float32)

    def validation_data(self):
        return []

    @property
    def global_batch_size(self):
        return 2
'''

# checkpoints every 2 batches -> several checkpoints per run
CKPT_TRIAL = '''
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
    tmp = tmp_path_factory.mktemp("lpgc")
    workdir = tmp / "agent-work"
    workdir.mkdir()
    (workdir / "failing_def.py").write_text(FAILING_TRIAL)
    (workdir / "ckpt_def.py").write_text(CKPT_TRIAL)

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
        [str(AGENT_BIN), "--master-port", str(port), "--id", "lpgc-agent",
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


def base_config(cluster, name, entrypoint, **over):
    cfg = {
        "name": name,
        "entrypoint": entrypoint,
        "searcher": {"name": "single", "metric": "loss",
                     "max_length": {"batches": 8}},
        "resources": {"slots_per_trial": 1},
        "scheduling_unit": 2,
        "checkpoint_storage": {"type": "shared_fs",
                               "host_path": str(cluster["tmp"] / "ckpts")},
        "hyperparameters": {},
        "max_restarts": 3,
    }
    cfg.update(over)
    return cfg


def test_bad_log_pattern_rejected_at_submission(cluster):
    from determined_clone_tpu.api.client import MasterError

    with pytest.raises(MasterError) as err:
        cluster["session"].create_experiment(base_config(
            cluster, "lp-bad", "failing_def:Trial",
            log_policies=[{"pattern": "DCT-POISON(",
                           "action": {"type": "cancel_retries"}}],
        ))
    assert err.value.status == 400


def test_cancel_retries_log_policy(cluster):
    """Poison line → cancel_retries → exactly ONE leg despite max_restarts=3."""
    session = cluster["session"]
    exp = session.create_experiment(base_config(
        cluster, "lp-cancel", "failing_def:Trial",
        log_policies=[{"pattern": r"DCT-POISON",
                       "action": {"type": "cancel_retries"}}],
    ))
    detail = wait_for(
        lambda: (lambda d: d if d["experiment"]["state"] == "ERRORED" else None)(
            session.get_experiment(exp["id"])),
        desc="experiment errored", timeout=120,
    )
    trial = detail["trials"][0]
    assert trial["state"] == "ERRORED"
    assert trial["no_retries"] is True
    # only the first leg ran: restarts counted once, no retry allocations
    assert trial["restarts"] == 1


def test_exclude_node_log_policy_blocks_agent(cluster):
    """Poison line → exclude_node → the only agent is blocklisted, so the
    retry leg can never schedule (stays QUEUED)."""
    session = cluster["session"]
    exp = session.create_experiment(base_config(
        cluster, "lp-exclude", "failing_def:Trial",
        log_policies=[{"pattern": r"DCT-POISON",
                       "action": {"type": "exclude_node"}}],
    ))

    def blocked():
        agents = session.list_agents()
        key = f"exp-{exp['id']}"
        return agents[0] if key in agents[0].get("blocked_by", []) else None

    wait_for(blocked, desc="agent blocklisted", timeout=120)

    # the retry allocation exists but cannot fit anywhere
    def retry_queued():
        detail = session.get_experiment(exp["id"])
        t = detail["trials"][0]
        return t if t["restarts"] >= 1 and t["state"] == "QUEUED" else None

    wait_for(retry_queued, desc="retry leg starved by blocklist", timeout=60)
    session.kill_experiment(exp["id"])


def test_checkpoint_gc_policy(cluster):
    """save_trial_latest=1: after completion only the newest checkpoint
    survives; older ones are registry-deleted AND removed from storage by
    the GC task."""
    session = cluster["session"]
    exp = session.create_experiment(base_config(
        cluster, "gc-exp", "ckpt_def:Trial",
        min_checkpoint_period={"batches": 2},
        checkpoint_storage={
            "type": "shared_fs",
            "host_path": str(cluster["tmp"] / "ckpts"),
            "save_trial_latest": 1,
            "save_trial_best": 0,
        },
        max_restarts=0,
    ))
    wait_for(
        lambda: session.get_experiment(exp["id"])["experiment"]["state"]
        == "COMPLETED",
        desc="experiment completion", timeout=120,
    )
    all_ckpts = session.get(
        f"/api/v1/experiments/{exp['id']}/checkpoints")["checkpoints"]
    # live records exclude deleted; exactly one survivor
    assert len(all_ckpts) == 1, all_ckpts
    survivor = all_ckpts[0]["uuid"]

    # GC task ran and the storage dir only holds the survivor
    ckpt_root = cluster["tmp"] / "ckpts"

    def storage_clean():
        dirs = {p.name for p in ckpt_root.iterdir() if p.is_dir()}
        mine = {d for d in dirs}
        return mine if survivor in mine else None

    wait_for(storage_clean, desc="storage has survivor", timeout=60)

    def gc_done():
        tasks = [t for t in session.list_tasks("command")
                 if t["name"].startswith(f"checkpoint-gc-exp-{exp['id']}")]
        return tasks if tasks and all(
            t["state"] in ("COMPLETED", "ERRORED") for t in tasks) else None

    tasks = wait_for(gc_done, desc="gc task finished", timeout=60)
    assert tasks[0]["state"] == "COMPLETED"

    # storage: survivor present, at least one deleted uuid absent
    deleted_uuid_logs = session.task_logs(tasks[0]["id"])
    joined = "\n".join(l.get("log", "") for l in deleted_uuid_logs)
    assert "deleted checkpoint" in joined
    assert (ckpt_root / survivor).exists()
    for line in joined.splitlines():
        if line.startswith("deleted checkpoint "):
            gone = line.split()[-1]
            assert not (ckpt_root / gone).exists()
