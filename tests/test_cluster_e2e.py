"""Cluster e2e: C++ master + C++ agent + real Python trial processes.

The reference's devcluster-style test (tools/devcluster.yaml,
e2e_tests/tests/cluster/managed_cluster.py): boot master+agent from source,
submit experiments over the API, assert scheduling/training/restart behavior.
"""
import json
import os
import shutil
import subprocess
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
MASTER_DIR = REPO / "determined_clone_tpu" / "master"
MASTER_BIN = MASTER_DIR / "build" / "dct-master"
AGENT_BIN = MASTER_DIR / "build" / "dct-agent"

TRIAL_MODULE = '''
import jax.numpy as jnp
import numpy as np
import optax

from determined_clone_tpu.training import JaxTrial


class Trial(JaxTrial):
    def initial_params(self, rng):
        return {"w": jnp.zeros(())}

    def optimizer(self):
        return optax.sgd(self.context.get_hparam("lr", 0.2))

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
    tmp = tmp_path_factory.mktemp("cluster")
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
        "DCT_AGENT_SLOTS": "1",           # artificial slot (detect.go:39 trick)
        "DCT_AGENT_TOPOLOGY": "v5e-1",
    }
    master = subprocess.Popen(
        [str(MASTER_BIN), "--port", str(port), "--data-dir",
         str(tmp / "master-data")],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
    )
    agent = subprocess.Popen(
        [str(AGENT_BIN), "--master-port", str(port), "--id", "test-agent",
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

    yield {"session": session, "tmp": tmp, "port": port,
           "master": master, "agent": agent, "workdir": workdir}

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


def exp_config(cluster, searcher, hparams=None, name="e2e"):
    return {
        "name": name,
        "entrypoint": "model_def:Trial",
        "searcher": searcher,
        "resources": {"slots_per_trial": 1},
        "scheduling_unit": 2,
        "checkpoint_storage": {"type": "shared_fs",
                               "host_path": str(cluster["tmp"] / "ckpts")},
        "hyperparameters": hparams or {"lr": 0.2},
        "max_restarts": 1,
    }


def test_master_and_agent_register(cluster):
    agents = cluster["session"].list_agents()
    assert len(agents) == 1
    assert agents[0]["slots"] == 1
    assert agents[0]["topology"] == "v5e-1"
    info = cluster["session"].master_info()
    assert info["agents"] == 1


def test_single_experiment_trains_to_completion(cluster):
    session = cluster["session"]
    exp = session.create_experiment(exp_config(cluster, {
        "name": "single", "metric": "loss", "max_length": {"batches": 6},
    }))
    detail = wait_for(
        lambda: (lambda d: d if d["experiment"]["state"] == "COMPLETED" else None)(
            session.get_experiment(exp["id"])),
        desc="experiment completion", timeout=180,
    )
    trials = detail["trials"]
    assert len(trials) == 1
    t = trials[0]
    assert t["state"] == "COMPLETED"
    assert t["units_done"] >= 6
    assert t["has_metric"]
    # metrics made it to the master
    metrics = session.trial_metrics(t["id"])
    groups = {m["group"] for m in metrics}
    assert "training" in groups and "validation" in groups
    # checkpoint was reported and linked. The master publishes COMPLETED
    # from the report of the last validation (the searcher closes the trial
    # and shuts the experiment down in that request, master.cc
    # apply_search_ops); the harness reports the checkpoint it saves on its
    # way out in a later request, so the link is waited for like the state
    t = wait_for(
        lambda: (lambda tr: tr if tr["latest_checkpoint"] else None)(
            session.get_experiment(exp["id"])["trials"][0]),
        desc="checkpoint linked", timeout=60,
    )
    assert t["latest_checkpoint"]
    ckpts = session.get(f"/api/v1/experiments/{exp['id']}/checkpoints")[
        "checkpoints"]
    assert any(c["uuid"] == t["latest_checkpoint"] for c in ckpts)
    # task logs shipped by the agent on exit (arrives after process reap)
    logs = wait_for(
        lambda: [l for l in session.task_logs(f"trial-{t['id']}.0")
                 if "leg finished" in json.dumps(l)] or None,
        desc="task logs shipped", timeout=30,
    )
    assert logs


def test_random_search_multiple_trials(cluster):
    session = cluster["session"]
    exp = session.create_experiment(exp_config(cluster, {
        "name": "random", "metric": "loss", "max_trials": 2,
        "max_length": {"batches": 4}, "max_concurrent_trials": 1,
    }, hparams={"lr": {"type": "double", "minval": 0.1, "maxval": 0.3}},
        name="e2e-random"))
    detail = wait_for(
        lambda: (lambda d: d if d["experiment"]["state"] == "COMPLETED" else None)(
            session.get_experiment(exp["id"])),
        desc="random search completion", timeout=300,
    )
    assert len(detail["trials"]) == 2
    assert all(t["state"] == "COMPLETED" for t in detail["trials"])
    lrs = {t["hparams"]["lr"] for t in detail["trials"]}
    assert len(lrs) == 2


def test_kill_experiment(cluster):
    session = cluster["session"]
    exp = session.create_experiment(exp_config(cluster, {
        "name": "single", "metric": "loss", "max_length": {"batches": 10_000},
    }, name="e2e-kill"))
    session.kill_experiment(exp["id"])
    detail = wait_for(
        lambda: (lambda d: d if d["experiment"]["state"] in
                 ("CANCELED", "COMPLETED") else None)(
            session.get_experiment(exp["id"])),
        desc="experiment cancel", timeout=60,
    )
    assert detail["experiment"]["state"] == "CANCELED"


SLOW_TRIAL = TRIAL_MODULE.replace(
    "    def training_data(self):\n"
    "        for _ in range(64):\n"
    "            yield np.zeros((2, 1), np.float32)",
    "    def training_data(self):\n"
    "        import time\n"
    "        for _ in range(64):\n"
    "            time.sleep(0.25)\n"
    "            yield np.zeros((2, 1), np.float32)")


def test_pause_activate_archive_delete(cluster):
    """≈ PauseExperiment/ActivateExperiment/Archive/Delete: pause preempts
    the running trial (it checkpoints and frees the chip), activate
    resumes from that checkpoint, archive/delete need a terminal state."""
    session = cluster["session"]
    assert SLOW_TRIAL != TRIAL_MODULE  # the replace really took
    (cluster["workdir"] / "slow_def.py").write_text(SLOW_TRIAL)
    cfg = exp_config(cluster, {"name": "single", "metric": "loss",
                               "max_length": {"batches": 30}},
                     name="pausable")
    cfg["entrypoint"] = "slow_def:Trial"
    exp = session.create_experiment(cfg)
    eid = exp["id"]

    # wait for real training progress (past compile) so the pause
    # exercises the graceful checkpoint-and-exit path, not the startup race
    wait_for(lambda: session.get_experiment(eid)["trials"] and
             session.get_experiment(eid)["trials"][0]["units_done"] > 0,
             desc="trial made progress")

    # cannot archive or delete while live
    from determined_clone_tpu.api.client import MasterError

    with pytest.raises(MasterError):
        session.archive_experiment(eid)
    with pytest.raises(MasterError):
        session.delete_experiment(eid)

    paused = session.pause_experiment(eid)
    assert paused["state"] == "PAUSED"
    # the trial preempts gracefully: checkpoints, exits, parks
    wait_for(lambda: session.get_experiment(eid)["trials"][0]["state"]
             == "PAUSED", desc="trial paused")
    trial = session.get_experiment(eid)["trials"][0]
    assert 0 < trial["units_done"] < 30  # mid-run, progress persisted
    assert trial["latest_checkpoint"]    # preemption checkpoint landed
    # the chip is free again (no live allocation for this trial)
    assert not any(j["id"].startswith(f"trial-{trial['id']}.")
                   for j in session.job_queue())

    # double-pause is a no-op error; activate resumes from the checkpoint
    with pytest.raises(MasterError):
        session.pause_experiment(eid)
    activated = session.activate_experiment(eid)
    assert activated["state"] == "RUNNING"
    wait_for(lambda: session.get_experiment(eid)["experiment"]["state"]
             == "COMPLETED", desc="completed after resume")
    trial = session.get_experiment(eid)["trials"][0]
    assert trial["units_done"] >= 30

    # archive, then delete: records and checkpoints drop out
    assert session.archive_experiment(eid)["archived"] is True
    assert session.archive_experiment(eid, archive=False)[
        "archived"] is False
    assert session.get_experiment(eid)["experiment"]  # still queryable
    session.delete_experiment(eid)
    with pytest.raises(MasterError) as err:
        session.get_experiment(eid)
    assert err.value.status == 404


def test_kill_single_trial_search_continues(cluster):
    """≈ KillTrial: killing one trial of a random search cancels only that
    trial; the searcher is told it exited early and the experiment still
    finishes."""
    session = cluster["session"]
    exp = session.create_experiment(exp_config(
        cluster, {"name": "random", "metric": "loss", "max_trials": 3,
                  "max_length": {"batches": 4}},
        hparams={"lr": {"type": "double", "minval": 0.05, "maxval": 0.3}},
        name="trial-kill"))
    eid = exp["id"]
    trials = wait_for(lambda: session.get_experiment(eid)["trials"] or None,
                      desc="trials created")
    victim = trials[0]["id"]
    killed = session.kill_trial(victim)
    # fast trials can finish before the kill lands; non-terminal ones cancel
    assert killed["state"] in ("CANCELED", "COMPLETED")

    # the experiment completes with the remaining trials either way
    wait_for(lambda: session.get_experiment(eid)["experiment"]["state"]
             == "COMPLETED", desc="search completed despite the kill")
    final = {t["id"]: t["state"]
             for t in session.get_experiment(eid)["trials"]}
    assert final[victim] == killed["state"]  # the kill's outcome held
    assert sum(1 for s in final.values() if s == "COMPLETED") >= 2
    # a second kill is an idempotent no-op
    assert session.kill_trial(victim)["state"] == killed["state"]


def test_kill_only_trial_cancels_experiment(cluster):
    """Killing a single-searcher experiment's only trial is a user cancel:
    the experiment ends CANCELED (like experiment kill), never ERRORED."""
    session = cluster["session"]
    exp = session.create_experiment(exp_config(cluster, {
        "name": "single", "metric": "loss",
        "max_length": {"batches": 10_000},
    }, name="kill-only-trial"))
    trials = wait_for(lambda: session.get_experiment(exp["id"])["trials"]
                      or None, desc="trial created")
    session.kill_trial(trials[0]["id"])
    detail = wait_for(
        lambda: (lambda d: d if d["experiment"]["state"] in
                 ("CANCELED", "ERRORED", "COMPLETED") else None)(
            session.get_experiment(exp["id"])),
        desc="experiment settled", timeout=60)
    assert detail["experiment"]["state"] == "CANCELED"
    assert detail["trials"][0]["state"] == "CANCELED"
