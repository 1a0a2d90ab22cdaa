"""Custom searcher: user SearchMethods driving experiments via the master's
event queue (RemoteSearchRunner) and the local orchestrator
(LocalSearchRunner).

≈ the reference's custom-search stack: master/pkg/searcher/custom_search.go
(event queue), harness/determined/searcher/_search_runner.py (runners),
e2e_tests custom-searcher flows.
"""
import os
import subprocess
import threading
import time
from pathlib import Path
from typing import List

import pytest

from determined_clone_tpu.searcher import (
    Close,
    Create,
    LocalSearchRunner,
    RemoteSearchRunner,
    SearchMethod,
    Shutdown,
    ValidateAfter,
    build_method,
)

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


class TwoTrialMethod(SearchMethod):
    """Create two trials with fixed lrs, one validation round each, then
    close both and shut down. Small but exercises every event type's path."""

    def __init__(self):  # noqa: D107 - no config needed
        self.validated: List[int] = []
        self.created: List[int] = []

    def initial_operations(self):
        return [
            Create(-1, {"lr": 0.1}),
            Create(-1, {"lr": 0.3}),
        ]

    def on_trial_created(self, request_id):
        self.created.append(request_id)
        return [ValidateAfter(request_id, 4)]

    def on_validation_completed(self, request_id, metric, units):
        self.validated.append(request_id)
        ops = [Close(request_id)]
        if len(self.validated) == 2:
            ops.append(Shutdown())
        return ops

    def progress(self):
        return len(self.validated) / 2.0


def build_binaries():
    if MASTER_BIN.exists() and AGENT_BIN.exists():
        return True
    r = subprocess.run(["make", "-C", str(MASTER_DIR)], capture_output=True)
    return r.returncode == 0


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    if not build_binaries():
        pytest.skip("C++ master/agent build unavailable")
    tmp = tmp_path_factory.mktemp("customsearch")
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
        "DCT_AGENT_SLOTS": "2",
        "DCT_AGENT_TOPOLOGY": "v5e-1",
    }
    master = subprocess.Popen(
        [str(MASTER_BIN), "--port", str(port), "--data-dir",
         str(tmp / "master-data")],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
    )
    agent = subprocess.Popen(
        [str(AGENT_BIN), "--master-port", str(port), "--id", "cs-agent",
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


def test_remote_search_runner_end_to_end(cluster):
    session = cluster["session"]
    method = TwoTrialMethod()
    runner = RemoteSearchRunner(method, session, poll_interval=0.2)
    config = {
        "name": "custom-e2e",
        "entrypoint": "model_def:Trial",
        "searcher": {"name": "custom", "metric": "loss",
                     "max_length": {"batches": 4}},
        "resources": {"slots_per_trial": 1},
        "scheduling_unit": 2,
        "checkpoint_storage": {"type": "shared_fs",
                               "host_path": str(cluster["tmp"] / "ckpts")},
        "hyperparameters": {"lr": 0.2},
        "max_restarts": 1,
    }
    done = {}

    def drive():
        done["exp_id"] = runner.run(config)

    t = threading.Thread(target=drive, daemon=True)
    t.start()
    t.join(timeout=300)
    assert not t.is_alive(), "runner did not converge"

    detail = session.get_experiment(done["exp_id"])
    assert detail["experiment"]["state"] == "COMPLETED"
    trials = detail["trials"]
    assert len(trials) == 2
    assert {t["hparams"]["lr"] for t in trials} == {0.1, 0.3}
    assert all(t["state"] == "COMPLETED" for t in trials)
    assert all(t["units_done"] >= 4 for t in trials)
    assert sorted(method.validated) == sorted(method.created)
    # the method's progress reached the master (GET experiment detail)
    assert detail.get("progress") == 1.0


def test_events_endpoint_rejects_builtin_searcher(cluster):
    from determined_clone_tpu.api.client import MasterError

    session = cluster["session"]
    exp = session.create_experiment({
        "name": "builtin",
        "entrypoint": "model_def:Trial",
        "searcher": {"name": "single", "metric": "loss",
                     "max_length": {"batches": 100000}},
        "resources": {"slots_per_trial": 1},
        "checkpoint_storage": {"type": "shared_fs",
                               "host_path": str(cluster["tmp"] / "ckpts")},
        "hyperparameters": {"lr": 0.2},
    })
    with pytest.raises(MasterError) as err:
        session.request(
            "GET", f"/api/v1/experiments/{exp['id']}/searcher/events")
    assert err.value.status == 400
    session.kill_experiment(exp["id"])


class PickBestLocal(SearchMethod):
    """Three fixed-lr trials, single validation, close all, shutdown."""

    def __init__(self):
        self.lrs = [0.5, 0.2, 0.8]
        self.n_done = 0

    def initial_operations(self):
        return [Create(-1, {"lr": lr}) for lr in self.lrs]

    def on_trial_created(self, request_id):
        return [ValidateAfter(request_id, 2)]

    def on_validation_completed(self, request_id, metric, units):
        self.n_done += 1
        ops = [Close(request_id)]
        if self.n_done == len(self.lrs):
            ops.append(Shutdown())
        return ops

    def progress(self):
        return self.n_done / len(self.lrs)


def test_local_search_runner(tmp_path):
    import jax

    from determined_clone_tpu.config import ExperimentConfig
    from determined_clone_tpu.parallel import MeshSpec, make_mesh
    from tests.test_experiment_runner import QuadraticTrial

    cfg = ExperimentConfig.from_dict({
        "searcher": {"name": "custom", "metric": "loss",
                     "max_length": {"batches": 2}},
        "scheduling_unit": 2,
        "checkpoint_storage": {"type": "shared_fs", "host_path": str(tmp_path)},
        "hyperparameters": {"lr": 0.5},
        "max_restarts": 1,
    })
    mesh = make_mesh(MeshSpec(dp=1), jax.devices()[:1])
    result = LocalSearchRunner(PickBestLocal()).run(
        cfg, QuadraticTrial, storage_path=str(tmp_path), mesh=mesh)
    assert result.shutdown
    assert result.n_trials == 3
    assert all(t.state == "completed" for t in result.trials.values())
    # loss floor = lr → best is the smallest lr
    assert result.best_trial.hparams["lr"] == 0.2


def test_build_method_custom_points_to_runners():
    from determined_clone_tpu.config.experiment import SearcherConfig
    from determined_clone_tpu.config.hyperparameters import (
        HyperparameterSpace,
    )

    cfg = SearcherConfig.from_dict({"name": "custom", "metric": "loss"})
    with pytest.raises(ValueError) as err:
        build_method(cfg, HyperparameterSpace({}))
    assert "RemoteSearchRunner" in str(err.value)
