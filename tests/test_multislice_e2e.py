"""Master-scheduled multislice e2e: two fake 4-chip agents (= two v5e-4
slices) are reserved AS ONE GANG by the scheduler's slice-group path
(scheduler.cc find_fit n_slices branch), the rendezvous payload carries
slice assignments, and exec/trial.py builds the hybrid ICI×DCN mesh
(parallel/mesh.py make_multislice_mesh) — ZeRO-style fsdp inside each
slice's ICI, data parallelism across slices over DCN.

The reference has no multislice equivalent (SURVEY §7.7 — this is the
beat-the-reference axis); its closest analogue is the flat multi-node
gang, which tests/test_multi_agent_gang.py mirrors.
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
        # a 2-process world, 4 chips per process = 8 global devices
        assert jax.process_count() == 2, jax.process_count()
        assert jax.device_count() == 8, jax.device_count()
        mesh = self.context.mesh
        shape = dict(mesh.shape)
        # dcn {dp: 2} x ici {fsdp: 4} from the experiment's mesh hparam
        assert shape["dp"] == 2 and shape["fsdp"] == 4, shape
        # dcn-major: dp index == slice == owning process, so dp collectives
        # cross DCN exactly once and fsdp collectives stay on-slice
        devs = mesh.devices.reshape(2, -1)
        for slice_id in range(2):
            procs = {d.process_index for d in devs[slice_id]}
            assert procs == {slice_id}, (slice_id, procs)
        return {"w": jnp.zeros((4, 4))}

    def optimizer(self):
        return optax.sgd(0.1)

    def loss(self, params, batch, rng):
        pred = batch @ params["w"]
        return jnp.mean((pred - 1.0) ** 2), {}

    def training_data(self):
        rng = np.random.RandomState(0)
        for _ in range(64):
            yield rng.randn(8, 4).astype(np.float32)

    def validation_data(self):
        return [np.ones((8, 4), np.float32)]

    @property
    def global_batch_size(self):
        return 8
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
    tmp = tmp_path_factory.mktemp("multislice")

    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    base_env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": str(REPO),
        # each agent process models ONE 4-chip slice
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "DCT_AGENT_SLOTS": "4",
        "DCT_AGENT_TOPOLOGY": "v5e-4",
    }
    master = subprocess.Popen(
        [str(MASTER_BIN), "--port", str(port), "--data-dir",
         str(tmp / "master-data")],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=base_env,
    )
    agents = []
    for i in range(2):
        workdir = tmp / f"slice-{i}"
        workdir.mkdir()
        (workdir / "model_def.py").write_text(TRIAL_MODULE)
        agents.append(subprocess.Popen(
            [str(AGENT_BIN), "--master-port", str(port),
             "--id", f"slice-agent-{i}", "--work-dir", str(workdir)],
            cwd=str(workdir),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=base_env,
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


def wait_for(predicate, timeout=120, interval=1.0, desc="condition",
             context=lambda: ""):
    deadline = time.time() + timeout
    while time.time() < deadline:
        result = predicate()
        if result:
            return result
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {desc}{context()}")


def test_two_slice_gang_builds_ici_dcn_mesh(cluster):
    session = cluster["session"]
    exp = session.create_experiment({
        "name": "multislice2x4",
        "entrypoint": "model_def:Trial",
        "searcher": {"name": "single", "metric": "loss",
                     "max_length": {"batches": 4}},
        "resources": {
            "slots_per_trial": 8,
            "topology": {"slices": 2, "slice_shape": "v5e-4"},
        },
        "scheduling_unit": 2,
        "checkpoint_storage": {"type": "shared_fs",
                               "host_path": str(cluster["tmp"] / "ckpts")},
        "hyperparameters": {
            "mesh": {"ici": {"fsdp": 4}, "dcn": {"dp": 2}},
        },
        "max_restarts": 0,
    })

    def log_tail():
        # the agents' own files: the master holds a rank's lines only
        # once the rank has exited
        tails = [f"\n{path.parent.name}/{path.name}, last lines:\n"
                 + "\n".join(line[:200] for line in path.read_text(
                     errors="replace").splitlines()[-20:])
                 for path in sorted(
                     cluster["tmp"].glob("slice-*/task-*.log"))]
        return "".join(tails) or "\n(no rank wrote a log)"

    def done():
        d = session.get_experiment(exp["id"])
        state = d["experiment"]["state"]
        if state == "ERRORED":
            raise AssertionError(
                "multislice experiment ERRORED:" + log_tail())
        return d if state == "COMPLETED" else None

    detail = wait_for(done, desc="multislice completion", context=log_tail)
    trial = detail["trials"][0]
    assert trial["state"] == "COMPLETED"

    # the rendezvous payload carried the slice-group assignment
    rdv = session.get(
        f"/api/v1/allocations/trial-{trial['id']}.0/rendezvous")
    assert rdv["world_size"] == 2
    assert rdv["n_slices"] == 2
    assert rdv["slice_ids"] == [0, 1]

    # validation metrics flowed (chief reported through the sharded step)
    metrics = session.trial_metrics(trial["id"])
    val = [m for m in metrics if m.get("group") == "validation"]
    assert val


def test_slice_group_waits_for_matching_topology(cluster):
    """A 4-slice request can never fit on two v5e-4 agents: it must stay
    QUEUED (all-or-nothing slice-group reservation), not half-schedule."""
    session = cluster["session"]
    exp = session.create_experiment({
        "name": "multislice-unfittable",
        "entrypoint": "model_def:Trial",
        "searcher": {"name": "single", "metric": "loss",
                     "max_length": {"batches": 2}},
        "resources": {
            "slots_per_trial": 16,
            "topology": {"slices": 4, "slice_shape": "v5e-4"},
        },
        "hyperparameters": {},
        "max_restarts": 0,
    })
    time.sleep(3)  # several scheduler ticks
    d = session.get_experiment(exp["id"])
    trials = d["trials"]
    assert d["experiment"]["state"] in ("ACTIVE", "QUEUED", "RUNNING")
    assert all(t["state"] in ("QUEUED", "PENDING") for t in trials), trials
    session.kill_experiment(exp["id"])
