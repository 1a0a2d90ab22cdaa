"""CLI (`det`) + Python SDK + context-dir upload e2e.

≈ the reference's CLI tests and SDK usage (harness/determined/cli,
common/experimental), plus the context-directory chain: client base64
upload → master storage → agent materialization → trial import
(cli/experiment.py:242 → prep_container.py:29).
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

TRIAL_MODULE = '''
import jax.numpy as jnp
import numpy as np
import optax

from determined_clone_tpu.training import JaxTrial
from uploaded_helper import TARGET


class Trial(JaxTrial):
    def initial_params(self, rng):
        return {"w": jnp.zeros(())}

    def optimizer(self):
        return optax.sgd(0.3)

    def loss(self, params, batch, rng):
        return (params["w"] - TARGET) ** 2, {}

    def training_data(self):
        for _ in range(64):
            yield np.zeros((2, 1), np.float32)

    def validation_data(self):
        return [np.zeros((2, 1), np.float32)]

    @property
    def global_batch_size(self):
        return 2
'''

HELPER_MODULE = "TARGET = 1.5\n"


def build_binaries():
    if MASTER_BIN.exists() and AGENT_BIN.exists():
        return True
    r = subprocess.run(["make", "-C", str(MASTER_DIR)], capture_output=True)
    return r.returncode == 0


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    if not build_binaries():
        pytest.skip("C++ master/agent build unavailable")
    tmp = tmp_path_factory.mktemp("clisdk")
    workdir = tmp / "agent-work"
    workdir.mkdir()  # deliberately NO model_def here: context upload must work

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
        [str(AGENT_BIN), "--master-port", str(port), "--id", "cli-agent",
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
           "master_addr": f"127.0.0.1:{port}"}

    agent.kill()
    master.kill()
    agent.wait(timeout=10)
    master.wait(timeout=10)


@pytest.fixture()
def det(cluster, tmp_path, monkeypatch):
    """Invoke the CLI in-process against the fixture master."""
    monkeypatch.setenv("HOME", str(tmp_path))  # isolate ~/.dct auth store
    from determined_clone_tpu.cli import main

    def run(*argv):
        return main(["-m", cluster["master_addr"], *argv])

    return run


def write_model_dir(tmp) -> Path:
    model_dir = tmp / "model_def"
    model_dir.mkdir(exist_ok=True)
    (model_dir / "model_def.py").write_text(TRIAL_MODULE)
    (model_dir / "uploaded_helper.py").write_text(HELPER_MODULE)
    return model_dir


def exp_config(cluster, name="cli-exp", batches=6):
    return {
        "name": name,
        "entrypoint": "model_def:Trial",
        "searcher": {"name": "single", "metric": "loss",
                     "max_length": {"batches": batches}},
        "resources": {"slots_per_trial": 1},
        "scheduling_unit": 2,
        "checkpoint_storage": {"type": "shared_fs",
                               "host_path": str(cluster["tmp"] / "ckpts")},
        "hyperparameters": {},
        "max_restarts": 0,
    }


def test_sdk_experiment_with_context_upload(cluster, tmp_path):
    """The agent workdir has no model code — the trial can only succeed if
    the uploaded context directory (two modules) is materialized."""
    from determined_clone_tpu.sdk import Determined

    d = Determined("127.0.0.1", cluster["port"])
    model_dir = write_model_dir(tmp_path)
    exp = d.create_experiment(exp_config(cluster, "sdk-ctx"),
                              model_dir=str(model_dir))
    state = exp.wait(timeout=180)
    assert state == "COMPLETED"

    trials = exp.trials()
    assert len(trials) == 1
    metrics = trials[0].metrics()
    assert metrics, "no metrics reported"
    # loss on the validation group converges toward (w-1.5)^2 -> 0
    val = [m for m in metrics if m.get("group") == "validation"]
    assert val and val[-1]["metrics"]["loss"] < 0.5

    ckpts = exp.checkpoints()
    assert ckpts
    out = tmp_path / "dl"
    ckpts[-1].download(str(out))
    assert any(out.iterdir())

    top = exp.top_checkpoint()
    assert top is not None

    # lifecycle surface: archive the finished experiment, then delete it
    exp.archive()
    assert exp.describe()["experiment"]["archived"] is True
    exp.archive(archived=False)
    exp.delete()
    import pytest as _pytest

    from determined_clone_tpu.api.client import MasterError

    with _pytest.raises(MasterError):
        exp.describe()


def test_cli_full_surface(cluster, det, tmp_path, capsys):
    import yaml

    # master info
    assert det("master", "info") == 0
    info = json.loads(capsys.readouterr().out)
    assert info["cluster_name"] == "dct"

    # experiment create from YAML + follow
    model_dir = write_model_dir(tmp_path)
    cfg_path = tmp_path / "config.yaml"
    cfg_path.write_text(yaml.safe_dump(exp_config(cluster, "cli-exp")))
    rc = det("experiment", "create", str(cfg_path), str(model_dir),
             "--follow", "--timeout", "180")
    out = capsys.readouterr().out
    assert rc == 0
    assert "COMPLETED" in out
    exp_id = int(out.split("Created experiment ")[1].split()[0])

    # listing/describe/metrics/logs
    assert det("experiment", "list") == 0
    assert f"cli-exp" in capsys.readouterr().out
    assert det("experiment", "describe", str(exp_id)) == 0
    detail = json.loads(capsys.readouterr().out)
    trial_id = detail["trials"][0]["id"]
    assert det("trial", "metrics", str(trial_id)) == 0
    assert json.loads(capsys.readouterr().out)
    assert det("trial", "logs", str(trial_id)) == 0
    capsys.readouterr()

    # checkpoints: list + download
    assert det("checkpoint", "list", str(exp_id)) == 0
    uuid = capsys.readouterr().out.splitlines()[2].split("|")[0].strip()
    dl_dir = tmp_path / "ckpt-dl"
    assert det("checkpoint", "download", uuid, "-o", str(dl_dir)) == 0
    capsys.readouterr()
    assert any(dl_dir.iterdir())

    # model registry round trip via CLI
    assert det("model", "create", "cli-model") == 0
    capsys.readouterr()
    assert det("model", "register-version", "cli-model", uuid) == 0
    assert "version 1" in capsys.readouterr().out

    # agents, job queue, workspaces
    assert det("agent", "list") == 0
    assert "cli-agent" in capsys.readouterr().out
    assert det("job", "list") == 0
    capsys.readouterr()
    assert det("workspace", "create", "cli-ws") == 0
    capsys.readouterr()
    assert det("workspace", "list") == 0
    assert "cli-ws" in capsys.readouterr().out

    # templates
    tpl_path = tmp_path / "tpl.yaml"
    tpl_path.write_text(yaml.safe_dump({"max_restarts": 2}))
    assert det("template", "set", "cli-tpl", str(tpl_path)) == 0
    capsys.readouterr()
    assert det("template", "list") == 0
    assert "cli-tpl" in capsys.readouterr().out

    # config override plumbing
    cfg2 = exp_config(cluster, "cli-exp2", batches=2)
    cfg2_path = tmp_path / "config2.yaml"
    cfg2_path.write_text(yaml.safe_dump(cfg2))
    assert det("experiment", "create", str(cfg2_path), str(model_dir),
               "--config-override", "name=overridden") == 0
    capsys.readouterr()
    assert det("experiment", "list") == 0
    assert "overridden" in capsys.readouterr().out


def test_cli_auth_login_logout(cluster, det, capsys):
    assert det("user", "login", "admin", "--password", "") == 0
    capsys.readouterr()
    assert det("user", "whoami") == 0
    assert "admin" in capsys.readouterr().out
    assert det("user", "create", "cliuser", "--password", "pw") == 0
    capsys.readouterr()
    assert det("user", "list") == 0
    assert "cliuser" in capsys.readouterr().out
    assert det("user", "logout") == 0
    capsys.readouterr()


def test_cli_shell_lifecycle(cluster, det, capsys):
    assert det("shell", "start", "--name", "cli-sh") == 0
    out = capsys.readouterr().out
    task_id = out.split("Started shell ")[1].strip()

    session = cluster["session"]
    deadline = time.time() + 60
    while time.time() < deadline:
        t = session.get_task(task_id)
        if t["state"] == "RUNNING" and t["proxy_address"]:
            break
        time.sleep(0.3)
    else:
        pytest.fail("shell task never came up")

    rc = det("shell", "exec", task_id, "echo", "from-cli")
    out = capsys.readouterr().out
    assert rc == 0
    assert "from-cli" in out

    assert det("task", "list") == 0
    assert task_id in capsys.readouterr().out
    assert det("task", "kill", task_id) == 0
    capsys.readouterr()
