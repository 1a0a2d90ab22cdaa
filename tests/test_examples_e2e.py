"""The five BASELINE.json configs run end-to-end through `det experiment
create` on artificial slots — the reference's nightly pattern
(e2e_tests/tests/nightly/test_distributed.py:15 submits the committed
example configs and waits for COMPLETED).

Each example directory under examples/ is submitted with its committed
YAML + its model-def context, scaled down via --config-override (the CLI's
dotted-path overrides) so CI on one CPU core finishes in minutes; the
committed configs keep real-scale hyperparameters for hardware runs.
"""
import json
import os
import subprocess
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
EXAMPLES = REPO / "examples"
MASTER_DIR = REPO / "determined_clone_tpu" / "master"
MASTER_BIN = MASTER_DIR / "build" / "dct-master"
AGENT_BIN = MASTER_DIR / "build" / "dct-agent"


def build_binaries():
    if MASTER_BIN.exists() and AGENT_BIN.exists():
        return True
    r = subprocess.run(["make", "-C", str(MASTER_DIR)], capture_output=True)
    return r.returncode == 0


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    if not build_binaries():
        pytest.skip("C++ master/agent build unavailable")
    tmp = tmp_path_factory.mktemp("examples-cluster")
    workdir = tmp / "agent-work"
    workdir.mkdir()

    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        # the distributed examples want 8 chips; give the trial processes
        # a virtual 8-device host (the conftest trick, but for the agent's
        # children: conftest's flags, its wait for a CPU collective's
        # participants among them)
        "XLA_FLAGS": os.environ["XLA_FLAGS"],
        "PYTHONPATH": str(REPO),
        "DCT_AGENT_SLOTS": "8",
        "DCT_AGENT_TOPOLOGY": "v5e-8",
    }
    master = subprocess.Popen(
        [str(MASTER_BIN), "--port", str(port), "--data-dir",
         str(tmp / "master-data")],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
    )
    agent = subprocess.Popen(
        [str(AGENT_BIN), "--master-port", str(port), "--id",
         "examples-agent", "--work-dir", str(workdir)],
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
    monkeypatch.setenv("HOME", str(tmp_path))  # isolate ~/.dct auth store
    from determined_clone_tpu.cli import main

    def run(*argv):
        return main(["-m", cluster["master_addr"], *argv])

    return run


def _submit(cluster, det, config_path, model_dir, overrides, name):
    """`det experiment create -f`: returns (exit_code, experiment_detail)."""
    args = ["experiment", "create", str(config_path), str(model_dir),
            "--config-override", f"name={name}",
            "--config-override",
            "checkpoint_storage.type=shared_fs",
            "--config-override",
            f"checkpoint_storage.host_path={cluster['tmp'] / 'ckpts'}",
            "-f", "--timeout", "420"]
    for ov in overrides:
        args += ["--config-override", ov]
    rc = det(*args)
    session = cluster["session"]
    exps = [e for e in session.list_experiments() if e["name"] == name]
    assert exps, f"experiment {name} not found after create"
    detail = session.get_experiment(exps[-1]["id"])
    if rc != 0:  # surface trial logs before failing
        for t in detail["trials"]:
            logs = session.task_logs(f"trial-{t['id']}.0")
            print(f"--- trial {t['id']} logs ---")
            for line in logs[-40:]:
                print(json.dumps(line)[:400])
    return rc, detail


def _linked_checkpoint(cluster, trial, timeout=60):
    """The trial's ``latest_checkpoint`` once the master holds it. The
    master publishes COMPLETED from the report of the last validation; the
    harness reports the checkpoint it saves on its way out in a later
    request, so the link is waited for, not read at once."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        for t in cluster["session"].get_experiment(
                trial["experiment_id"])["trials"]:
            if t["id"] == trial["id"] and t["latest_checkpoint"]:
                return t["latest_checkpoint"]
        time.sleep(0.5)
    return None


TINY_COMMON = [
    "scheduling_unit=2",
    "min_validation_period.batches=4",
    "max_restarts=0",
]


def test_mnist_const(cluster, det):
    rc, detail = _submit(
        cluster, det, EXAMPLES / "mnist" / "const.yaml", EXAMPLES / "mnist",
        TINY_COMMON + [
            "searcher.max_length.batches=8",
            "hyperparameters.global_batch_size=16",
            "hyperparameters.n_filters_1=4",
            "hyperparameters.n_filters_2=8",
        ], name="ex-mnist-const")
    assert rc == 0 and detail["experiment"]["state"] == "COMPLETED"
    [trial] = detail["trials"]
    # real held-out digits accuracy was reported through the platform
    metrics = cluster["session"].trial_metrics(trial["id"])
    val = [m for m in metrics if m["group"] == "validation"]
    assert val and "accuracy" in val[-1]["metrics"]
    assert _linked_checkpoint(cluster, trial)


def test_mnist_distributed_dp8(cluster, det):
    rc, detail = _submit(
        cluster, det, EXAMPLES / "mnist" / "distributed.yaml",
        EXAMPLES / "mnist",
        TINY_COMMON + [
            "searcher.max_length.batches=6",
            "hyperparameters.global_batch_size=16",  # 2 per virtual chip
            "hyperparameters.n_filters_1=4",
            "hyperparameters.n_filters_2=8",
        ], name="ex-mnist-dp8")
    assert rc == 0 and detail["experiment"]["state"] == "COMPLETED"


def test_resnet_distributed(cluster, det):
    rc, detail = _submit(
        cluster, det, EXAMPLES / "resnet50" / "distributed.yaml",
        EXAMPLES / "resnet50",
        TINY_COMMON + [
            "searcher.max_length.batches=4",
            "hyperparameters.global_batch_size=16",
            "hyperparameters.depth=26",
            "hyperparameters.width=8",
            "hyperparameters.n_classes=10",
            "hyperparameters.image_size=16",
            "hyperparameters.n_train=128",
        ], name="ex-resnet")
    assert rc == 0 and detail["experiment"]["state"] == "COMPLETED"


def test_bert_core_api(cluster, det):
    rc, detail = _submit(
        cluster, det, EXAMPLES / "bert_finetune" / "const.yaml",
        EXAMPLES / "bert_finetune",
        ["max_restarts=0",
         "searcher.max_length.batches=20",
         "hyperparameters.global_batch_size=8",
         "hyperparameters.n_layers=2",
         "hyperparameters.d_model=32",
         "hyperparameters.n_heads=2",
         "hyperparameters.d_ff=64",
         "hyperparameters.vocab_size=128",
         "hyperparameters.seq_len=32",
         ], name="ex-bert-core")
    assert rc == 0 and detail["experiment"]["state"] == "COMPLETED"
    [trial] = detail["trials"]
    # the Core API script reported validation + completed the searcher op
    metrics = cluster["session"].trial_metrics(trial["id"])
    val = [m for m in metrics if m["group"] == "validation"]
    assert val and "accuracy" in val[-1]["metrics"]
    # and uploaded a checkpoint through core_context.checkpoint
    assert _linked_checkpoint(cluster, trial)


def test_bert_core_api_resume_local(tmp_path):
    """The restore path the cluster test can't reach (max_restarts=0 there):
    run the Core API script locally, then re-run it pointed at the uploaded
    checkpoint — it must resume batches_done and complete the (already
    satisfied) searcher op without retraining."""
    import sys

    sys.path.insert(0, str(EXAMPLES / "bert_finetune"))
    try:
        import train_bert
    finally:
        sys.path.pop(0)
    from determined_clone_tpu import core
    from determined_clone_tpu.config.experiment import ExperimentConfig

    config = ExperimentConfig.from_dict({
        "name": "bert-resume-local",
        "entrypoint": "train_bert:main",
        "searcher": {"name": "single", "metric": "accuracy",
                     "smaller_is_better": False,
                     "max_length": {"batches": 3}},
        "hyperparameters": {},
    })
    hp = {"global_batch_size": 4, "n_layers": 1, "d_model": 16,
          "n_heads": 2, "d_ff": 32, "vocab_size": 64, "seq_len": 16}

    class Info:
        hparams = hp
        latest_checkpoint = None

    with core.init(config=config, storage_path=str(tmp_path)) as cctx:
        res = train_bert.main(cctx, Info)
    assert res == {"state": "completed", "batches": 3}

    recs = [json.loads(line)
            for line in open(tmp_path / "checkpoints.jsonl")]
    assert recs and recs[-1]["metadata"]["steps_completed"] == 3

    class Resumed:
        hparams = hp
        latest_checkpoint = recs[-1]["storage_id"]

    with core.init(config=config, storage_path=str(tmp_path)) as cctx:
        res2 = train_bert.main(cctx, Resumed)
    # op target (3) already met by the restored batches_done: no retraining
    assert res2 == {"state": "completed", "batches": 3}


def test_gpt_fsdp(cluster, det):
    rc, detail = _submit(
        cluster, det, EXAMPLES / "gpt_fsdp" / "fsdp.yaml",
        EXAMPLES / "gpt_fsdp",
        TINY_COMMON + [
            "searcher.max_length.batches=4",
            "hyperparameters.global_batch_size=8",
            "hyperparameters.n_layers=2",
            "hyperparameters.d_model=64",
            "hyperparameters.n_heads=4",
            "hyperparameters.d_ff=128",
            "hyperparameters.vocab_size=512",
            "hyperparameters.seq_len=64",
            "hyperparameters.n_train_tokens=10000",
            "hyperparameters.remat=false",
            "hyperparameters.attention_impl=mha",
        ], name="ex-gpt-fsdp")
    assert rc == 0 and detail["experiment"]["state"] == "COMPLETED"
