"""NTSC tasks (shell/command/notebook/tensorboard) + master reverse proxy.

≈ the reference's NTSC e2e behavior: task create → allocation → container →
proxy registration → master routes /proxy/:taskID/* (master/internal/command,
master/internal/proxy/proxy.go), idle watcher kill (task/idle/watcher.go).
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


def build_binaries():
    if MASTER_BIN.exists() and AGENT_BIN.exists():
        return True
    r = subprocess.run(["make", "-C", str(MASTER_DIR)], capture_output=True)
    return r.returncode == 0


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    if not build_binaries():
        pytest.skip("C++ master/agent build unavailable")
    tmp = tmp_path_factory.mktemp("ntsc")
    workdir = tmp / "agent-work"
    workdir.mkdir()

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
        [str(AGENT_BIN), "--master-port", str(port), "--id", "ntsc-agent",
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


def wait_for(predicate, timeout=60, interval=0.3, desc="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        result = predicate()
        if result:
            return result
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {desc}")


def wait_proxied(session, task_id):
    """Task RUNNING with a registered proxy address."""
    return wait_for(
        lambda: (lambda t: t if t["state"] == "RUNNING" and
                 t["proxy_address"] else None)(session.get_task(task_id)),
        desc=f"{task_id} running + proxied",
    )


def test_shell_task_exec_through_proxy(cluster):
    session = cluster["session"]
    task = session.create_task("shell", name="sh1")
    assert task["task_type"] == "shell"
    assert task["slots"] == 0

    wait_proxied(session, task["id"])
    out = session.proxy(task["id"], "/exec", "POST",
                        {"cmd": ["echo", "hello-ntsc"]})
    assert out["code"] == 0
    assert out["stdout"].strip() == "hello-ntsc"

    # landing page through the proxy
    page = session.proxy(task["id"], "/")
    assert page["mode"] == "shell"

    session.kill_task(task["id"])
    wait_for(
        lambda: session.get_task(task["id"])["state"] == "CANCELED",
        desc="task canceled",
    )


def test_command_task_runs_user_argv(cluster):
    session = cluster["session"]
    marker = cluster["tmp"] / "cmd-ran.txt"
    task = session.create_task(
        "command", name="cmd1",
        cmd=["python", "-c",
             f"open({str(marker)!r}, 'w').write('done')"],
    )
    wait_for(
        lambda: session.get_task(task["id"])["state"] == "COMPLETED",
        desc="command task completion",
    )
    assert marker.read_text() == "done"
    assert session.get_task(task["id"])["exit_code"] == 0


def test_command_task_requires_argv(cluster):
    from determined_clone_tpu.api.client import MasterError

    with pytest.raises(MasterError) as err:
        cluster["session"].create_task("command", name="bad")
    assert err.value.status == 400


def test_task_listing_and_filter(cluster):
    session = cluster["session"]
    task = session.create_task("notebook", name="nb1")
    all_ids = {t["id"] for t in session.list_tasks()}
    assert task["id"] in all_ids
    nb_ids = {t["id"] for t in session.list_tasks("notebook")}
    assert task["id"] in nb_ids
    sh_ids = {t["id"] for t in session.list_tasks("shell")}
    assert task["id"] not in sh_ids

    # notebook fallback server responds through the proxy
    wait_proxied(session, task["id"])
    page = session.proxy(task["id"], "/")
    assert page["mode"] == "notebook"
    session.kill_task(task["id"])


def test_idle_watcher_reaps_idle_task(cluster):
    session = cluster["session"]
    task = session.create_task("shell", name="idle1", idle_timeout=2.0)
    wait_proxied(session, task["id"])
    # no proxy traffic → the idle watcher cancels it (idle/watcher.go)
    final = wait_for(
        lambda: (lambda t: t if t["state"] == "CANCELED" else None)(
            session.get_task(task["id"])),
        timeout=30, desc="idle task reaped",
    )
    assert final["state"] == "CANCELED"


def test_tensorboard_task_serves_metric_data(cluster):
    session = cluster["session"]
    task = session.create_task("tensorboard", name="tb1", experiment_ids=[])
    wait_proxied(session, task["id"])
    data = session.proxy(task["id"], "/data")
    assert data == {"experiments": {}}
    session.kill_task(task["id"])
