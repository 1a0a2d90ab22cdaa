"""Container runtime + reattach-after-restart e2e.

≈ the reference's container reattach (agent/internal/containers/
manager.go:76 + e2e managed-cluster agent-restart tests): with the
container runtime, tasks run detached under a supervisor, survive the
agent being SIGKILLed, and a restarted agent re-adopts them from its state
file — the master never sees the task exit.
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


@pytest.fixture()
def cluster(tmp_path):
    if not build_binaries():
        pytest.skip("C++ master/agent build unavailable")
    workdir = tmp_path / "agent-work"
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
         str(tmp_path / "master-data"), "--agent-timeout", "30"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
    )

    def spawn_agent():
        return subprocess.Popen(
            [str(AGENT_BIN), "--master-port", str(port), "--id", "ra-agent",
             "--work-dir", str(workdir), "--runtime", "container"],
            cwd=str(workdir),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
        )

    agent = spawn_agent()

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

    state = {"agent": agent}
    yield {"session": session, "tmp": tmp_path, "workdir": workdir,
           "spawn_agent": spawn_agent, "state": state}

    state["agent"].kill()
    master.kill()
    state["agent"].wait(timeout=10)
    master.wait(timeout=10)


def wait_for(predicate, timeout=60, interval=0.3, desc="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        result = predicate()
        if result:
            return result
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {desc}")


def test_task_survives_agent_restart(cluster):
    session = cluster["session"]
    marker = cluster["tmp"] / "survived.txt"
    # a task that takes ~6s and then writes a marker: long enough to kill
    # the agent mid-flight, short enough for the test
    task = session.create_task(
        "command", name="survivor",
        cmd=["python", "-c",
             "import time; time.sleep(6); "
             f"open({str(marker)!r}, 'w').write('alive')"],
    )
    tid = task["id"]
    wait_for(lambda: session.get_task(tid)["state"] == "RUNNING",
             desc="task running")

    # SIGKILL the agent mid-task: with the container runtime the
    # supervisor+task pair keeps running (own session, no PDEATHSIG)
    agent = cluster["state"]["agent"]
    agent.kill()
    agent.wait(timeout=10)
    assert not marker.exists(), "task finished before the agent was killed"
    # the state file the restarted agent reattaches from
    assert (cluster["workdir"] / "agent-state.json").exists()

    # restart the agent: it must re-adopt the task, keep reporting it
    # running, and deliver the real exit when it completes
    cluster["state"]["agent"] = cluster["spawn_agent"]()
    final = wait_for(
        lambda: (lambda t: t if t["state"] == "COMPLETED" else None)(
            session.get_task(tid)),
        timeout=60, desc="task completion after reattach",
    )
    assert final["exit_code"] == 0
    assert marker.read_text() == "alive"
    # the master never saw a failure: restarts/kill path untouched
    assert final["state"] == "COMPLETED"


def test_exit_while_agent_down_is_reported_on_restart(cluster):
    session = cluster["session"]
    task = session.create_task(
        "command", name="fast-exit",
        cmd=["python", "-c", "import time; time.sleep(1.5)"],
    )
    tid = task["id"]
    wait_for(lambda: session.get_task(tid)["state"] == "RUNNING",
             desc="task running")
    agent = cluster["state"]["agent"]
    agent.kill()
    agent.wait(timeout=10)
    # let the task finish while no agent is watching
    time.sleep(3)
    cluster["state"]["agent"] = cluster["spawn_agent"]()
    final = wait_for(
        lambda: (lambda t: t if t["state"] == "COMPLETED" else None)(
            session.get_task(tid)),
        timeout=30, desc="exit reported after restart",
    )
    # the supervisor outlived the agent and recorded the real exit code
    assert final["exit_code"] == 0
