"""Streaming log follow (VERDICT r3 #3): long-poll follow mode end-to-end.

The reference streams TrialLogs over gRPC with a follow flag
(/root/reference/proto/src/determined/api/v1/api.proto:781). Here the
master holds GET /allocations/:id/logs?follow=N open on a condition
variable pinged by every store append, so a follower sees new lines
within milliseconds of ingestion — no reconnect-per-poll, no tail
re-fetch — and is told end_of_stream when the allocation is terminal
and drained.
"""
import json
import os
import subprocess
import threading
import time
import urllib.request
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
    tmp = tmp_path_factory.mktemp("follow")
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
        [str(AGENT_BIN), "--master-port", str(port), "--id", "follow-agent",
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

    yield {"session": session, "port": port,
           "master_addr": f"127.0.0.1:{port}"}

    agent.kill()
    master.kill()
    agent.wait(timeout=10)
    master.wait(timeout=10)


def wait_running(session, tid):
    deadline = time.time() + 30
    while time.time() < deadline:
        if session.get_task(tid)["state"] in ("RUNNING", "PULLING"):
            return
        time.sleep(0.2)
    raise AssertionError(f"task {tid} never started")


def drain_startup_noise(session, port, tid):
    """The shell task logs its own startup line on the agent's shipping
    cadence; settle and consume it so the assertions below are exact."""
    time.sleep(2.5)
    out, _ = follow_get(port, tid, 0, 0)
    return out["next_offset"]


def follow_get(port, alloc_id, offset, follow, timeout=60):
    t0 = time.monotonic()
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/api/v1/allocations/{alloc_id}/logs"
            f"?limit=1000&offset={offset}&follow={follow}",
            timeout=timeout) as resp:
        return json.loads(resp.read()), time.monotonic() - t0


def test_follow_blocks_until_new_line_arrives(cluster):
    session = cluster["session"]
    port = cluster["port"]
    task = session.create_task("shell", name="follow-sh")
    tid = task["id"]
    wait_running(session, tid)
    base = drain_startup_noise(session, port, tid)

    session.post(f"/api/v1/allocations/{tid}/logs", {"logs": ["line-0"]})

    # backlog is served instantly, with a cursor
    out, took = follow_get(port, tid, base, 15)
    assert [r["log"] for r in out["logs"]] == ["line-0"]
    assert out["next_offset"] == base + 1
    assert not out["end_of_stream"]
    assert took < 5  # no pointless wait when data is ready

    # an empty cursor BLOCKS until the next line lands, then returns it
    result = {}

    def poll():
        result["out"], result["took"] = follow_get(port, tid,
                                                   out["next_offset"], 20)

    t = threading.Thread(target=poll)
    t.start()
    time.sleep(1.5)
    session.post(f"/api/v1/allocations/{tid}/logs", {"logs": ["line-1"]})
    t.join(timeout=30)
    assert not t.is_alive()
    assert [r["log"] for r in result["out"]["logs"]] == ["line-1"]
    # it genuinely long-polled: waited for the post, woke promptly after
    assert 1.0 < result["took"] < 8.0
    session.kill_task(tid)


def test_follow_reports_end_of_stream_on_terminal(cluster):
    session = cluster["session"]
    port = cluster["port"]
    task = session.create_task("shell", name="follow-end")
    tid = task["id"]
    wait_running(session, tid)
    base = drain_startup_noise(session, port, tid)
    session.post(f"/api/v1/allocations/{tid}/logs", {"logs": ["bye"]})
    session.kill_task(tid)
    deadline = time.time() + 30
    while time.time() < deadline:
        if session.get_task(tid)["state"] in ("COMPLETED", "ERRORED",
                                              "CANCELED"):
            break
        time.sleep(0.2)

    # drain: records first (end_of_stream false while lines remain) ...
    out, _ = follow_get(port, tid, base, 10)
    assert "bye" in [r["log"] for r in out["logs"]]
    assert not out["end_of_stream"]
    # ... then a prompt end_of_stream, NOT a 10 s block
    out, took = follow_get(port, tid, out["next_offset"], 10)
    assert out["logs"] == []
    assert out["end_of_stream"]
    assert took < 5


def test_client_follow_generator_and_cli_tail(cluster):
    """session.follow_task_logs streams lines as they land and returns on
    end_of_stream; `det task logs -f` prints them and exits."""
    session = cluster["session"]
    task = session.create_task("shell", name="follow-gen")
    tid = task["id"]
    wait_running(session, tid)
    drain_startup_noise(session, cluster["port"], tid)
    session.post(f"/api/v1/allocations/{tid}/logs", {"logs": ["a", "b"]})

    got = []

    def consume():
        for rec in session.follow_task_logs(tid, follow_seconds=10):
            got.append(rec["log"])

    t = threading.Thread(target=consume)
    t.start()
    time.sleep(1.0)
    session.post(f"/api/v1/allocations/{tid}/logs", {"logs": ["c"]})
    time.sleep(1.0)
    session.kill_task(tid)
    t.join(timeout=45)
    assert not t.is_alive(), "generator did not stop at end_of_stream"

    def subsequence(needles, haystack):
        it = iter(haystack)
        return all(any(n == h for h in it) for n in needles)

    # the task's own startup lines interleave; ours arrive in order
    assert subsequence(["a", "b", "c"], got), got

    # the CLI path over the same records (task already terminal: -f drains
    # and exits — the live blocking path is covered above)
    import contextlib
    import io

    from determined_clone_tpu.cli import main

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main(["-m", cluster["master_addr"], "task", "logs", "-f", tid])
    assert rc == 0
    assert subsequence(["a", "b", "c"], buf.getvalue().splitlines())
