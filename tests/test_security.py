"""Auth boundary e2e: --auth-required master, allocation tokens, KDF.

≈ the reference's auth model: user sessions gate the API surface
(master/internal/api_auth.go), allocation-scoped session tokens carry the
data plane (master/internal/task/allocation_service.go), and the proxy is
part of the authenticated surface (master/internal/proxy/proxy.go).
Covers the round-1 ADVICE findings: anonymous /proxy dispatch, /exec
exposure, task-server interface-binding trust, FNV password hashing.
"""
import json
import os
import subprocess
import time
import urllib.error
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
    tmp = tmp_path_factory.mktemp("sec")
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
         str(tmp / "master-data"), "--auth-required"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
    )
    agent = subprocess.Popen(
        [str(AGENT_BIN), "--master-port", str(port), "--id", "sec-agent",
         "--work-dir", str(workdir)],
        cwd=str(workdir),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
    )

    from determined_clone_tpu.api.client import MasterSession

    session = MasterSession("127.0.0.1", port, timeout=10, retries=20)
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            session.login("admin", "")
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


def raw_request(port, method, path, body=None, headers=None, host="127.0.0.1"):
    """Anonymous/direct HTTP without MasterSession's token handling.
    Returns (status, parsed-or-text body)."""
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        f"http://{host}:{port}{path}", data=data, method=method,
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            text = resp.read().decode()
            status = resp.status
    except urllib.error.HTTPError as e:
        text = e.read().decode(errors="replace")
        status = e.code
    try:
        return status, json.loads(text)
    except ValueError:
        return status, text


def wait_for(predicate, timeout=60, interval=0.3, desc="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        result = predicate()
        if result:
            return result
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {desc}")


def read_master_snapshot(data_dir):
    """The persisted master state, whichever store backend is active
    (sqlite kv table, or the legacy snapshot.json)."""
    db = data_dir / "master.db"
    if db.exists():
        import sqlite3

        with sqlite3.connect(db) as conn:
            row = conn.execute(
                "SELECT value FROM kv WHERE key='snapshot'").fetchone()
        if row:
            return json.loads(row[0])
        return None
    snap = data_dir / "snapshot.json"
    if snap.exists():
        return json.loads(snap.read_text())
    return None


def test_anonymous_api_rejected(cluster):
    port = cluster["port"]
    for method, path in [
        ("GET", "/api/v1/experiments"),
        ("GET", "/api/v1/tasks"),
        ("GET", "/api/v1/users"),
        ("POST", "/api/v1/tasks"),
        ("GET", "/api/v1/job-queue"),
    ]:
        status, body = raw_request(port, method, path, body={} if method == "POST" else None)
        assert status == 401, f"{method} {path} -> {status} {body}"


def test_login_and_me(cluster):
    port = cluster["port"]
    status, out = raw_request(port, "POST", "/api/v1/auth/login",
                              {"username": "admin", "password": ""})
    assert status == 200 and out["token"]
    status, me = raw_request(port, "GET", "/api/v1/auth/me",
                             headers={"Authorization": f"Bearer {out['token']}"})
    assert status == 200 and me["user"]["username"] == "admin"
    status, _ = raw_request(port, "POST", "/api/v1/auth/login",
                            {"username": "admin", "password": "wrong"})
    assert status == 401


def test_password_change_uses_kdf(cluster):
    session = cluster["session"]
    port = cluster["port"]
    user = session.request("POST", "/api/v1/users",
                           {"username": "kdfuser", "password": "first"})["user"]
    status, out = raw_request(port, "POST", "/api/v1/auth/login",
                              {"username": "kdfuser", "password": "first"})
    assert status == 200
    session.request("POST", f"/api/v1/users/{user['id']}/password",
                    {"password": "second"})
    status, _ = raw_request(port, "POST", "/api/v1/auth/login",
                            {"username": "kdfuser", "password": "first"})
    assert status == 401
    status, _ = raw_request(port, "POST", "/api/v1/auth/login",
                            {"username": "kdfuser", "password": "second"})
    assert status == 200
    # the persisted hash is the KDF format, not a bare FNV hex
    data_dir = cluster["tmp"] / "master-data"
    snap = wait_for(
        lambda: (lambda s: s if s and any(
            u["username"] == "kdfuser" for u in s.get("users", []))
            else None)(read_master_snapshot(data_dir)),
        desc="snapshot with kdfuser")
    stored = [u for u in snap["users"] if u["username"] == "kdfuser"][0]
    assert stored["password_hash"].startswith("pbkdf2_sha256$")


def test_api_responses_never_leak_alloc_token(cluster):
    session = cluster["session"]
    task = session.create_task("shell", name="leakcheck")
    assert "token" not in task
    listed = [t for t in session.list_tasks() if t["id"] == task["id"]][0]
    assert "token" not in listed
    session.kill_task(task["id"])


def test_proxy_requires_auth_and_task_requires_token(cluster):
    session = cluster["session"]
    port = cluster["port"]
    task = session.create_task("shell", name="sec-sh")
    tid = task["id"]
    wait_for(
        lambda: (lambda t: t if t["state"] == "RUNNING" and
                 t["proxy_address"] else None)(session.get_task(tid)),
        desc="shell task proxied",
    )

    # 1. anonymous /proxy POST (the round-1 RCE hole) is rejected
    status, body = raw_request(port, "POST", f"/proxy/{tid}/exec",
                               {"cmd": ["id"]})
    assert status == 401, f"anonymous proxy exec allowed: {body}"

    # 2. authenticated proxy exec works
    out = session.proxy(tid, "/exec", "POST", {"cmd": ["echo", "sec-ok"]})
    assert out["code"] == 0 and out["stdout"].strip() == "sec-ok"

    # 3. direct task-server access (bypassing the proxy) without the
    #    allocation token is rejected — binding is not the boundary
    host, tport = session.get_task(tid)["proxy_address"].rsplit(":", 1)
    status, body = raw_request(int(tport), "POST", "/exec",
                               {"cmd": ["id"]}, host=host)
    assert status == 401, f"tokenless direct exec allowed: {body}"
    status, _ = raw_request(int(tport), "POST", "/exec", {"cmd": ["id"]},
                            headers={"X-Alloc-Token": "f" * 32}, host=host)
    assert status == 401

    session.kill_task(tid)


def test_alloc_token_is_readonly_scoped(cluster):
    """Task containers run untrusted code: their DCT_ALLOC_TOKEN must open
    data-plane reads (experiments GET) but no mutating route."""
    session = cluster["session"]
    port = cluster["port"]
    task = session.create_task("shell", name="scope-sh")
    data_dir = cluster["tmp"] / "master-data"
    alloc_token = wait_for(
        lambda: next((a.get("token") for a in
                      (read_master_snapshot(data_dir) or {}).get(
                          "allocations", [])
                      if a["id"] == task["id"] and a.get("token")), None),
        desc="allocation token persisted")
    headers = {"Authorization": f"Bearer {alloc_token}"}
    status, _ = raw_request(port, "GET", "/api/v1/experiments",
                            headers=headers)
    assert status == 200
    status, _ = raw_request(port, "POST", "/api/v1/tasks",
                            {"type": "shell", "name": "evil"}, headers=headers)
    assert status == 401
    status, _ = raw_request(port, "GET", "/api/v1/job-queue", headers=headers)
    assert status == 401
    session.kill_task(task["id"])


def test_exec_is_shell_mode_only(cluster):
    session = cluster["session"]
    task = session.create_task("notebook", name="sec-nb")
    tid = task["id"]
    wait_for(
        lambda: (lambda t: t if t["state"] == "RUNNING" and
                 t["proxy_address"] else None)(session.get_task(tid)),
        desc="notebook task proxied",
    )
    from determined_clone_tpu.api.client import MasterError

    with pytest.raises(MasterError) as err:
        session.proxy(tid, "/exec", "POST", {"cmd": ["id"]})
    assert err.value.status == 403
    session.kill_task(tid)


def test_trial_kill_requires_session(cluster):
    """Round-3 ADVICE (high): with --auth-required but RBAC off, anonymous
    POST /trials/:id/kill previously fell through rbac_allows() (which
    passes unconditionally when RBAC is disabled). It must 401 without a
    session and succeed with one."""
    session = cluster["session"]
    port = cluster["port"]
    exp = session.create_experiment({
        "name": "killsec", "entrypoint": "x:Y",
        "searcher": {"name": "single", "metric": "loss",
                     "max_length": {"batches": 1}},
        "hyperparameters": {},
    })
    trial_id = wait_for(
        lambda: next((t["id"] for t in
                      session.get_experiment(exp["id"]).get("trials", [])),
                     None),
        desc="trial created")
    status, _ = raw_request(port, "POST", f"/api/v1/trials/{trial_id}/kill")
    assert status == 401
    status, _ = raw_request(
        port, "POST", f"/api/v1/trials/{trial_id}/kill",
        headers={"Authorization": f"Bearer {session.token}"})
    assert status == 200
    session.kill_experiment(exp["id"])


def test_allgather_requires_alloc_token(cluster):
    """Round-3 ADVICE (medium): the allgather barrier must demand the
    allocation's data-plane token — an anonymous peer could otherwise
    inject its own address into a live gang's rendezvous payload."""
    session = cluster["session"]
    port = cluster["port"]
    task = session.create_task("shell", name="ag-sec")
    tid = task["id"]
    data_dir = cluster["tmp"] / "master-data"
    alloc_token = wait_for(
        lambda: next((a.get("token") for a in
                      (read_master_snapshot(data_dir) or {}).get(
                          "allocations", [])
                      if a["id"] == tid and a.get("token")), None),
        desc="allocation token persisted")
    wait_for(lambda: session.get_task(tid)["state"] in
             ("RUNNING", "PULLING"), desc="allocation live")
    body = {"rank": 0, "round": 0, "data": {"addr": "evil:1"}}
    status, _ = raw_request(
        port, "POST", f"/api/v1/allocations/{tid}/allgather", body)
    assert status == 401
    status, resp = raw_request(
        port, "POST", f"/api/v1/allocations/{tid}/allgather", body,
        headers={"Authorization": f"Bearer {alloc_token}"})
    assert status == 200
    session.kill_task(tid)


def test_allocation_data_plane_requires_token(cluster):
    """All /allocations/:id/* routes are data-plane: rendezvous and proxy
    posts steer gang/user traffic, log posts feed log-pattern policies (a
    kill primitive). Anonymous access must 401; the allocation's token (or
    a session) opens them."""
    session = cluster["session"]
    port = cluster["port"]
    task = session.create_task("shell", name="dp-sec")
    tid = task["id"]
    data_dir = cluster["tmp"] / "master-data"
    alloc_token = wait_for(
        lambda: next((a.get("token") for a in
                      (read_master_snapshot(data_dir) or {}).get(
                          "allocations", [])
                      if a["id"] == tid and a.get("token")), None),
        desc="allocation token persisted")
    headers = {"Authorization": f"Bearer {alloc_token}"}

    for method, path, body in [
        ("POST", f"/api/v1/allocations/{tid}/rendezvous",
         {"rank": 0, "address": "evil:1"}),
        ("POST", f"/api/v1/allocations/{tid}/proxy",
         {"address": "evil:80"}),
        ("POST", f"/api/v1/allocations/{tid}/logs",
         {"logs": ["injected"]}),
        ("GET", f"/api/v1/allocations/{tid}/logs", None),
        ("GET", f"/api/v1/allocations/{tid}/preempt", None),
    ]:
        status, _ = raw_request(port, method, path, body)
        assert status == 401, f"anonymous {method} {path} -> {status}"
        status, _ = raw_request(port, method, path, body, headers=headers)
        assert status == 200, f"token {method} {path} -> {status}"

    # out-of-range rendezvous ranks are rejected even with the token
    status, _ = raw_request(
        port, "POST", f"/api/v1/allocations/{tid}/rendezvous",
        {"rank": 5, "address": "x:1"}, headers=headers)
    assert status == 400
    session.kill_task(tid)


def test_trial_mutations_require_session_or_own_token(cluster):
    """Trial data-plane mutations (metrics/searcher ops) can steer or stop
    an HP search, so anonymous posts must 401; the trial's own allocation
    token or a session opens them."""
    session = cluster["session"]
    port = cluster["port"]
    exp = session.create_experiment({
        "name": "trialgate", "entrypoint": "x:Y",
        "searcher": {"name": "single", "metric": "loss",
                     "max_length": {"batches": 1}},
        "hyperparameters": {},
    })
    trial_id = wait_for(
        lambda: next((t["id"] for t in
                      session.get_experiment(exp["id"]).get("trials", [])),
                     None),
        desc="trial created")
    body = {"group": "training", "steps_completed": 999999,
            "metrics": {"loss": 0.0}}
    status, _ = raw_request(
        port, "POST", f"/api/v1/trials/{trial_id}/metrics", body)
    assert status == 401
    status, _ = raw_request(
        port, "GET", f"/api/v1/trials/{trial_id}")
    assert status == 401
    status, _ = raw_request(
        port, "POST", f"/api/v1/trials/{trial_id}/metrics", body,
        headers={"Authorization": f"Bearer {session.token}"})
    assert status == 200
    session.kill_experiment(exp["id"])


def test_log_follow_route_requires_auth(cluster):
    """The follow long-poll is dispatched outside route()'s gate and
    carries its own copy — anonymous followers must 401, token 200."""
    session = cluster["session"]
    port = cluster["port"]
    task = session.create_task("shell", name="follow-sec")
    tid = task["id"]
    data_dir = cluster["tmp"] / "master-data"
    alloc_token = wait_for(
        lambda: next((a.get("token") for a in
                      (read_master_snapshot(data_dir) or {}).get(
                          "allocations", [])
                      if a["id"] == tid and a.get("token")), None),
        desc="allocation token persisted")
    status, _ = raw_request(
        port, "GET", f"/api/v1/allocations/{tid}/logs?follow=0")
    assert status == 401
    status, out = raw_request(
        port, "GET", f"/api/v1/allocations/{tid}/logs?follow=0",
        headers={"Authorization": f"Bearer {alloc_token}"})
    assert status == 200 and "next_offset" in out
    session.kill_task(tid)
