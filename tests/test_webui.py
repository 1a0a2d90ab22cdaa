"""WebUI: the master serves the static bundle and the app's API surface.

≈ the reference's webui smoke coverage: assets load from the master, content
types are right, path traversal is blocked, and the pages' API calls return
the shapes the views render.
"""
import json
import subprocess
import time
import urllib.error
import urllib.request
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
MASTER_DIR = REPO / "determined_clone_tpu" / "master"
MASTER_BIN = MASTER_DIR / "build" / "dct-master"
WEBUI_DIR = REPO / "webui"


@pytest.fixture(scope="module")
def master(tmp_path_factory):
    if not MASTER_BIN.exists():
        r = subprocess.run(["make", "-C", str(MASTER_DIR)],
                           capture_output=True)
        if r.returncode != 0:
            pytest.skip("C++ master build unavailable")
    tmp = tmp_path_factory.mktemp("webui")

    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    proc = subprocess.Popen(
        [str(MASTER_BIN), "--port", str(port), "--data-dir", str(tmp / "data"),
         "--webui-dir", str(WEBUI_DIR)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    deadline = time.time() + 20
    while time.time() < deadline:
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/api/v1/master", timeout=2)
            break
        except Exception:
            time.sleep(0.2)
    else:
        proc.kill()
        pytest.fail("master did not come up")
    yield port
    proc.kill()
    proc.wait(timeout=10)


def fetch(port, path):
    resp = urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=5)
    return resp.status, resp.headers.get("Content-Type", ""), resp.read()


def test_index_served_at_root(master):
    status, ctype, body = fetch(master, "/")
    assert status == 200 and ctype.startswith("text/html")
    assert b"DCT" in body and b"/ui/app.js" in body


def test_assets_with_content_types(master):
    status, ctype, body = fetch(master, "/ui/app.js")
    assert status == 200 and ctype == "text/javascript"
    assert b"lineChart" in body
    status, ctype, body = fetch(master, "/ui/style.css")
    assert status == 200 and ctype == "text/css"
    assert b"--series-1" in body
    status, ctype, body = fetch(master, "/ui/index.html")
    assert status == 200 and ctype.startswith("text/html")


def test_traversal_blocked(master):
    # encoded and raw traversal must 404, never escape webui/; the target
    # is a real file beside webui/, so a traversal that worked would serve it
    assert b"import" in (WEBUI_DIR / ".." / "chip_smoke.py").read_bytes()
    for path in ("/ui/..%2Fchip_smoke.py", "/ui/%2e%2e/secrets",
                 "/ui/%2e%2e/chip_smoke.py"):
        try:
            status, _, body = fetch(master, path)
        except urllib.error.HTTPError as e:
            status, body = e.code, e.read()
        assert status == 404, (path, body[:100])
        assert b"import" not in body


def test_unknown_asset_404(master):
    with pytest.raises(urllib.error.HTTPError) as err:
        fetch(master, "/ui/nope.js")
    assert err.value.code == 404


def test_directory_is_not_an_asset(master):
    # "." resolves to the webui dir itself: must 404, not 200-empty
    with pytest.raises(urllib.error.HTTPError) as err:
        fetch(master, "/ui/%2e")
    assert err.value.code == 404


def test_view_api_shapes(master):
    """Each view's fetches return the keys the JS renders."""
    _, _, body = fetch(master, "/api/v1/master")
    info = json.loads(body)
    assert {"version", "cluster_name", "agents"} <= set(info)
    _, _, body = fetch(master, "/api/v1/experiments")
    assert "experiments" in json.loads(body)
    _, _, body = fetch(master, "/api/v1/agents")
    assert "agents" in json.loads(body)
    _, _, body = fetch(master, "/api/v1/job-queue")
    assert "queue" in json.loads(body)
    _, _, body = fetch(master, "/api/v1/tasks")
    assert "tasks" in json.loads(body)
    # admin view fetches
    _, _, body = fetch(master, "/api/v1/users")
    assert "users" in json.loads(body)
    _, _, body = fetch(master, "/api/v1/groups")
    assert "groups" in json.loads(body)
    _, _, body = fetch(master, "/api/v1/rbac/roles")
    assert "roles" in json.loads(body)
    _, _, body = fetch(master, "/api/v1/rbac/assignments")
    assert "assignments" in json.loads(body)


def test_admin_nav_and_view_shipped(master):
    _, _, body = fetch(master, "/ui/index.html")
    assert 'data-nav="admin"' in body.decode()
    _, _, body = fetch(master, "/ui/app.js")
    js = body.decode()
    # admin actions ride the generated client (webui/bindings.js)
    assert "viewAdmin" in js and "assignRole" in js and "unassignRole" in js
    assert "moveJob" in js and "setJobPriority" in js  # queue actions wired


def test_trial_logs_view_shipped(master):
    _, _, body = fetch(master, "/ui/app.js")
    js = body.decode()
    assert "viewTrialLogs" in js
    # the view derives the live leg's allocation id from trial.legs
    assert "trial.legs" in js and "getTaskLogs" in js


def post(port, path, body=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body or {}).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=5) as resp:
        return json.loads(resp.read() or b"{}")


def test_parity_pages_shipped_and_drive_real_api(master):
    """Round-4 parity pages (VERDICT #6): queue, model registry,
    workspaces/projects, trial detail with metrics + profiler charts —
    each present in the bundle and backed by a live API flow."""
    _, _, body = fetch(master, "/ui/app.js")
    js = body.decode()
    for marker in ["viewQueue", "viewModels", "viewModelDetail",
                   "viewWorkspaces", "viewWorkspaceDetail",
                   "viewTrialDetail", "listResourcePools",
                   "getTrialProfiler", "registerModelVersion"]:
        assert marker in js, f"app.js missing {marker}"
    _, _, body = fetch(master, "/ui/index.html")
    index = body.decode()
    for nav in ["queue", "models", "workspaces"]:
        assert f'data-nav="{nav}"' in index

    # the queue page's fetches
    _, _, body = fetch(master, "/api/v1/resource-pools")
    pools = json.loads(body)["resource_pools"]
    assert any(p["is_default"] for p in pools)

    # model registry flow exactly as the page drives it
    post(master, "/api/v1/models", {"name": "ui-model",
                                    "description": "from the ui test"})
    _, _, body = fetch(master, "/api/v1/models/ui-model")
    assert json.loads(body)["model"]["description"] == "from the ui test"

    # workspace detail flow
    ws = post(master, "/api/v1/workspaces", {"name": "ui-ws"})["workspace"]
    post(master, f"/api/v1/workspaces/{ws['id']}/projects",
         {"name": "ui-proj"})
    _, _, body = fetch(master, f"/api/v1/workspaces/{ws['id']}")
    detail = json.loads(body)
    assert [p["name"] for p in detail["projects"]][-1] == "ui-proj"
    assert "experiments" in detail

    # trial detail flow: experiment -> trial -> metrics/profiler/checkpoints
    exp = post(master, "/api/v1/experiments", {"config": {
        "name": "ui-exp", "entrypoint": "x:Y",
        "searcher": {"name": "single", "metric": "loss",
                     "max_length": {"batches": 1}},
        "hyperparameters": {},
    }})["experiment"]
    deadline = time.time() + 30
    trial_id = None
    while time.time() < deadline and trial_id is None:
        _, _, body = fetch(master, f"/api/v1/experiments/{exp['id']}")
        trials = json.loads(body).get("trials") or []
        trial_id = trials[0]["id"] if trials else None
        time.sleep(0.2)
    post(master, f"/api/v1/trials/{trial_id}/metrics",
         {"group": "training", "steps_completed": 1,
          "metrics": {"loss": 1.5}})
    post(master, f"/api/v1/trials/{trial_id}/profiler",
         {"samples": [{"cpu_pct": 12.5, "mem_mb": 100}]})
    _, _, body = fetch(master, f"/api/v1/trials/{trial_id}")
    assert json.loads(body)["trial"]["id"] == trial_id
    _, _, body = fetch(master, f"/api/v1/trials/{trial_id}/metrics?limit=10")
    assert json.loads(body)["metrics"][-1]["metrics"]["loss"] == 1.5
    _, _, body = fetch(master, f"/api/v1/trials/{trial_id}/profiler?limit=10")
    assert json.loads(body)["samples"][-1]["cpu_pct"] == 12.5
    _, _, body = fetch(master, f"/api/v1/trials/{trial_id}/checkpoints")
    assert "checkpoints" in json.loads(body)
    post(master, f"/api/v1/experiments/{exp['id']}/kill")
