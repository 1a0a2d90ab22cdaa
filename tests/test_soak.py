"""Sustained soak gate (VERDICT r4 #4): the BASELINE.md k6-equivalent.

≈ the reference's nightly k6 run (performance/src/api_performance_tests.ts:
336-374 — 25 ramping VUs, 20 min, ~40 endpoint groups, p95 < 1 s). Scaled
to CI wall-clock: DCT_SOAK_SECONDS of sustained load from 25 VUs across
every GET endpoint group, WHILE 12 log followers long-poll a live stream
being appended to and a WebSocket relay shuttles frames through the
reverse proxy. The same p95 < 1 s / <5% failure gates apply throughout —
in every window of a quarter of the run (30 s at most), not just at the
end.

Which length runs where: 20 s by default, which is what tier-1 runs (the
gates are the same and every path is driven within the first seconds; a
longer sleep beside five other test workers measures the machine);
``DCT_SOAK_SECONDS=120`` by hand for the comparison with the reference's
k6 run above, four windows of 30 s.
"""
import base64
import hashlib
import json
import os
import socket
import statistics
import struct
import subprocess
import threading
import time
import urllib.request
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
MASTER_DIR = REPO / "determined_clone_tpu" / "master"
MASTER_BIN = MASTER_DIR / "build" / "dct-master"

SOAK_SECONDS = float(os.environ.get("DCT_SOAK_SECONDS", "20"))
WINDOW_SECONDS = min(30.0, SOAK_SECONDS / 4)
VUS = 25
FOLLOWERS = 12
P95_BUDGET_S = 1.0
WS_GUID = "258EAFA5-E914-47DA-95CA-C5AB0DC85B11"


@pytest.fixture(scope="module")
def master(tmp_path_factory):
    if not MASTER_BIN.exists():
        r = subprocess.run(["make", "-C", str(MASTER_DIR)],
                           capture_output=True)
        if r.returncode != 0:
            pytest.skip("C++ master build unavailable")
    tmp = tmp_path_factory.mktemp("soak")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    proc = subprocess.Popen(
        [str(MASTER_BIN), "--port", str(port), "--data-dir",
         str(tmp / "data"), "--db", "sqlite"],
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
    yield {"port": port, "tmp": tmp}
    proc.kill()
    proc.wait(timeout=10)


def _req(port, method, path, body=None, timeout=30):
    r = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode() if body is not None else None,
        method=method, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(r, timeout=timeout) as resp:
        return json.loads(resp.read() or "{}")


def _seed(port):
    """History across every entity family the GET groups page over."""
    ws = _req(port, "POST", "/api/v1/workspaces",
              {"name": "soak-ws"})["workspace"]
    _req(port, "POST", f"/api/v1/workspaces/{ws['id']}/projects",
         {"name": "soak-proj"})
    _req(port, "POST", "/api/v1/models",
         {"name": "soak-model", "description": "soak"})
    _req(port, "POST", "/api/v1/webhooks",
         {"url": "http://127.0.0.1:9/hook", "triggers": []})
    _req(port, "POST", "/api/v1/templates",
         {"name": "soak-tpl", "config": {"resources": {"slots_per_trial": 1}}})
    exp = _req(port, "POST", "/api/v1/experiments", {"config": {
        "name": "soak", "entrypoint": "m:T",
        "searcher": {"name": "custom", "metric": "loss"},
        "hyperparameters": {"lr": 0.1}}})["experiment"]
    _req(port, "POST",
         f"/api/v1/experiments/{exp['id']}/searcher/operations",
         {"ops": [{"type": "create", "request_id": 0,
                   "hparams": {"lr": 0.1}},
                  {"type": "create", "request_id": 1,
                   "hparams": {"lr": 0.2}},
                  {"type": "validate_after", "request_id": 0,
                   "units": 10_000},
                  {"type": "validate_after", "request_id": 1,
                   "units": 10_000}]})
    trials = _req(port, "GET", f"/api/v1/experiments/{exp['id']}")["trials"]
    for t in trials:
        for step in range(0, 1500, 50):
            _req(port, "POST", f"/api/v1/trials/{t['id']}/metrics",
                 {"group": "training", "steps_completed": step,
                  "metrics": {"loss": 1.0 / (step + 1),
                              "acc": step / 1500.0}})
    alloc = f"trial-{trials[0]['id']}.0"
    for i in range(0, 1000, 100):
        _req(port, "POST", f"/api/v1/allocations/{alloc}/logs",
             {"logs": [f"seed-{i + j}" for j in range(100)]})
    return exp["id"], [t["id"] for t in trials], alloc


class WsEchoServer:
    """Accepts upgrades and echoes text frames (one connection at a time)."""

    def __init__(self):
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(4)
        self.port = self.sock.getsockname()[1]
        self.running = True
        self.thread = threading.Thread(target=self._serve, daemon=True)
        self.thread.start()

    def _serve(self):
        while self.running:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            try:
                head = b""
                while b"\r\n\r\n" not in head:
                    chunk = conn.recv(4096)
                    if not chunk:
                        raise ConnectionError
                    head += chunk
                key = next(
                    line.split(b":", 1)[1].strip()
                    for line in head.split(b"\r\n")
                    if line.lower().startswith(b"sec-websocket-key"))
                accept = base64.b64encode(hashlib.sha1(
                    key + WS_GUID.encode()).digest()).decode()
                conn.sendall(
                    ("HTTP/1.1 101 Switching Protocols\r\n"
                     "Upgrade: websocket\r\nConnection: Upgrade\r\n"
                     f"Sec-WebSocket-Accept: {accept}\r\n\r\n").encode())
                while self.running:
                    payload = _ws_decode(conn)
                    conn.sendall(_ws_encode(b"echo:" + payload))
            except Exception:
                pass
            finally:
                conn.close()

    def close(self):
        self.running = False
        self.sock.close()


def _ws_encode(payload, mask=False):
    head = bytes([0x81])
    n = len(payload)
    mbit = 0x80 if mask else 0
    if n < 126:
        head += bytes([mbit | n])
    else:
        head += bytes([mbit | 126]) + struct.pack(">H", n)
    if mask:
        key = os.urandom(4)
        return head + key + bytes(b ^ key[i % 4]
                                  for i, b in enumerate(payload))
    return head + payload


def _recv_exact(sock, n):
    data = b""
    while len(data) < n:
        chunk = sock.recv(n - len(data))
        if not chunk:
            raise ConnectionError("peer closed mid-frame")
        data += chunk
    return data


def _ws_decode(sock):
    b0, b1 = _recv_exact(sock, 2)
    masked = b1 & 0x80
    n = b1 & 0x7F
    if n == 126:
        n = struct.unpack(">H", _recv_exact(sock, 2))[0]
    elif n == 127:
        n = struct.unpack(">Q", _recv_exact(sock, 8))[0]
    key = _recv_exact(sock, 4) if masked else None
    payload = _recv_exact(sock, n)
    if key:
        payload = bytes(b ^ key[i % 4] for i, b in enumerate(payload))
    return payload


def test_sustained_soak_p95_with_followers_and_ws(master):
    port = master["port"]
    exp_id, trial_ids, alloc = _seed(port)

    paths = [
        "/api/v1/experiments",
        f"/api/v1/experiments/{exp_id}",
        f"/api/v1/experiments/{exp_id}/checkpoints",
        f"/api/v1/trials/{trial_ids[0]}",
        f"/api/v1/trials/{trial_ids[0]}/metrics?limit=500",
        f"/api/v1/trials/{trial_ids[-1]}/metrics?limit=100&offset=20",
        f"/api/v1/trials/{trial_ids[0]}/metrics/summary",
        f"/api/v1/allocations/{alloc}/logs?limit=300",
        f"/api/v1/allocations/{alloc}/logs?limit=50&offset=900",
        "/api/v1/agents",
        "/api/v1/job-queue",
        "/api/v1/master",
        "/api/v1/master/config",
        "/api/v1/workspaces",
        "/api/v1/models",
        "/api/v1/webhooks",
        "/api/v1/templates",
        "/api/v1/users",
        "/metrics",
    ]

    stop = threading.Event()
    lock = threading.Lock()
    window_latencies = []   # (t_end, latency) for per-window p95
    errors = []
    follower_rounds = [0]
    ws_rounds = [0]

    def vu(idx):
        i = 0
        while not stop.is_set():
            path = paths[(idx + i) % len(paths)]
            i += 1
            t0 = time.perf_counter()
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}{path}", timeout=30) as r:
                    r.read()
                with lock:
                    window_latencies.append(
                        (time.monotonic(), time.perf_counter() - t0))
            except Exception as exc:  # noqa: BLE001
                with lock:
                    errors.append(f"{path}: {exc!r}")

    def follower(idx):
        offset = 0
        while not stop.is_set():
            try:
                out = _req(port, "GET",
                           f"/api/v1/allocations/{alloc}/logs"
                           f"?follow=3&offset={offset}&limit=200",
                           timeout=30)
                offset = out.get("next_offset", offset)
                with lock:
                    follower_rounds[0] += 1
            except Exception as exc:  # noqa: BLE001
                with lock:
                    errors.append(f"follower: {exc!r}")

    def log_writer():
        i = 0
        while not stop.is_set():
            try:
                _req(port, "POST", f"/api/v1/allocations/{alloc}/logs",
                     {"logs": [f"live-{i}-{j}" for j in range(10)]})
            except Exception as exc:  # noqa: BLE001
                with lock:
                    errors.append(f"writer: {exc!r}")
            i += 1
            time.sleep(0.5)

    def ws_relay(echo_port):
        _req(port, "POST", f"/api/v1/allocations/{alloc}/proxy",
             {"address": f"127.0.0.1:{echo_port}"})
        while not stop.is_set():
            try:
                s = socket.create_connection(("127.0.0.1", port), timeout=10)
                s.sendall(
                    (f"GET /proxy/{alloc}/kernels/ws HTTP/1.1\r\n"
                     f"Host: 127.0.0.1\r\nUpgrade: websocket\r\n"
                     f"Connection: Upgrade\r\n"
                     f"Sec-WebSocket-Key: c29ha3Nlc3Npb24hIQ==\r\n"
                     f"Sec-WebSocket-Version: 13\r\n\r\n").encode())
                head = b""
                while b"\r\n\r\n" not in head:
                    chunk = s.recv(4096)
                    if not chunk:
                        raise ConnectionError("no 101")
                    head += chunk
                assert b"101" in head.split(b"\r\n", 1)[0]
                for k in range(20):
                    if stop.is_set():
                        break
                    s.sendall(_ws_encode(f"frame-{k}".encode(), mask=True))
                    echoed = _ws_decode(s)
                    assert echoed == f"echo:frame-{k}".encode()
                    with lock:
                        ws_rounds[0] += 1
                    time.sleep(0.25)
                s.close()
            except Exception as exc:  # noqa: BLE001
                if not stop.is_set():
                    with lock:
                        errors.append(f"ws: {exc!r}")
                    time.sleep(1)

    echo = WsEchoServer()
    threads = (
        [threading.Thread(target=vu, args=(i,)) for i in range(VUS)]
        + [threading.Thread(target=follower, args=(i,))
           for i in range(FOLLOWERS)]
        + [threading.Thread(target=log_writer),
           threading.Thread(target=ws_relay, args=(echo.port,))]
    )
    t_start = time.monotonic()
    for t in threads:
        t.start()
    time.sleep(SOAK_SECONDS)
    stop.set()
    for t in threads:
        t.join(timeout=40)
    echo.close()

    with lock:
        all_lat = sorted(lat for _, lat in window_latencies)
        errs = list(errors)

    assert all_lat, "no requests completed"
    fail_rate = len(errs) / (len(all_lat) + len(errs))
    p50 = all_lat[len(all_lat) // 2]
    p95 = all_lat[int(len(all_lat) * 0.95)]

    # per-window p95: the gate must hold THROUGHOUT, not just on average
    windows = {}
    for t_end, lat in window_latencies:
        windows.setdefault(int((t_end - t_start) // WINDOW_SECONDS),
                           []).append(lat)
    window_p95 = {}
    for w, lats in sorted(windows.items()):
        lats.sort()
        if len(lats) >= 20:  # skip ramp slivers
            window_p95[w] = lats[int(len(lats) * 0.95)]

    print(f"\n[soak] {SOAK_SECONDS:.0f}s, {VUS} VUs + {FOLLOWERS} followers"
          f" + WS relay: {len(all_lat)} reqs, p50={p50 * 1000:.1f}ms "
          f"p95={p95 * 1000:.1f}ms, follower_rounds={follower_rounds[0]}, "
          f"ws_frames={ws_rounds[0]}, errors={len(errs)}")
    print(f"[soak] per-{WINDOW_SECONDS:.0f}s-window p95: "
          f"{[f'{v * 1000:.0f}ms' for _, v in sorted(window_p95.items())]}")

    assert fail_rate < 0.05, (fail_rate, errs[:5])
    assert p95 < P95_BUDGET_S, f"p95 {p95:.3f}s over {P95_BUDGET_S}s"
    for w, v in window_p95.items():
        assert v < P95_BUDGET_S, f"window {w}: p95 {v:.3f}s over budget"
    # the followers actually tailed (long-poll path exercised, not idle)
    assert follower_rounds[0] >= FOLLOWERS * 2
    # the WS relay stayed live through the soak
    assert ws_rounds[0] >= 20
