"""WebSocket upgrade through the master's reverse proxy (VERDICT r3 #4).

The reference proxies WebSocket and raw TCP between the browser and task
containers (/root/reference/master/internal/proxy/ws.go, tcp.go). Here the
master detects Connection: Upgrade on /proxy/<alloc>/..., replays the
request head to the task server, and splices the two sockets with a
dedicated relay thread — so real jupyter kernel channels (and live
shells) work through the authenticated proxy instead of request/response
buffering.

The test implements just enough RFC6455 by hand (no websocket deps in the
image): the echo server computes Sec-WebSocket-Accept and echoes text
frames; the client masks its frames as the RFC requires.
"""
import base64
import hashlib
import json
import os
import socket
import struct
import subprocess
import threading
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
MASTER_DIR = REPO / "determined_clone_tpu" / "master"
MASTER_BIN = MASTER_DIR / "build" / "dct-master"
AGENT_BIN = MASTER_DIR / "build" / "dct-agent"

WS_GUID = "258EAFA5-E914-47DA-95CA-C5AB0DC85B11"


def build_binaries():
    if MASTER_BIN.exists() and AGENT_BIN.exists():
        return True
    r = subprocess.run(["make", "-C", str(MASTER_DIR)], capture_output=True)
    return r.returncode == 0


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    if not build_binaries():
        pytest.skip("C++ master/agent build unavailable")
    tmp = tmp_path_factory.mktemp("wsproxy")
    workdir = tmp / "agent-work"
    workdir.mkdir()

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
        [str(AGENT_BIN), "--master-port", str(port), "--id", "ws-agent",
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

    yield {"session": session, "port": port}

    agent.kill()
    master.kill()
    agent.wait(timeout=10)
    master.wait(timeout=10)


# -- minimal RFC6455 framing -------------------------------------------------

def ws_encode(payload: bytes, mask: bool) -> bytes:
    head = bytes([0x81])  # FIN + text
    n = len(payload)
    mbit = 0x80 if mask else 0
    if n < 126:
        head += bytes([mbit | n])
    elif n < 65536:
        head += bytes([mbit | 126]) + struct.pack(">H", n)
    else:
        head += bytes([mbit | 127]) + struct.pack(">Q", n)
    if mask:
        key = os.urandom(4)
        return head + key + bytes(b ^ key[i % 4]
                                  for i, b in enumerate(payload))
    return head + payload


def recv_exact(sock, n: int) -> bytes:
    data = b""
    while len(data) < n:
        chunk = sock.recv(n - len(data))
        if not chunk:
            raise ConnectionError("peer closed mid-frame")
        data += chunk
    return data


def ws_decode(sock) -> bytes:
    b0, b1 = recv_exact(sock, 2)
    masked = b1 & 0x80
    n = b1 & 0x7F
    if n == 126:
        n = struct.unpack(">H", recv_exact(sock, 2))[0]
    elif n == 127:
        n = struct.unpack(">Q", recv_exact(sock, 8))[0]
    key = recv_exact(sock, 4) if masked else None
    payload = recv_exact(sock, n)
    if key:
        payload = bytes(b ^ key[i % 4] for i, b in enumerate(payload))
    return payload


class WsEchoServer:
    """Accepts one upgrade, records the request head, echoes text frames."""

    def __init__(self):
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(4)
        self.port = self.sock.getsockname()[1]
        self.request_head = b""
        self.thread = threading.Thread(target=self._serve, daemon=True)
        self.thread.start()

    def _serve(self):
        conn, _ = self.sock.accept()
        try:
            head = b""
            while b"\r\n\r\n" not in head:
                chunk = conn.recv(4096)
                if not chunk:
                    return
                head += chunk
            self.request_head = head
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
            while True:
                payload = ws_decode(conn)
                conn.sendall(ws_encode(b"echo:" + payload, mask=False))
        except (ConnectionError, StopIteration, OSError):
            pass
        finally:
            conn.close()

    def close(self):
        self.sock.close()


def test_websocket_roundtrip_through_proxy(cluster):
    session = cluster["session"]
    port = cluster["port"]
    task = session.create_task("shell", name="ws-sh")
    tid = task["id"]
    deadline = time.time() + 30
    while time.time() < deadline:
        if session.get_task(tid)["state"] in ("RUNNING", "PULLING"):
            break
        time.sleep(0.2)

    echo = WsEchoServer()
    # point the allocation's proxy at the echo server (what a real task
    # server does on startup)
    session.post(f"/api/v1/allocations/{tid}/proxy",
                 {"address": f"127.0.0.1:{echo.port}"})

    client = socket.create_connection(("127.0.0.1", port), timeout=15)
    try:
        key = base64.b64encode(os.urandom(16)).decode()
        client.sendall(
            (f"GET /proxy/{tid}/kernels/ws HTTP/1.1\r\n"
             f"Host: 127.0.0.1:{port}\r\n"
             "Connection: Upgrade\r\nUpgrade: websocket\r\n"
             "Sec-WebSocket-Version: 13\r\n"
             f"Sec-WebSocket-Key: {key}\r\n\r\n").encode())
        # 101 comes from the task server THROUGH the relay
        head = b""
        while b"\r\n\r\n" not in head:
            chunk = client.recv(4096)
            assert chunk, "proxy closed before the 101"
            head += chunk
        status_line = head.split(b"\r\n", 1)[0]
        assert b"101" in status_line, head
        expect = base64.b64encode(hashlib.sha1(
            (key + WS_GUID).encode()).digest())
        assert expect in head  # handshake passed through unaltered

        # full frame round trips, both directions, multiple times
        for i in range(3):
            msg = f"ping-{i}".encode()
            client.sendall(ws_encode(msg, mask=True))
            assert ws_decode(client) == b"echo:" + msg

        # the upstream saw the alloc token injected by the master, and
        # never the Authorization header
        assert b"x-alloc-token:" in echo.request_head.lower()
        assert b"authorization" not in echo.request_head.lower()
    finally:
        client.close()
        echo.close()
    session.kill_task(tid)


def test_plain_http_proxy_still_buffers(cluster):
    """Non-upgrade requests keep the request/response relay path."""
    session = cluster["session"]
    task = session.create_task("shell", name="ws-plain")
    tid = task["id"]
    deadline = time.time() + 30
    while time.time() < deadline:
        t = session.get_task(tid)
        if t["state"] == "RUNNING" and t.get("proxy_address"):
            break
        time.sleep(0.2)
    out = session.proxy(tid, "/", "GET")
    assert out  # the task server's landing payload came through
    session.kill_task(tid)
