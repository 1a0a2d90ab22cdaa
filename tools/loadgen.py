#!/usr/bin/env python3
"""Synthetic control-plane load harness for the C++ master.

Drives a REAL ``dct-master`` binary (spawned here, or an existing one via
``--master``) with simulated agents and thousands of no-op trials, then
reads the scheduler's own telemetry back out of
``GET /api/v1/cluster/scheduler`` to produce the ``control_plane``
section of BENCH (docs/observability.md):

- **submits/sec admitted** — trials minted through the custom-searcher
  operations route over the submission wall time;
- **decisions/sec** — scheduler decision passes over the run;
- **p50/p99 submit→running** — the master's own lifecycle-timestamp
  latency reservoir (``dct_master_sched_submit_to_running_seconds``);
- **peak queue depth** — max of the queue-depth gauge polled over the run.

The simulated agent protocol is the real one: ``POST
/api/v1/agents/register``, heartbeats that receive derived ``start``
commands, ``task_event running`` → ``searcher/completed_op`` →
``task_event exited``. Completing the searcher op before the clean exit
parks each trial instead of requeueing it, so slots recycle and the
queue drains at scheduler speed, not harness speed.

Usage:
    python tools/loadgen.py --trials 1000 --agents 8 --slots 8
    python tools/loadgen.py --trials 10000 --budget 300   # the 10k run

Importable: ``run_load(trials=1000, ...) -> dict``.
Never raises on an unavailable master build — returns ``{"error": ...}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import random
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MASTER_DIR = os.path.join(REPO, "determined_clone_tpu", "master")
MASTER_BIN = os.path.join(MASTER_DIR, "build", "dct-master")

if REPO not in sys.path:
    sys.path.insert(0, REPO)

from determined_clone_tpu.utils.retry import (  # noqa: E402
    RetryPolicy, retry_call, sleep_backoff)

OPS_PER_BATCH = 200  # creates per searcher/operations POST

# boot wait: steady sampling, no jitter (the deploy_wait pattern in
# docs/fault_tolerance.md); ValueError covers a half-up server returning
# a torn JSON body
_MASTER_UP = RetryPolicy(
    name="loadgen_master_up", max_attempts=1_000_000, base_delay_s=0.2,
    multiplier=1.0, max_delay_s=0.2, jitter="none",
    retryable=(OSError, ValueError))
_HEARTBEAT = RetryPolicy(name="loadgen_heartbeat", base_delay_s=0.1,
                         max_delay_s=2.0, retryable=(OSError, ValueError))


def _req(port: int, method: str, path: str, body=None, timeout: float = 30):
    data = json.dumps(body).encode() if body is not None else None
    r = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data, method=method,
        headers={"Content-Type": "application/json"} if data else {})
    with urllib.request.urlopen(r, timeout=timeout) as resp:
        payload = resp.read()
    return json.loads(payload) if payload else {}


def ensure_master_binary() -> str | None:
    if os.path.exists(MASTER_BIN):
        return MASTER_BIN
    r = subprocess.run(["make", "-C", MASTER_DIR], capture_output=True)
    return MASTER_BIN if r.returncode == 0 and os.path.exists(MASTER_BIN) \
        else None


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_up(port: int, deadline_s: float = 15.0) -> bool:
    policy = dataclasses.replace(_MASTER_UP, deadline_s=deadline_s)
    try:
        retry_call(_req, port, "GET", "/api/v1/master", timeout=3,
                   policy=policy)
        return True
    except (OSError, ValueError):
        return False


def _sched(port: int) -> dict:
    return _req(port, "GET", "/api/v1/cluster/scheduler")


class _AgentSim(threading.Thread):
    """One fake agent: heartbeats, runs every ``start`` it receives as a
    no-op (running → completed_op → clean exit), all inside one beat."""

    def __init__(self, port: int, agent_id: str, stop: threading.Event):
        super().__init__(daemon=True, name=f"loadgen-{agent_id}")
        self.port = port
        self.agent_id = agent_id
        self.stop_ev = stop
        self.ran = 0
        self.errors = 0

    def run(self) -> None:
        hb_failures = 0
        while not self.stop_ev.is_set():
            try:
                resp = _req(self.port, "POST",
                            f"/api/v1/agents/{self.agent_id}/heartbeat",
                            {"exited": [], "running": []})
                hb_failures = 0
            except (OSError, ValueError):
                self.errors += 1
                hb_failures += 1
                sleep_backoff(_HEARTBEAT, hb_failures)
                continue
            cmds = [c for c in resp.get("commands", [])
                    if c.get("type") == "start"]
            for cmd in cmds:
                try:
                    self._run_task(cmd)
                    self.ran += 1
                except (OSError, ValueError):
                    self.errors += 1
            # beat fast while work flows, back off when idle — poll pacing
            # (the Event doubles as the stop signal)
            self.stop_ev.wait(0.02 if cmds else 0.1)

    def _run_task(self, cmd: dict) -> None:
        alloc_id = cmd["allocation_id"]
        trial = cmd.get("trial") or {}
        _req(self.port, "POST",
             f"/api/v1/agents/{self.agent_id}/task_event",
             {"allocation_id": alloc_id, "event": "running"})
        tid = trial.get("id")
        if tid:
            # satisfy the searcher op BEFORE exiting: units_done reaches
            # target, so the clean exit completes the trial leg instead of
            # requeueing it — the slot frees for the next queued trial
            _req(self.port, "POST",
                 f"/api/v1/trials/{tid}/searcher/completed_op",
                 {"metric": 0.0, "units": trial.get("target_units", 1)})
        _req(self.port, "POST",
             f"/api/v1/agents/{self.agent_id}/task_event",
             {"allocation_id": alloc_id, "event": "exited", "exit_code": 0})


def _counters(summary: dict) -> dict:
    return summary.get("counters") or {}


def run_load(trials: int = 1000, agents: int = 8, slots_per_agent: int = 8,
             budget_s: float = 180.0, master_port: int | None = None,
             keep_master: bool = False) -> dict:
    """Run the synthetic load and return the control-plane measurement.

    Spawns its own master (``--db sqlite``) unless ``master_port`` points
    at a live one. Always returns a dict; ``error`` is set (and the
    latency fields None) when the master can't be built or reached.
    """
    t_total0 = time.monotonic()
    proc = None
    tmp = None
    port = master_port
    try:
        if port is None:
            binary = ensure_master_binary()
            if binary is None:
                return {"error": "dct-master build unavailable"}
            tmp = tempfile.mkdtemp(prefix="dct-loadgen-")
            port = _free_port()
            proc = subprocess.Popen(
                [binary, "--port", str(port), "--data-dir",
                 os.path.join(tmp, "data"), "--db", "sqlite"],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
            if not _wait_up(port):
                return {"error": "spawned master did not come up"}
        elif not _wait_up(port, 5.0):
            return {"error": f"no master on port {port}"}

        base = _sched(port)
        base_c = _counters(base)

        for i in range(agents):
            _req(port, "POST", "/api/v1/agents/register",
                 {"id": f"loadgen-agent-{i}", "slots": slots_per_agent,
                  "topology": f"fake-{slots_per_agent}",
                  "address": "127.0.0.1:0", "resource_pool": "default"})

        stop = threading.Event()
        sims = [_AgentSim(port, f"loadgen-agent-{i}", stop)
                for i in range(agents)]
        for s in sims:
            s.start()

        exp = _req(port, "POST", "/api/v1/experiments", {"config": {
            "name": "loadgen", "entrypoint": "noop:Noop",
            "searcher": {"name": "custom", "metric": "loss"},
            "resources": {"slots_per_trial": 1},
            "hyperparameters": {},
        }})
        exp_id = (exp.get("experiment") or exp)["id"]

        # -- submission phase: mint trials through the searcher ops route --
        t_sub0 = time.monotonic()
        submitted = 0
        rid = 0
        while submitted < trials:
            if time.monotonic() - t_total0 > budget_s:
                break
            n = min(OPS_PER_BATCH, trials - submitted)
            ops = []
            for _ in range(n):
                ops.append({"type": "create", "request_id": rid,
                            "hparams": {}})
                ops.append({"type": "validate_after", "request_id": rid,
                            "units": 1})
                rid += 1
            _req(port, "POST",
                 f"/api/v1/experiments/{exp_id}/searcher/operations",
                 {"ops": ops}, timeout=60)
            submitted += n
        submit_wall = max(time.monotonic() - t_sub0, 1e-9)

        # -- drain phase: poll the scheduler summary until done/budget ----
        peak_queue = 0
        done = 0
        incomplete = False
        while True:
            s = _sched(port)
            gauges = s.get("gauges") or {}
            peak_queue = max(peak_queue, int(gauges.get("queue_depth") or 0))
            done = int(_counters(s).get("completed", 0)
                       - base_c.get("completed", 0))
            if done >= submitted:
                break
            if time.monotonic() - t_total0 > budget_s:
                incomplete = True
                break
            time.sleep(0.25)
        stop.set()
        for s_ in sims:
            s_.join(timeout=5)

        final = _sched(port)
        wall = max(time.monotonic() - t_total0, 1e-9)
        fc, lat = _counters(final), final.get("latency") or {}

        def delta(name: str) -> int:
            return int(fc.get(name, 0) - base_c.get(name, 0))

        s2r = lat.get("submit_to_running_seconds") or {}
        return {
            "trials": trials,
            "submitted": delta("submitted"),
            "completed": done,
            "agents": agents,
            "slots": agents * slots_per_agent,
            "duration_s": round(wall, 3),
            "submit_wall_s": round(submit_wall, 3),
            "submits_per_sec": round(submitted / submit_wall, 2),
            "decisions": delta("decisions"),
            "decisions_per_sec": round(delta("decisions") / wall, 2),
            "considered": delta("considered"),
            "scheduled": delta("scheduled"),
            "reschedules": delta("reschedules"),
            "preemptions": delta("preemptions"),
            "peak_queue_depth": peak_queue,
            "submit_to_running_s": {
                "p50": s2r.get("p50"), "p95": s2r.get("p95"),
                "p99": s2r.get("p99"), "count": s2r.get("count"),
            },
            "queue_wait_s": {
                k: (lat.get("queue_wait_seconds") or {}).get(k)
                for k in ("p50", "p95", "p99", "count")
            },
            "decision_s": {
                k: (lat.get("decision_seconds") or {}).get(k)
                for k in ("p50", "p95", "p99", "count")
            },
            "agent_errors": sum(s_.errors for s_ in sims),
            "incomplete": incomplete,
        }
    except (OSError, ValueError, KeyError) as exc:
        return {"error": f"{type(exc).__name__}: {exc}"}
    finally:
        if proc is not None and not keep_master:
            proc.kill()
            try:
                proc.wait(timeout=10)
            except Exception:  # noqa: BLE001
                pass
        if tmp is not None and not keep_master:
            shutil.rmtree(tmp, ignore_errors=True)


def _percentiles(samples: list) -> dict:
    """p50/p95/p99 with numpy-style linear interpolation (no numpy dep —
    loadgen must run beside a master with nothing but the stdlib)."""
    if not samples:
        return {"p50": None, "p95": None, "p99": None, "count": 0}
    s = sorted(samples)

    def pct(q: float) -> float:
        pos = q / 100.0 * (len(s) - 1)
        lo = int(pos)
        hi = min(lo + 1, len(s) - 1)
        return s[lo] * (1 - (pos - lo)) + s[hi] * (pos - lo)

    return {"p50": round(pct(50), 6), "p95": round(pct(95), 6),
            "p99": round(pct(99), 6), "count": len(s)}


def run_mixed_load(trials: int = 400, agents: int = 4,
                   slots_per_agent: int = 8, serving_replicas: int = 2,
                   serving_requests: int = 120,
                   tokens_per_request: int = 8,
                   iteration_floor_s: float = 0.01,
                   budget_s: float = 240.0,
                   master_port: int | None = None,
                   shared_prefix: bool = False) -> dict:
    """Trials AND a serving fleet on one simulated cluster.

    ``shared_prefix`` switches the serving traffic to the "millions of
    users, one system prompt" shape: every request opens with the same
    system prefix (a whole KV block) followed by a varied tail, and the
    fleet's engines run with the COW prefix cache on — the serving
    numbers then report the aggregate block hit-rate next to the p99,
    which is the pair the prefix cache is supposed to move.

    The trial half is :func:`run_load`'s machinery (simulated agents in
    the ``default`` pool, trials minted through the searcher ops route).
    The serving half is REAL: a ``ServingFleet`` of tiny-GPT engines
    whose replicas are master ``serving`` gang allocations in their own
    ``serving`` pool (the standard serving/training pool split), driven
    through the least-loaded router while the trial storm is in flight.
    Both sides contend for the master's decision loop and this host's
    CPU, which is the contention the mixed numbers measure: trial
    submit→running p95 from the master's own reservoir, serving p99 from
    client-observed request latencies. Also returns the fleet rollup the
    aggregator computes from the per-replica registries (what ``dct
    metrics`` shows) and the master's serving counters (what proves the
    gang allocations went through the scheduler).
    """
    t_total0 = time.monotonic()
    proc = None
    tmp = None
    port = master_port
    fleet = None
    link = None
    try:
        # serving imports are deliberately lazy: the control_plane lane
        # must keep working on hosts without jax
        import jax

        from determined_clone_tpu.models import gpt
        from determined_clone_tpu.serving import MasterLink, ServingFleet
        from determined_clone_tpu.serving.bucketing import BucketSpec
        from determined_clone_tpu.serving.kv_cache import KVCacheConfig
        from determined_clone_tpu.telemetry.aggregate import (
            ClusterMetricsAggregator,
        )

        if port is None:
            binary = ensure_master_binary()
            if binary is None:
                return {"error": "dct-master build unavailable"}
            tmp = tempfile.mkdtemp(prefix="dct-loadgen-")
            port = _free_port()
            proc = subprocess.Popen(
                [binary, "--port", str(port), "--data-dir",
                 os.path.join(tmp, "data"), "--db", "sqlite"],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
            if not _wait_up(port):
                return {"error": "spawned master did not come up"}
        elif not _wait_up(port, 5.0):
            return {"error": f"no master on port {port}"}

        base_c = _counters(_sched(port))

        for i in range(agents):
            _req(port, "POST", "/api/v1/agents/register",
                 {"id": f"loadgen-agent-{i}", "slots": slots_per_agent,
                  "topology": f"fake-{slots_per_agent}",
                  "address": "127.0.0.1:0", "resource_pool": "default"})

        # -- the serving half: real engines, master-managed ---------------
        cfg = gpt.GPTConfig(vocab_size=97, n_layers=2, d_model=32,
                            n_heads=4, d_ff=64, max_seq_len=48,
                            remat=False, attention_impl="mha")
        params = gpt.init(jax.random.PRNGKey(0), cfg)
        aggregator = ClusterMetricsAggregator()
        fleet = ServingFleet(
            params, cfg, name="loadgen",
            buckets=BucketSpec.build(4, 16),
            cache=KVCacheConfig(num_blocks=24, block_size=8),
            max_queue_depth=max(64, serving_requests),
            iteration_floor_s=iteration_floor_s, aggregator=aggregator,
            prefix_cache=shared_prefix)
        link = MasterLink(fleet, port, replicas=serving_replicas,
                          resource_pool="serving")
        link.wait_replicas(serving_replicas, timeout=60)
        fleet.sample_telemetry()  # baseline for the tokens/sec delta

        stop = threading.Event()
        sims = [_AgentSim(port, f"loadgen-agent-{i}", stop)
                for i in range(agents)]
        for s in sims:
            s.start()

        serving_lat: list = []          # (total_s, request_id) pairs
        serving_errors = [0]

        # one KV block (block_size=8) of common system prompt; tails vary
        system_prefix = [7, 3, 5, 2, 9, 4, 6, 8]

        def drive_serving() -> None:
            handles = []
            for i in range(serving_requests):
                if stop.is_set():
                    break
                prompt = [1 + (i % 7), 2, 3]
                if shared_prefix:
                    prompt = system_prefix + prompt
                try:
                    handles.append(fleet.submit(
                        prompt, tokens_per_request, timeout=30.0))
                except Exception:  # noqa: BLE001 — counted, not fatal
                    serving_errors[0] += 1
            for h in handles:
                try:
                    res = h.result(60.0)
                    serving_lat.append((res.total_s, res.request_id))
                except Exception:  # noqa: BLE001
                    serving_errors[0] += 1

        serving_thread = threading.Thread(target=drive_serving,
                                          name="loadgen-serving",
                                          daemon=True)
        t_serving0 = time.monotonic()
        serving_thread.start()

        # -- the trial half, concurrent with the serving traffic ----------
        exp = _req(port, "POST", "/api/v1/experiments", {"config": {
            "name": "loadgen-mixed", "entrypoint": "noop:Noop",
            "searcher": {"name": "custom", "metric": "loss"},
            "resources": {"slots_per_trial": 1},
            "hyperparameters": {},
        }})
        exp_id = (exp.get("experiment") or exp)["id"]
        t_sub0 = time.monotonic()
        submitted = 0
        rid = 0
        while submitted < trials:
            if time.monotonic() - t_total0 > budget_s:
                break
            n = min(OPS_PER_BATCH, trials - submitted)
            ops = []
            for _ in range(n):
                ops.append({"type": "create", "request_id": rid,
                            "hparams": {}})
                ops.append({"type": "validate_after", "request_id": rid,
                            "units": 1})
                rid += 1
            _req(port, "POST",
                 f"/api/v1/experiments/{exp_id}/searcher/operations",
                 {"ops": ops}, timeout=60)
            submitted += n
        submit_wall = max(time.monotonic() - t_sub0, 1e-9)

        peak_queue = 0
        done = 0
        incomplete = False
        while True:
            s = _sched(port)
            gauges = s.get("gauges") or {}
            peak_queue = max(peak_queue, int(gauges.get("queue_depth") or 0))
            done = int(_counters(s).get("completed", 0)
                       - base_c.get("completed", 0))
            # completed_total counts every terminal allocation, serving
            # replicas included — subtract them to see the trial side
            serving_done = int(_counters(s).get("serving_completed", 0)
                               - base_c.get("serving_completed", 0))
            trial_done = (done - serving_done) >= submitted
            if trial_done and not serving_thread.is_alive():
                break
            if time.monotonic() - t_total0 > budget_s:
                incomplete = True
                break
            time.sleep(0.25)
        serving_thread.join(timeout=60)
        serving_wall = max(time.monotonic() - t_serving0, 1e-9)
        stop.set()
        for s_ in sims:
            s_.join(timeout=5)

        fleet.sample_telemetry()
        fleet_roll = aggregator.serving_fleet_rollup()
        fleet_stats = fleet.stats()
        # prefix-cache effectiveness, summed over the replicas' engines —
        # the hit-rate to read next to the serving p99 below
        prefix_hits = prefix_misses = 0
        for r in fleet.replicas():
            st = r.engine.stats()
            prefix_hits += st.prefix_hit_blocks
            prefix_misses += st.prefix_miss_blocks
        prefix_total = prefix_hits + prefix_misses
        prefix_hit_rate = (round(prefix_hits / prefix_total, 4)
                           if prefix_total else None)

        # the observed p99-slowest request, by id — paste it straight into
        # ``dct trace request <id>`` to pull the stitched per-request trace
        lat_pcts = _percentiles([t for t, _ in serving_lat])
        p99_slowest = None
        if serving_lat and lat_pcts["p99"] is not None:
            at_or_above = [(t, r) for t, r in serving_lat
                           if t >= lat_pcts["p99"]]
            pool = at_or_above or serving_lat
            p99_slowest = max(pool, key=lambda p: p[0])[1]

        final = _sched(port)
        fc, lat = _counters(final), final.get("latency") or {}
        # the acceptance probe: serving gang allocations visible in the
        # master's own scheduler families
        metrics_text = ""
        try:
            r = urllib.request.Request(f"http://127.0.0.1:{port}/metrics")
            with urllib.request.urlopen(r, timeout=10) as resp:
                metrics_text = resp.read().decode()
        except (OSError, ValueError):
            pass
        serving_families = sorted({
            line.split("{")[0].split(" ")[0]
            for line in metrics_text.splitlines()
            if line.startswith("dct_master_sched_serving")})

        def delta(name: str) -> int:
            return int(fc.get(name, 0) - base_c.get(name, 0))

        s2r = lat.get("submit_to_running_seconds") or {}
        return {
            "trials": {
                "requested": trials,
                "submitted": submitted,
                "completed": done,
                "submits_per_sec": round(submitted / submit_wall, 2),
                "peak_queue_depth": peak_queue,
                "submit_to_running_s": {
                    "p50": s2r.get("p50"), "p95": s2r.get("p95"),
                    "p99": s2r.get("p99"), "count": s2r.get("count"),
                },
            },
            "serving": {
                "replicas": serving_replicas,
                "requests": serving_requests,
                "errors": serving_errors[0],
                "completed": fleet_stats.completed,
                "tokens_generated": fleet_stats.tokens_generated,
                "tokens_per_sec": round(
                    fleet_stats.tokens_generated / serving_wall, 2),
                "request_total_s": lat_pcts,
                "p99_slowest_request_id": p99_slowest,
                "shared_prefix": shared_prefix,
                "prefix_hit_blocks": prefix_hits,
                "prefix_miss_blocks": prefix_misses,
                "prefix_hit_rate": prefix_hit_rate,
                "master_counters": {
                    "serving_submitted": delta("serving_submitted"),
                    "serving_running": delta("serving_running"),
                    "serving_completed": delta("serving_completed"),
                },
                "sched_serving_families": serving_families,
            },
            "fleet_rollup": fleet_roll,
            "duration_s": round(time.monotonic() - t_total0, 3),
            "agent_errors": sum(s_.errors for s_ in sims),
            "incomplete": incomplete,
        }
    except (OSError, ValueError, KeyError, ImportError) as exc:
        return {"error": f"{type(exc).__name__}: {exc}"}
    finally:
        if link is not None:
            link.close(kill_fleet=True)
        if fleet is not None:
            fleet.close()
        if proc is not None:
            proc.kill()
            try:
                proc.wait(timeout=10)
            except Exception:  # noqa: BLE001
                pass
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)


def make_zipf_prompts(count: int, *, templates: int = 12,
                      skew: float = 1.1, seed: int = 0,
                      block_size: int = 8, shared_blocks: int = 1,
                      tail_len: int = 3) -> list:
    """Seeded Zipf-shaped prompt stream over a template pool.

    Every prompt opens with the same ``shared_blocks`` KV blocks of
    system prefix (the "millions of users, one system prompt" head),
    then one block of per-template body drawn Zipf(``skew``) — rank 1
    dominates — then a short per-request tail. The shape is what the
    KV hierarchy and router affinity are built for: a few hot chains
    plus a long cold tail, fully deterministic per ``seed``.
    """
    rnd = random.Random(seed)
    weights = [1.0 / (r ** skew) for r in range(1, max(1, templates) + 1)]
    total = sum(weights)
    system = [(7 * i + 3) % 89 + 1 for i in range(shared_blocks * block_size)]
    pool = []
    for t in range(max(1, templates)):
        body_rnd = random.Random(10_000 + t)
        pool.append(system
                    + [body_rnd.randrange(1, 90) for _ in range(block_size)])
    prompts = []
    for _ in range(count):
        x = rnd.random() * total
        acc = 0.0
        idx = 0
        for i, w in enumerate(weights):
            acc += w
            if x <= acc:
                idx = i
                break
        prompts.append(pool[idx]
                       + [rnd.randrange(1, 90) for _ in range(tail_len)])
    return prompts


def run_zipf_load(requests: int = 160, replicas: int = 4,
                  templates: int = 12, skew: float = 1.1, seed: int = 0,
                  tokens_per_request: int = 8, shared_blocks: int = 1,
                  iteration_floor_s: float = 0.01, kv_store=False,
                  restart_at: float | None = None,
                  budget_s: float = 300.0) -> dict:
    """Zipf-shaped serving load against a standalone fleet (no master).

    The measurement the KV memory hierarchy is judged by: fleet-wide
    prefix hit rate printed beside the request p99, under a seeded Zipf
    over a prompt-template pool whose heads share a system prefix.
    ``kv_store=False`` is the per-replica prefix-cache baseline;
    ``kv_store=True`` (or a ``KVBlockStore``) turns on the shared
    host/CAS tier plus router prefix affinity.

    ``restart_at`` (a fraction of the burst) restarts one replica
    mid-burst through the drain protocol: the departing replica flushes
    its resident blocks to the tier, and the report's ``restart`` block
    shows how many blocks the replacement promoted back instead of
    re-prefilling (``kv_promoted_blocks`` > 0 with ``kv_miss_blocks``
    low is the warm-failover signature).
    """
    t0 = time.monotonic()
    fleet = None
    try:
        import jax

        from determined_clone_tpu.models import gpt
        from determined_clone_tpu.serving import ServingFleet
        from determined_clone_tpu.serving.bucketing import BucketSpec
        from determined_clone_tpu.serving.kv_cache import KVCacheConfig

        cfg = gpt.GPTConfig(vocab_size=97, n_layers=2, d_model=32,
                            n_heads=4, d_ff=64, max_seq_len=64,
                            remat=False, attention_impl="mha")
        params = gpt.init(jax.random.PRNGKey(0), cfg)
        cache = KVCacheConfig(num_blocks=32, block_size=8)
        fleet = ServingFleet(
            params, cfg, name="zipf", buckets=BucketSpec.build(4, 32),
            cache=cache, max_queue_depth=max(64, requests),
            iteration_floor_s=iteration_floor_s,
            prefix_cache=True, kv_store=kv_store)
        fleet.scale_up(replicas)
        prompts = make_zipf_prompts(
            requests, templates=templates, skew=skew, seed=seed,
            block_size=cache.block_size, shared_blocks=shared_blocks)
        restart_idx = (min(requests - 1, max(1, int(requests * restart_at)))
                       if restart_at is not None else None)

        lat: list = []
        errors = [0]
        # engine counters survive replica teardown only if snapshotted
        # first — the burst's fleet-wide totals fold these back in
        retired = {"prefix_hits": 0, "prefix_misses": 0, "kv_host": 0,
                   "kv_cas": 0, "kv_miss": 0, "kv_promoted": 0,
                   "kv_spilled": 0}

        def drain(handles: list) -> None:
            for h in handles:
                try:
                    lat.append(h.result(60.0).total_s)
                except Exception:  # noqa: BLE001 — counted, not fatal
                    errors[0] += 1

        def snapshot(rep) -> None:
            st = rep.engine.stats()
            retired["prefix_hits"] += st.prefix_hit_blocks
            retired["prefix_misses"] += st.prefix_miss_blocks
            retired["kv_host"] += st.kv_host_hit_blocks
            retired["kv_cas"] += st.kv_cas_hit_blocks
            retired["kv_miss"] += st.kv_miss_blocks
            retired["kv_promoted"] += st.kv_promoted_blocks
            retired["kv_spilled"] += st.kv_spilled_blocks

        restarted = None
        handles: list = []
        for i, prompt in enumerate(prompts):
            if restart_idx is not None and i == restart_idx:
                # quiesce in-flight work, then restart one replica
                # through the drain protocol (stop_replica flushes its
                # resident blocks to the tier on the way down)
                drain(handles)
                handles = []
                victim_id = fleet.replica_ids()[0]
                with fleet._lock:
                    victim = fleet._replicas[victim_id]
                # flush before snapshotting so the victim's spill
                # counters land in the totals (stop_replica's own flush
                # then dedups as duplicate_puts)
                fleet._flush_kv(victim)
                snapshot(victim)
                fleet.stop_replica(victim_id)
                restarted = fleet.scale_up(1)[0]
            if time.monotonic() - t0 > budget_s:
                break
            try:
                handles.append(fleet.submit(prompt, tokens_per_request,
                                            timeout=30.0))
            except Exception:  # noqa: BLE001
                errors[0] += 1
        drain(handles)

        hits = retired["prefix_hits"]
        misses = retired["prefix_misses"]
        kv = dict(retired)
        warm = None
        for rep in fleet.replicas():
            st = rep.engine.stats()
            hits += st.prefix_hit_blocks
            misses += st.prefix_miss_blocks
            kv["kv_host"] += st.kv_host_hit_blocks
            kv["kv_cas"] += st.kv_cas_hit_blocks
            kv["kv_miss"] += st.kv_miss_blocks
            kv["kv_promoted"] += st.kv_promoted_blocks
            kv["kv_spilled"] += st.kv_spilled_blocks
            if rep.replica_id == restarted:
                warm = {
                    "replica": restarted,
                    "kv_promoted_blocks": st.kv_promoted_blocks,
                    "kv_host_hit_blocks": st.kv_host_hit_blocks,
                    "kv_cas_hit_blocks": st.kv_cas_hit_blocks,
                    "kv_miss_blocks": st.kv_miss_blocks,
                    "prefix_hit_blocks": st.prefix_hit_blocks,
                }
        looked = hits + misses
        kv_looked = kv["kv_host"] + kv["kv_cas"] + kv["kv_miss"]
        return {
            "requests": requests,
            "completed": len(lat),
            "errors": errors[0],
            "replicas": replicas,
            "templates": templates,
            "skew": skew,
            "seed": seed,
            "kv_store": bool(kv_store),
            "request_total_s": _percentiles(lat),
            "prefix_hit_blocks": hits,
            "prefix_miss_blocks": misses,
            "prefix_hit_rate": (round(hits / looked, 4)
                                if looked else None),
            "kv_tier_hit_rate": (round(
                (kv["kv_host"] + kv["kv_cas"]) / kv_looked, 4)
                if kv_looked else None),
            "kv_host_hit_blocks": kv["kv_host"],
            "kv_cas_hit_blocks": kv["kv_cas"],
            "kv_miss_blocks": kv["kv_miss"],
            "kv_promoted_blocks": kv["kv_promoted"],
            "kv_spilled_blocks": kv["kv_spilled"],
            "kv_stats": fleet.kv_stats(),
            "restart": warm,
            "duration_s": round(time.monotonic() - t0, 3),
        }
    except ImportError as exc:
        return {"error": f"ImportError: {exc}"}
    finally:
        if fleet is not None:
            fleet.close()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--trials", type=int, default=1000)
    parser.add_argument("--agents", type=int, default=8)
    parser.add_argument("--slots", type=int, default=8,
                        help="slots per simulated agent")
    parser.add_argument("--budget", type=float, default=180.0,
                        help="total wall-clock budget in seconds")
    parser.add_argument("--master", default=None,
                        help="PORT of a live master (default: spawn one)")
    parser.add_argument("--mixed", action="store_true",
                        help="mixed traffic: trials + a real serving "
                             "fleet on one simulated cluster")
    parser.add_argument("--serving-replicas", type=int, default=2)
    parser.add_argument("--serving-requests", type=int, default=120)
    parser.add_argument("--shared-prefix", action="store_true",
                        help="serving traffic shares a common system "
                             "prompt (exercises the COW prefix cache; "
                             "reports block hit-rate beside p99)")
    parser.add_argument("--zipf", action="store_true",
                        help="master-free Zipf serving load: seeded Zipf "
                             "over a prompt-template pool with shared "
                             "system-prefix heads; reports fleet-wide "
                             "prefix hit rate beside p99")
    parser.add_argument("--zipf-templates", type=int, default=12)
    parser.add_argument("--zipf-skew", type=float, default=1.1)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--kv-store", action="store_true",
                        help="with --zipf: turn on the fleet-wide KV "
                             "memory hierarchy (host tier + router "
                             "prefix affinity)")
    parser.add_argument("--restart-at", type=float, default=None,
                        help="with --zipf: restart one replica after "
                             "this fraction of the burst (warm-failover "
                             "leg)")
    args = parser.parse_args(argv)
    if args.zipf:
        result = run_zipf_load(
            requests=args.serving_requests,
            replicas=args.serving_replicas,
            templates=args.zipf_templates, skew=args.zipf_skew,
            seed=args.seed, kv_store=args.kv_store,
            restart_at=args.restart_at, budget_s=args.budget)
    elif args.mixed:
        result = run_mixed_load(
            trials=args.trials, agents=args.agents,
            slots_per_agent=args.slots,
            serving_replicas=args.serving_replicas,
            serving_requests=args.serving_requests, budget_s=args.budget,
            master_port=int(args.master) if args.master else None,
            shared_prefix=args.shared_prefix)
    else:
        result = run_load(trials=args.trials, agents=args.agents,
                          slots_per_agent=args.slots, budget_s=args.budget,
                          master_port=int(args.master) if args.master
                          else None)
    print(json.dumps(result, indent=2))
    return 1 if result.get("error") else 0


if __name__ == "__main__":
    sys.exit(main())
