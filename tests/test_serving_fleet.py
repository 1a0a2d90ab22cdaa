"""Serving-fleet surface (docs/serving.md "Replica fleets"): the
least-loaded router's selection/failover/exclusion contract on fake
ports, drain-protected scale-down that never drops an in-flight request,
blue-green rollout under load with bit-identical greedy outputs, the
queue-driven autoscaler's deterministic grow/shrink/cooldown ticks, the
aggregator's fleet rollup, the fleet HTTP front door, and the master
``serving`` gang-allocation lifecycle (skips when the C++ build is
unavailable)."""
import dataclasses
import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from determined_clone_tpu.core._serialization import save_pytree
from determined_clone_tpu.models import gpt
from determined_clone_tpu.serving import (
    AutoscalePolicy,
    Autoscaler,
    AutoscaleSignals,
    BucketSpec,
    KVCacheConfig,
    LeastLoadedRouter,
    MasterLink,
    NoHealthyReplica,
    ServerOverloaded,
    ServingFleet,
)
from determined_clone_tpu.serving.http import (
    FleetHTTPServer,
    generate_over_http,
)
from determined_clone_tpu.telemetry import MetricsRegistry
from determined_clone_tpu.telemetry.aggregate import (
    ClusterMetricsAggregator,
    format_summary,
)
from tests.test_platform import build_binaries, start_master

CFG = gpt.GPTConfig(vocab_size=97, n_layers=2, d_model=32, n_heads=4,
                    d_ff=64, max_seq_len=48, remat=False,
                    attention_impl="mha")
# the smallest ladder that still has a batch dimension: 2 batch buckets x
# 1 prefill bucket keeps per-test warmup to a handful of tiny compiles
BUCKETS = BucketSpec.build(2, 8)
CACHE = KVCacheConfig(num_blocks=16, block_size=8)
PROMPT = [1, 2, 3]  # == the rollout probe default, so probe output is a ref
MAX_NEW = 8


@pytest.fixture(scope="module")
def params():
    return gpt.init(jax.random.PRNGKey(0), CFG)


@jax.jit
def _next_token(params, toks, n):
    return jnp.argmax(gpt.apply(params, CFG, toks)[0, n - 1])


def naive_greedy(params, prompt, max_new):
    """Reference decode: full-context uncached forward every step, one
    program for every length: the context is padded on the right to
    ``max_seq_len``, which no causal position on the left can see."""
    toks = list(prompt)
    for _ in range(max_new):
        padded = toks + [0] * (CFG.max_seq_len - len(toks))
        toks.append(int(_next_token(
            params, jnp.asarray([padded], jnp.int32), len(toks))))
    return toks[len(prompt):]


def make_fleet(params, **kw):
    kw.setdefault("buckets", BUCKETS)
    kw.setdefault("cache", CACHE)
    kw.setdefault("warmup", False)  # correctness tests compile on demand
    return ServingFleet(params, CFG, **kw)


# -- router units (fake ports — no engines, no jax) --------------------------

class FakePort:
    def __init__(self, rid, queue=0, free=16, fail=None):
        self.replica_id = rid
        self.queue = queue
        self.free = free
        self.fail = fail
        self.admit = True
        self.submitted = 0

    def admitting(self):
        return self.admit

    def load(self):
        return (self.queue, -self.free)

    def submit(self, prompt, max_new_tokens, *, eos_token_id=None,
               request_id=None):
        if self.fail is not None:
            raise self.fail
        self.submitted += 1

        class Handle:
            def result(self, timeout=None):
                return None

        return Handle()


def test_router_picks_least_queue_then_blocks_then_id():
    r = LeastLoadedRouter()
    a = FakePort("a", queue=3, free=16)
    b = FakePort("b", queue=1, free=2)
    c = FakePort("c", queue=1, free=9)
    for port in (a, b, c):
        r.add(port)
    # queue depth is the primary key ...
    assert r.pick().replica_id == "c"
    # ... free blocks break the queue tie (more is better) ...
    c.free = 2
    c2 = FakePort("a0", queue=1, free=2)
    r.add(c2)
    # ... and the id breaks a full tie, deterministically
    assert r.pick().replica_id == "a0"
    # a draining replica is never picked, whatever its load
    c2.admit = False
    b.admit = False
    c.admit = False
    assert r.pick().replica_id == "a"


def test_router_failover_excludes_and_counts_redispatch():
    now = [0.0]
    r = LeastLoadedRouter(exclude_cooldown_s=5.0, clock=lambda: now[0])
    bad = FakePort("bad", queue=0, fail=ServerOverloaded("queue full"))
    good = FakePort("good", queue=7)
    r.add(bad)
    r.add(good)
    # least-loaded would be bad; its 429 fails over to good in ONE call
    handle = r.submit(PROMPT, MAX_NEW)
    assert handle.replica_id == "good"
    assert good.submitted == 1
    assert r.excluded() == ["bad"]
    assert 'router_redispatch_total{reason="overloaded"} 1' \
        in r.registry.dump()
    # while excluded, traffic keeps landing on the healthy replica
    assert r.submit(PROMPT, MAX_NEW).replica_id == "good"
    # the cooldown expiring re-probes the failed replica
    now[0] = 6.0
    bad.fail = None
    assert r.excluded() == []
    assert r.submit(PROMPT, MAX_NEW).replica_id == "bad"


def test_router_connection_error_reason_label():
    r = LeastLoadedRouter()
    flaky = FakePort("flaky", queue=0, fail=ConnectionError("reset"))
    ok = FakePort("ok", queue=9)
    r.add(flaky)
    r.add(ok)
    assert r.submit(PROMPT, MAX_NEW).replica_id == "ok"
    assert 'router_redispatch_total{reason="connection"} 1' \
        in r.registry.dump()


def test_router_no_healthy_replica_raises():
    r = LeastLoadedRouter()
    with pytest.raises(NoHealthyReplica):
        r.submit(PROMPT, MAX_NEW, timeout=0.3)
    sick = FakePort("sick", fail=ServerOverloaded("full"))
    r.add(sick)
    with pytest.raises(NoHealthyReplica):
        r.submit(PROMPT, MAX_NEW, timeout=0.3)


def test_router_bad_request_not_failed_over():
    boom = FakePort("boom", fail=ValueError("empty prompt"))
    spare = FakePort("spare", queue=9)
    r = LeastLoadedRouter()
    r.add(boom)
    r.add(spare)
    # a malformed request is the client's fault: surfaced, not re-routed
    with pytest.raises(ValueError):
        r.submit(PROMPT, MAX_NEW)
    assert spare.submitted == 0
    assert r.excluded() == []


# -- fleet: routing parity, stats, aggregator rollup -------------------------

def test_fleet_parity_stats_and_rollup(params):
    """Both replicas serve, every routed output is bit-identical to the
    uncached reference, and the sampled per-replica registries roll up
    into the aggregator's fleet view (and its dct_fleet_* gauges)."""
    expected = naive_greedy(params, PROMPT, MAX_NEW)
    agg = ClusterMetricsAggregator()
    fleet = make_fleet(params, iteration_floor_s=0.05, aggregator=agg)
    try:
        fleet.scale_up(2)
        handles = [fleet.submit(PROMPT, MAX_NEW, timeout=60.0)
                   for _ in range(16)]
        results = [h.result(timeout=60.0) for h in handles]
        assert all(r.tokens == expected for r in results)
        # the burst queues deep enough that least-loaded MUST spread it
        assert {h.replica_id for h in handles} == set(fleet.replica_ids())

        st = fleet.stats()
        assert st.replicas == 2 and st.healthy == 2
        assert st.completed == 16 and st.rejected == 0
        assert st.tokens_generated == 16 * MAX_NEW
        assert st.max_p99_s > 0.0

        fleet.sample_telemetry()
        rollup = agg.serving_fleet_rollup()
        assert rollup is not None
        assert rollup["replicas"] == 2
        assert rollup["requests_completed"] == 16
        assert rollup["free_kv_blocks"] == 2 * CACHE.num_blocks
        assert rollup["max_replica_p99_s"] == pytest.approx(
            st.max_p99_s, rel=1e-6)
        dump = agg.dump()
        assert "dct_fleet_replicas 2" in dump
        assert "dct_fleet_requests_completed 16" in dump
        assert 'component="serving_replica_' in dump
        summary = agg.summary()
        assert summary["serving_fleet"]["replicas"] == 2
        assert "serving fleet: 2 replicas" in format_summary(summary)
    finally:
        fleet.close()


def test_scale_down_mid_burst_never_drops_requests(params):
    """The drain protocol: scaling down while a burst is in flight must
    complete every accepted request (on the right params) before the
    victim replica exits."""
    expected = naive_greedy(params, PROMPT, MAX_NEW)
    fleet = make_fleet(params, iteration_floor_s=0.02)
    try:
        fleet.scale_up(2)
        handles = [fleet.submit(PROMPT, MAX_NEW, timeout=60.0)
                   for _ in range(16)]
        # mid-burst: both replicas hold queued + running work right now
        assert fleet.stats().queue_depth > 0
        removed = fleet.scale_down(1, timeout=60.0)
        assert len(removed) == 1
        # the drain blocked until the victim was idle — nothing dropped
        results = [h.result(timeout=60.0) for h in handles]
        assert [r.tokens for r in results] == [expected] * 16
        assert fleet.stats().rejected == 0
        assert len(fleet.replica_ids()) == 1
        # the survivor keeps serving
        assert fleet.submit(PROMPT, MAX_NEW,
                            timeout=60.0).result(60.0).tokens == expected
    finally:
        fleet.close()


def test_blue_green_rollout_under_load_bit_identical(params):
    """Rollout mid-burst: zero failed requests, and every greedy output
    equals the old- or new-version reference bit for bit — a drain
    boundary means no sequence ever spans the param swap."""
    old_ref = naive_greedy(params, PROMPT, MAX_NEW)
    new_params = jax.tree_util.tree_map(lambda x: x * 3.0, params)
    new_ref = naive_greedy(new_params, PROMPT, MAX_NEW)
    assert old_ref != new_ref  # x3 provably changes the greedy stream

    fleet = make_fleet(params, iteration_floor_s=0.01)
    try:
        fleet.scale_up(2)
        box = {}

        def do_rollout():
            box["report"] = fleet.rollout(new_params,
                                          probe_tokens=MAX_NEW)

        roller = threading.Thread(target=do_rollout, name="test-rollout")
        handles = []
        for i in range(24):
            handles.append(fleet.submit(PROMPT, MAX_NEW, timeout=60.0))
            if i == 6:
                roller.start()
            time.sleep(0.005)  # the burst must span the rollout window
        results = [h.result(timeout=60.0) for h in handles]
        roller.join(60.0)
        assert not roller.is_alive()

        phases = {tuple(r.tokens) for r in results}
        assert phases <= {tuple(old_ref), tuple(new_ref)}
        assert tuple(old_ref) in phases  # traffic before the swap ...
        report = box["report"]
        assert report.order == sorted(fleet.replica_ids())
        assert report.probe_output == new_ref  # canary proven on new params
        assert set(report.drain_s) == set(report.order)
        assert report.duration_s > 0.0
        # ... and the fleet serves the new version afterwards
        assert fleet.submit(PROMPT, MAX_NEW,
                            timeout=60.0).result(60.0).tokens == new_ref
        assert fleet.stats().rejected == 0
    finally:
        fleet.close()


# -- autoscaler: deterministic ticks on injected signals ---------------------

class FakeFleet:
    def __init__(self, healthy=1):
        self.registry = MetricsRegistry()
        self.healthy = healthy
        self.ups = []
        self.downs = []

    def healthy_count(self):
        return self.healthy

    def scale_up(self, n):
        self.ups.append(n)
        self.healthy += n

    def scale_down(self, n, timeout=60.0):
        self.downs.append(n)
        self.healthy -= n


def test_autoscaler_grow_shrink_cooldown():
    fleet = FakeFleet(healthy=1)
    policy = AutoscalePolicy(min_replicas=1, max_replicas=3,
                             queue_high=8.0, p99_high_s=2.0,
                             breach_ticks=2, queue_low=0.5,
                             idle_ticks=2, cooldown_ticks=1)
    scaler = Autoscaler(fleet, policy)
    hot = AutoscaleSignals(healthy=1, queue_depth=20, p99_s=0.1)
    # sustained breach: hold (streak 1) → grow (streak 2) → cooldown hold
    assert scaler.tick(hot) == "hold"
    assert scaler.tick(hot) == "grow"
    assert fleet.ups == [1] and fleet.healthy == 2
    assert scaler.tick(hot) == "hold"  # cooldown eats this tick
    # a single calm tick resets the breach streak
    calm = AutoscaleSignals(healthy=2, queue_depth=4, p99_s=0.1)
    assert scaler.tick(hot) == "hold"
    assert scaler.tick(calm) == "hold"
    assert scaler.tick(hot) == "hold"
    assert fleet.ups == [1]
    # p99 breach alone also counts as congestion — the streak is shared
    # with the queue signal, so the hot tick above plus this one grows
    slow = AutoscaleSignals(healthy=2, queue_depth=0, p99_s=5.0)
    assert scaler.tick(slow) == "grow"
    assert fleet.healthy == 3
    assert scaler.tick(slow) == "hold"  # cooldown
    # at max_replicas a sustained breach holds instead of growing
    full = AutoscaleSignals(healthy=3, queue_depth=60, p99_s=9.0)
    assert scaler.tick(full) == "hold"
    assert scaler.tick(full) == "hold"
    assert fleet.ups == [1, 1]
    # idle: two quiet ticks shrink, through the drain-protected path
    idle = AutoscaleSignals(healthy=3, queue_depth=0, p99_s=0.0)
    assert scaler.tick(idle) == "hold"
    assert scaler.tick(idle) == "shrink"
    assert fleet.downs == [1] and fleet.healthy == 2
    assert scaler.tick(idle) == "hold"  # cooldown
    dump = scaler.registry.dump()
    assert "autoscale_grow_total 2" in dump
    assert "autoscale_shrink_total 1" in dump


def test_autoscaler_respects_min_replicas_and_dry_run():
    fleet = FakeFleet(healthy=1)
    policy = AutoscalePolicy(min_replicas=1, max_replicas=2,
                             breach_ticks=1, idle_ticks=1,
                             cooldown_ticks=0)
    scaler = Autoscaler(fleet, policy)
    idle = AutoscaleSignals(healthy=1, queue_depth=0, p99_s=0.0)
    # already at the floor: idle streaks never shrink below min
    assert scaler.tick(idle) == "hold"
    assert scaler.tick(idle) == "hold"
    assert fleet.downs == []
    dry = Autoscaler(FakeFleet(healthy=1), policy, dry_run=True)
    hot = AutoscaleSignals(healthy=1, queue_depth=50, p99_s=9.0)
    assert dry.tick(hot) == "grow"
    assert dry.fleet.ups == []  # decided, not applied


# -- HTTP front door ---------------------------------------------------------

def test_fleet_http_generate_scale_rollout(params, tmp_path):
    expected = naive_greedy(params, PROMPT, MAX_NEW)
    new_params = jax.tree_util.tree_map(lambda x: x * 3.0, params)
    new_ref = naive_greedy(new_params, PROMPT, MAX_NEW)
    ckpt = tmp_path / "v2"
    save_pytree(str(ckpt), new_params)

    fleet = make_fleet(params, iteration_floor_s=0.0)
    fleet.scale_up(1)
    try:
        with FleetHTTPServer(fleet) as srv:
            out = generate_over_http(srv.url, PROMPT, MAX_NEW)
            assert out["tokens"] == expected
            assert out["replica_id"] in fleet.replica_ids()

            def req(method, path, body=None):
                r = urllib.request.Request(
                    f"{srv.url}{path}",
                    data=(json.dumps(body).encode()
                          if body is not None else None),
                    method=method,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(r, timeout=60) as resp:
                    return json.loads(resp.read() or "{}")

            j = req("GET", "/v1/fleet")
            assert j["name"] == fleet.name
            assert [r["state"] for r in j["replicas"]] == ["healthy"]

            j = req("POST", "/v1/scale", {"replicas": 2})
            assert len(j["replicas"]) == 2

            j = req("POST", "/v1/rollout", {"checkpoint": str(ckpt)})
            assert j["probe_output"] == new_ref
            assert sorted(j["drain_s"]) == sorted(fleet.replica_ids())
            assert generate_over_http(srv.url, PROMPT,
                                      MAX_NEW)["tokens"] == new_ref

            with urllib.request.urlopen(f"{srv.url}/metrics",
                                        timeout=30) as resp:
                text = resp.read().decode()
            assert "router_requests_total" in text
            assert "dct_fleet_replicas 2" in text

            with pytest.raises(urllib.error.HTTPError) as e:
                req("POST", "/v1/generate", {"prompt": "not-a-list"})
            assert e.value.code == 400
            with pytest.raises(urllib.error.HTTPError) as e:
                req("POST", "/v1/rollout", {})
            assert e.value.code == 400
    finally:
        fleet.close()


# -- master integration: the `serving` gang allocation type ------------------

def master_req(port, method, path, body=None):
    r = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode() if body is not None else None,
        method=method, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(r, timeout=30) as resp:
        return json.loads(resp.read() or "{}")


def test_master_serving_gang_lifecycle(params, tmp_path):
    """Replicas ride real master allocations: the fleet shows up in
    /api/v1/serving/fleets with running gangs, sched telemetry carries
    the serving families, master-driven scale-down drains locally, and
    the kill reclaims every slot."""
    if not build_binaries():
        pytest.skip("C++ master build unavailable")
    expected = naive_greedy(params, PROMPT, MAX_NEW)
    proc, _session, port = start_master(tmp_path)
    fleet = make_fleet(params, name="itest", iteration_floor_s=0.0)
    link = None
    try:
        link = MasterLink(fleet, port, replicas=2)
        link.wait_replicas(2, timeout=60.0)

        fleets = master_req(port, "GET", "/api/v1/serving/fleets")["fleets"]
        mine = next(f for f in fleets if f["name"] == "itest")
        assert mine["running"] == 2 and mine["queued"] == 0
        states = [r["state"] for r in mine["replicas"]]
        assert states.count("RUNNING") == 2

        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
            text = resp.read().decode()
        for fam in ("dct_master_sched_serving_submitted_total",
                    "dct_master_sched_serving_running_total",
                    "dct_master_sched_serving_completed_total"):
            assert fam in text
        assert "dct_master_sched_serving_submitted_total 2" in text

        handles = [fleet.submit(PROMPT, MAX_NEW, timeout=60.0)
                   for _ in range(4)]
        assert all(h.result(60.0).tokens == expected for h in handles)

        # master-driven scale-down: the kill command drains locally
        link.scale(1)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if len(fleet.replica_ids()) == 1:
                break
            time.sleep(0.1)
        assert len(fleet.replica_ids()) == 1
        assert fleet.stats().rejected == 0

        link.close(kill_fleet=True)
        link = None
        mine = next(
            f for f in master_req(port, "GET",
                                  "/api/v1/serving/fleets")["fleets"]
            if f["name"] == "itest")
        assert mine["running"] == 0
    finally:
        if link is not None:
            link.close(kill_fleet=True)
        fleet.close()
        proc.kill()
        proc.wait(timeout=10)
