"""Test configuration: force a virtual 8-device CPU mesh before JAX initializes.

Mirrors the reference's "artificial slots" trick (agent/internal/detect/detect.go:39-56)
— an 8-"chip" gang runs on one box — but via XLA's host-platform device count so that
jax.sharding.Mesh code paths are exercised exactly as they would be on a v5e-8.

The steering itself (env + jax.config) lives in
determined_clone_tpu.utils.host_steering, shared with __graft_entry__.
"""
import dataclasses
import functools
import os
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from determined_clone_tpu.utils.host_steering import steer_to_host_cpu  # noqa: E402

steer_to_host_cpu(8)

# XLA aborts the process when the participants of a CPU collective (the
# eight virtual devices' threads) have not all arrived within 40 s of the
# first. Beside five other workers on a crowded host that clock reads the
# machine (``test_trainer.py::TestTrainerMnist::test_mnist_mlp_learns_sharded``
# and ``test_examples_e2e.py::test_mnist_distributed_dp8`` died of it now
# and then): the rendezvous waits for its participants, four minutes at
# most so that a real deadlock still ends well inside the run's limit. The
# trial processes the e2e tests start inherit it with the environment.
os.environ["XLA_FLAGS"] += (
    " --xla_cpu_collective_call_warn_stuck_timeout_seconds=60"
    " --xla_cpu_collective_call_terminate_timeout_seconds=240")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running chaos/e2e tests, excluded from the tier-1 "
        "lane (-m 'not slow'); run_tests.sh --chaos runs them")


# Library threads are daemon (so a leak can't hang interpreter exit), but
# every one of them has a join()ing owner — a survivor means a test skipped
# a close()/stop() path. Named prefixes cover the telemetry-adjacent fleet:
# the device feeders (spans ride the producer thread), the profiler's
# sampler/flusher, checkpoint uploads and tb-sync.
_LIBRARY_THREAD_PREFIXES = (
    "train-prefetch", "eval-prefetch", "device-prefetch",
    "profiler-", "ckpt-upload", "tb-sync",
    "serving-engine", "serving-http",
    "fleet-link", "fleet-drain", "fleet-autoscaler", "fleet-http",
    "fleet-supervisor",
    "dct-tsdb-scrape",
)

# Deliberately process-lifetime daemon threads: the shared transfer pool's
# workers (storage/transfer.py) park on a queue between checkpoint
# uploads/restores by design — surviving a test is correct, not a leak.
_PERSISTENT_THREAD_PREFIXES = ("dct-xfer",)


@pytest.fixture(autouse=True)
def no_leaked_nondaemon_threads():
    """Fail any test that leaks a non-daemon thread, or a *library* daemon
    thread (by name prefix — see _LIBRARY_THREAD_PREFIXES).

    A surviving non-daemon thread would hang interpreter exit in
    production; a surviving library daemon thread means a feeder/profiler
    shutdown path was skipped. A short grace window lets threads a test
    just signalled finish dying. Threads in _PERSISTENT_THREAD_PREFIXES
    are exempt — they are shared process-wide by design.
    """
    before = set(threading.enumerate())
    yield

    def leaked():
        return [t for t in threading.enumerate()
                if t not in before and t.is_alive()
                and not t.name.startswith(_PERSISTENT_THREAD_PREFIXES)
                and (not t.daemon
                     or t.name.startswith(_LIBRARY_THREAD_PREFIXES))]

    deadline = time.monotonic() + 2.0
    while leaked() and time.monotonic() < deadline:
        time.sleep(0.05)
    remaining = leaked()
    assert not remaining, (
        f"test leaked threads: "
        f"{[(t.name, 'daemon' if t.daemon else 'non-daemon') for t in remaining]}")


# -- serving: the two pool-row geometries ------------------------------------

@pytest.fixture(params=["padded_rows", "aligned_rows"])
def geometry(request):
    """``(cfg, params)`` for the two cases of a KV pool row
    (serving/kv_cache.py:kv_row_width): the requesting module's ``CFG``
    and ``params`` (H * head_dim = 32, padded to a 128-wide row) and the
    same model at d_model = 128, which fills the row exactly."""
    cfg = request.module.CFG
    if request.param == "padded_rows":
        return cfg, request.getfixturevalue("params")
    return _aligned(cfg)


@functools.lru_cache(maxsize=None)
def _aligned(cfg):
    """One tree a configuration and worker: the cases only read it."""
    import jax

    from determined_clone_tpu.models import gpt

    aligned = dataclasses.replace(cfg, d_model=128)
    return aligned, gpt.init(jax.random.PRNGKey(0), aligned)


@pytest.fixture
def assert_pool_rows():
    """Check an idle engine's pools: [L, N, block, R] with R a multiple of
    128, something written, the padding columns past H * head_dim still
    zero, and ``pool_bytes`` counting exactly what is allocated."""
    def check(eng, cfg):
        D = cfg.n_heads * cfg.head_dim
        for pool in eng._pools:
            assert pool.shape == (cfg.n_layers, eng.cache.num_blocks,
                                  eng.cache.block_size, -(-D // 128) * 128)
            assert bool((pool[..., :D] != 0).any())
            assert not bool((pool[..., D:] != 0).any())
        assert eng.cache.pool_bytes(
            cfg.n_layers, cfg.n_heads, cfg.head_dim,
            eng._pools[0].dtype.itemsize) == 2 * eng._pools[0].nbytes
    return check
