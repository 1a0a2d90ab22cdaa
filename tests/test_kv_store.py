"""Fleet-wide KV memory hierarchy (docs/serving.md "KV memory
hierarchy"): the host-RAM tier's LRU/byte-budget contract and its CAS
cascade, the ``cas/kv/`` blob tier's every-failure-is-a-plain-miss
integrity story (torn spills, corrupt blobs on disk, double-spill
idempotence), the namespace byte-budget sweep, the prefix-inventory
digest + the router's affinity pre-filter on fake ports, and the
end-to-end warm handoff: a second fleet sharing the tier serves a
previously-seen prefix by promoting blocks instead of re-prefilling —
bit-identically."""
import glob
import os

import jax
import numpy as np
import pytest

from determined_clone_tpu import faults
from determined_clone_tpu.models import gpt
from determined_clone_tpu.serving import (
    BucketSpec,
    KVCacheConfig,
    LeastLoadedRouter,
    ServingFleet,
)
from determined_clone_tpu.serving.kv_cache import PrefixCache
from determined_clone_tpu.serving.kv_store import (
    KVBlockStore,
    PrefixInventory,
    prompt_chain_keys,
)
from determined_clone_tpu.storage.base import SharedFSStorageManager
from determined_clone_tpu.storage.cas import (
    CASStorageManager,
    KVBlobStore,
    namespace_usage,
    sweep_namespace,
)
from determined_clone_tpu.storage.transfer import TransferPool

CFG = gpt.GPTConfig(vocab_size=97, n_layers=2, d_model=32, n_heads=4,
                    d_ff=64, max_seq_len=48, remat=False,
                    attention_impl="mha")
BUCKETS = BucketSpec.build(2, 16)
CACHE = KVCacheConfig(num_blocks=16, block_size=8)
MAX_NEW = 6
# exactly two full KV blocks of shared prefix: a fully-covered prompt
# exercises the COW fork of the final shared block (the engine always
# re-scores the last prompt token). The shapes compiled here must stay a
# subset of tests/test_serving.py's ladder — the jit cache is keyed on
# the underlying forward and shared process-wide, and that module
# asserts its exact size.
PROMPT = [5, 9, 2, 7, 4, 8, 3, 6, 11, 13, 17, 19, 23, 29, 31, 37]


@pytest.fixture(scope="module")
def params():
    return gpt.init(jax.random.PRNGKey(0), CFG)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def payload(seed: int, nbytes: int = 1024) -> dict:
    rng = np.random.default_rng(seed)
    half = nbytes // 2
    return {"k": rng.standard_normal(half // 8).astype(np.float64),
            "v": rng.standard_normal(half // 8).astype(np.float64)}


def make_fleet(params, cfg=CFG, **kw):
    kw.setdefault("buckets", BUCKETS)
    kw.setdefault("cache", CACHE)
    kw.setdefault("warmup", False)
    kw.setdefault("prefix_cache", True)
    return ServingFleet(params, cfg, **kw)


# -- chain keys + inventory (pure units) ------------------------------------

def test_prompt_chain_keys_match_prefix_cache_chain():
    """The router hashes prompts with the same chain the prefix cache
    uses to key blocks — otherwise affinity coverage is always zero."""
    prompt = list(range(1, 20))
    keys = prompt_chain_keys(prompt, 8, 8)
    assert len(keys) == 2  # 19 tokens -> 2 full blocks of 8
    prev = b""
    for i, k in enumerate(keys):
        prev = PrefixCache._chain(prev, prompt[i * 8:(i + 1) * 8])
        assert k == prev.hex()
    # fewer than one full block -> no keys; max_blocks caps the depth
    assert prompt_chain_keys([1, 2, 3], 8, 8) == []
    assert len(prompt_chain_keys(list(range(64)), 8, 3)) == 3


def test_prefix_inventory_coverage_and_roundtrip():
    keys = [f"{i:02x}" * 32 for i in range(40)]
    inv = PrefixInventory.build(keys, top_k=32)
    # exact top-K and bloom overflow are both one-sided: no false
    # negatives for any key that went in
    assert all(inv.covers(k) for k in keys)
    # coverage_depth counts the LEADING covered run — a missed root
    # zeroes it even if deeper keys are resident
    assert inv.coverage_depth(keys[:5]) == 5
    doc = inv.to_dict()
    back = PrefixInventory.from_dict(doc)
    assert back.coverage_depth(keys[:7]) == 7
    assert PrefixInventory.build([]).coverage_depth(keys[:3]) == 0


# -- host tier (KVBlockStore) -----------------------------------------------

def test_host_tier_budget_evicts_lru_under_churn():
    store = KVBlockStore(budget_bytes=4096)
    fp = "fp0"
    for i in range(12):  # ~1 KiB each into a 4 KiB budget
        store.put(fp, f"{i:02d}" * 16, payload(i))
    st = store.stats()
    assert st["bytes"] <= 4096
    assert st["host_evictions"] >= 8
    assert st["entries"] + st["host_evictions"] == st["puts"]
    # without a CAS tier the evicted entries are gone: plain misses
    assert store.get(fp, "00" * 16) is None
    assert store.stats()["misses"] == 1
    # survivors are exact
    got = store.get(fp, "11" * 16)
    assert got is not None
    np.testing.assert_array_equal(got["k"], payload(11)["k"])


def test_host_tier_duplicate_put_is_idempotent():
    store = KVBlockStore(budget_bytes=1 << 20)
    store.put("fp", "aa", payload(1))
    store.put("fp", "aa", payload(1))
    st = store.stats()
    assert st["puts"] == 1 and st["duplicate_puts"] == 1
    assert st["entries"] == 1


def test_host_tier_keys_are_mru_first_per_fingerprint():
    store = KVBlockStore(budget_bytes=1 << 20)
    for hx in ("aa", "bb", "cc"):
        store.put("fp1", hx, payload(0, 64))
    store.put("fp2", "dd", payload(0, 64))
    store.get("fp1", "aa")  # touch -> most recent
    assert store.keys("fp1") == ["aa", "cc", "bb"]
    assert store.keys("fp2") == ["dd"]


def test_host_tier_cascades_to_cas_and_promotes_back(tmp_path):
    inner = SharedFSStorageManager(str(tmp_path))
    blobs = KVBlobStore(inner)
    store = KVBlockStore(budget_bytes=2048, blob_store=blobs)
    fp = "fp0"
    for i in range(6):
        store.put(fp, f"{i:02d}" * 16, payload(i))
    st = store.stats()
    assert st["cas_spills"] == st["host_evictions"] > 0
    # the evicted root is served from cas/kv/ and re-inserted host-side
    got = store.get(fp, "00" * 16)
    assert got is not None
    np.testing.assert_array_equal(got["v"], payload(0)["v"])
    assert store.stats()["cas_hits"] == 1
    assert store.contains(fp, "00" * 16)  # re-inserted


# -- CAS tier (cas/kv/) -----------------------------------------------------

def test_cas_kv_double_spill_is_idempotent(tmp_path):
    blobs = KVBlobStore(SharedFSStorageManager(str(tmp_path)))
    key = {"fingerprint": "fp", "chain": "ab" * 32}
    assert blobs.store(key, payload(3)) is True
    assert blobs.store(key, payload(3)) is True
    assert blobs.session["stores"] == 1
    assert blobs.session["duplicate_stores"] == 1
    assert blobs.stats()["entries"] == 1


def test_cas_kv_torn_spill_is_a_plain_miss(tmp_path):
    """An injected torn write lands truncated bytes under the full
    digest's key; the fetch-side sha256 check convicts and the reader
    sees a plain miss — never wrong K/V."""
    blobs = KVBlobStore(SharedFSStorageManager(str(tmp_path)))
    key = {"fingerprint": "fp", "chain": "cd" * 32}
    plan = faults.activate(faults.plan_from_dict({"rules": [
        {"point": "kv_store.spill", "action": "truncate",
         "keep_bytes": 7, "times": 1}]}))
    try:
        blobs.store(key, payload(4))
    finally:
        faults.deactivate(plan)
    assert blobs.load(key) is None
    assert blobs.session["misses"] >= 1
    assert blobs.session["errors"] >= 1
    # the miss is recoverable: a clean re-spill serves exact bytes.
    # (the torn blob squatted on the full digest's key; the CAS put
    # dedups against it, so the re-spill must still convict at fetch)
    blobs2 = KVBlobStore(SharedFSStorageManager(str(tmp_path) + "-2"))
    assert blobs2.store(key, payload(4)) is True
    got = blobs2.load(key)
    np.testing.assert_array_equal(got["k"], payload(4)["k"])


def test_cas_kv_corrupt_blob_on_disk_is_a_plain_miss(tmp_path):
    blobs = KVBlobStore(SharedFSStorageManager(str(tmp_path)))
    key = {"fingerprint": "fp", "chain": "ef" * 32}
    assert blobs.store(key, payload(5)) is True
    assert blobs.load(key) is not None
    paths = [p for p in glob.glob(str(tmp_path) + "/**/kv/blobs/**",
                                  recursive=True) if os.path.isfile(p)]
    assert paths, "expected a blob file under cas/kv/blobs/"
    with open(paths[0], "r+b") as f:
        f.truncate(11)  # torn on disk after a clean spill
    assert blobs.load(key) is None
    assert blobs.session["errors"] >= 1


def test_cas_kv_index_without_blob_is_a_plain_miss(tmp_path):
    blobs = KVBlobStore(SharedFSStorageManager(str(tmp_path)))
    key = {"fingerprint": "fp", "chain": "0f" * 32}
    assert blobs.store(key, payload(6)) is True
    for p in glob.glob(str(tmp_path) + "/**/kv/blobs/**", recursive=True):
        if os.path.isfile(p):
            os.unlink(p)
    assert blobs.load(key) is None


# -- namespace budget sweep -------------------------------------------------

def test_sweep_namespace_enforces_kv_budget(tmp_path):
    inner = SharedFSStorageManager(str(tmp_path))
    blobs = KVBlobStore(inner)
    for i in range(8):
        blobs.store({"fingerprint": "fp", "chain": f"{i:02d}" * 32},
                    payload(i, 4096))
    before = sum(namespace_usage(inner, "kv").values())
    res = sweep_namespace(inner, "kv", before // 2)
    assert res["swept"] is True
    assert res["evicted"] > 0
    assert res["bytes"] <= before // 2
    # a swept entry is a plain miss; survivors still serve
    hits = sum(
        blobs.load({"fingerprint": "fp", "chain": f"{i:02d}" * 32})
        is not None for i in range(8))
    assert 0 < hits < 8


def test_manager_namespace_budgets_and_stats(tmp_path):
    inner = SharedFSStorageManager(str(tmp_path))
    mgr = CASStorageManager(inner, namespace_budgets={"kv": 8192})
    kv = mgr.kv_store()
    assert kv.budget_bytes == 8192  # inherits the manager's budget
    for i in range(8):
        kv.store({"fingerprint": "fp", "chain": f"{i:02d}" * 32},
                 payload(i, 4096))
    swept = mgr.sweep_namespaces()
    assert swept["kv"]["swept"] is True and swept["kv"]["evicted"] > 0
    stats = mgr.storage_stats()
    ns = stats["namespaces"]["kv"]
    assert ns["bytes"] <= 8192
    assert ns["evictions"] == swept["kv"]["evicted"]
    # chunk GC / checkpoint accounting never counts kv objects
    assert stats["chunk_count"] == 0


# -- the kv/ namespace beside the checkpoint chunks ---------------------------

KV_KEY = {"fingerprint": "fp", "chain": "ab" * 32}


def cas_with_kv_entry(tmp_path):
    """A chunking manager whose store already holds one spilled block."""
    inner = SharedFSStorageManager(str(tmp_path / "store"))
    mgr = CASStorageManager(inner, chunk_size=1024,
                            pool=TransferPool(workers=0))
    assert mgr.kv_store().store(KV_KEY, payload(1)) is True
    rels = set(namespace_usage(inner, "kv"))
    assert len(rels) == 2  # one blob + one index entry
    return mgr, inner, rels


def upload_checkpoint(mgr, tmp_path, sid, nbytes):
    src = tmp_path / "src"
    src.mkdir(exist_ok=True)
    (src / "weights.bin").write_bytes(os.urandom(nbytes))
    mgr.upload(str(src), sid)


def test_chunk_gc_never_sweeps_kv_entries(tmp_path):
    mgr, inner, before = cas_with_kv_entry(tmp_path)
    upload_checkpoint(mgr, tmp_path, "ck-1", 3 * 1024)
    upload_checkpoint(mgr, tmp_path, "ck-2", 3 * 1024)
    # ref-count GC runs on every delete; kv entries are structurally
    # outside the chunk namespace it walks
    mgr.delete("ck-2")
    assert set(namespace_usage(inner, "kv")) == before
    mgr.delete("ck-1")  # last checkpoint gone: chunks empty, kv intact
    assert set(namespace_usage(inner, "kv")) == before
    assert mgr.kv_store().load(KV_KEY) is not None


def test_uncommitted_sweep_skips_the_cas_namespace(tmp_path, monkeypatch):
    from determined_clone_tpu.exec.gc_checkpoints import sweep_uncommitted

    _, inner, before = cas_with_kv_entry(tmp_path)
    # age floor 0: everything uncommitted is sweepable — including the
    # "cas" storage_id (never committed, no COMMIT marker) if the sweep
    # failed to skip it
    monkeypatch.setenv("DCT_GC_UNCOMMITTED_AGE_S", "0")
    assert sweep_uncommitted(inner) == 0
    assert set(namespace_usage(inner, "kv")) == before


def test_storage_stats_splits_chunks_from_kv(tmp_path):
    mgr, _, _ = cas_with_kv_entry(tmp_path)
    upload_checkpoint(mgr, tmp_path, "ck-1", 4 * 1024)
    stats = mgr.storage_stats()
    ns = stats["namespaces"]
    assert set(ns) == {"chunks", "kv"}
    assert ns["chunks"]["objects"] == 4
    assert ns["chunks"]["bytes"] == 4 * 1024
    assert ns["kv"]["entries"] == 1
    assert ns["kv"]["objects"] == 2  # blob + index
    assert ns["kv"]["bytes"] > 0
    # the top-level chunk accounting ignores kv blobs entirely
    assert stats["chunk_count"] == 4
    assert stats["chunk_bytes"] == 4 * 1024


# -- router affinity (fake ports) -------------------------------------------

class FakePort:
    def __init__(self, rid, queue=0, free=16, inventory=None):
        self.replica_id = rid
        self.queue = queue
        self.free = free
        self.admit = True
        self.inventory = inventory

    def admitting(self):
        return self.admit

    def load(self):
        return (self.queue, -self.free)

    def prefix_inventory(self):
        return self.inventory


def test_router_affinity_steers_within_slack():
    prompt = list(range(1, 25))
    keys = prompt_chain_keys(prompt, 8, 8)
    warm = PrefixInventory.build(keys).to_dict()
    r = LeastLoadedRouter(prefix_block_size=8, affinity_queue_slack=2)
    cold = FakePort("a-cold", queue=0, free=16)
    hot = FakePort("b-warm", queue=1, free=16, inventory=warm)
    r.add(cold)
    r.add(hot)
    # coverage wins inside the slack band even against a shorter queue
    assert r.pick(prompt=prompt).replica_id == "b-warm"
    assert r.registry.counter(
        "router_affinity_picks_total", "").value == 1
    # ... but never overrides overload: outside the band, least-loaded
    hot.queue = 3
    assert r.pick(prompt=prompt).replica_id == "a-cold"
    # no prompt / affinity off -> the plain least-loaded contract
    assert r.pick().replica_id == "a-cold"
    r2 = LeastLoadedRouter()  # prefix_block_size=0: affinity disarmed
    r2.add(hot)
    r2.add(cold)
    hot.queue = 0
    assert r2.pick(prompt=prompt).replica_id == "a-cold"


def test_router_affinity_zero_coverage_falls_back():
    prompt = list(range(1, 25))
    r = LeastLoadedRouter(prefix_block_size=8)
    a = FakePort("a", queue=1, free=2)
    b = FakePort("b", queue=1, free=9)
    r.add(a)
    r.add(b)
    # nobody advertises coverage (inventory None): free blocks break
    # the tie exactly as without affinity
    assert r.pick(prompt=prompt).replica_id == "b"
    assert r.registry.counter(
        "router_affinity_picks_total", "").value == 0


# -- end-to-end: promotion is bit-exact, restarts warm from the tier --------

def test_fleet_kv_store_requires_prefix_cache(params):
    with pytest.raises(ValueError, match="prefix_cache"):
        ServingFleet(params, CFG, buckets=BUCKETS, cache=CACHE,
                     prefix_cache=False, kv_store=True)


def test_warm_handoff_promotes_bit_identical(geometry, assert_pool_rows,
                                             tmp_path):
    """The acceptance path in miniature: fleet A serves a prompt and
    flushes to a CAS-backed tier; a brand-new fleet B sharing the tier
    serves the same prompt by PROMOTING the shared blocks (zero misses
    on the shared prefix) and emits bit-identical greedy tokens. A tier
    payload is a block's pool rows, [L, block, R], padding included, and
    the padding is still zero after the round trip."""
    cfg, params = geometry
    D = cfg.n_heads * cfg.head_dim
    blobs = KVBlobStore(SharedFSStorageManager(str(tmp_path)))
    store = KVBlockStore(budget_bytes=32 << 20, blob_store=blobs)

    fleet_a = make_fleet(params, cfg, name="kv-a", kv_store=store)
    try:
        fleet_a.scale_up(1)
        ref, _ = fleet_a.handle_request(PROMPT, MAX_NEW, timeout=60.0)
        ref_tokens = list(ref.tokens)
    finally:
        fleet_a.close()  # close() flushes resident blocks to the tier
    assert store.stats()["puts"] >= 2  # both full prompt blocks landed

    fleet_b = make_fleet(params, cfg, name="kv-b", kv_store=store)
    try:
        fleet_b.scale_up(1)
        res, _ = fleet_b.handle_request(PROMPT, MAX_NEW, timeout=60.0)
        assert list(res.tokens) == ref_tokens
        engine = fleet_b.replicas()[0].engine
        st = engine.stats()
        assert st.kv_promoted_blocks >= 2
        assert st.kv_miss_blocks == 0
        assert st.kv_host_hit_blocks + st.kv_cas_hit_blocks >= 2
        assert_pool_rows(engine, cfg)
        key = prompt_chain_keys(PROMPT, CACHE.block_size, 1)[0]
        spilled = store.get(engine._params_fp, key)
        for rows in (spilled["k"], spilled["v"]):
            assert rows.shape == (cfg.n_layers, CACHE.block_size, 128)
            assert np.any(rows[..., :D] != 0)
            assert not np.any(rows[..., D:] != 0)
        rollup_src = fleet_b.stats()
    finally:
        fleet_b.close()
    assert rollup_src is not None
    assert store.stats()["hit_rate"] is not None


def test_replace_replica_flushes_then_replacement_warms(params):
    """stop/replace teardown demotes resident blocks; the replacement
    promotes them back on its first shared-prefix request."""
    store = KVBlockStore(budget_bytes=32 << 20)
    fleet = make_fleet(params, name="kv-r", kv_store=store)
    try:
        ids = fleet.scale_up(1)
        fleet.handle_request(PROMPT, MAX_NEW, timeout=60.0)
        for rep in fleet.replicas():
            rep.engine.wait_idle(15.0)
        replacement = fleet.replace_replica(ids[0], reason="test")
        assert store.stats()["puts"] >= 2
        res, _ = fleet.handle_request(PROMPT, MAX_NEW, timeout=60.0)
        assert res is not None
        st = [r.engine.stats() for r in fleet.replicas()
              if r.replica_id in replacement][0]
        assert st.kv_promoted_blocks >= 2
        assert st.kv_miss_blocks == 0
    finally:
        fleet.close()


@pytest.mark.slow
def test_chaos_kv_warm_failover_scenario(params):
    """The full seeded chaos scenario: mid-burst replace + drain, the
    replacement warms from the tier with zero tier misses, outputs
    bit-identical, zero leaked blocks."""
    from determined_clone_tpu.serving.chaos import run_scenarios
    (result,) = run_scenarios(["kv_warm_failover"], seed=0,
                              params=params)
    failed = [c.name + ": " + c.detail for c in result.checks
              if not c.ok]
    assert result.passed, failed
