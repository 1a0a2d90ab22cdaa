"""Serving raw-speed features: COW prefix sharing, draft-model
speculative decoding, and chunked prefill (docs/serving.md).

Every optimisation here is a *scheduling/memory* trick over the same
jitted paged forward, so the acceptance property throughout is the one
tests/test_serving.py pins for the base engine: greedy output stays
bit-identical to the naive uncached forward, with all three features
on at once. The allocator-refcount tests pin the invariants the COW
protocol leans on (never freed while referenced, fork-then-release),
and the compile-budget test pins that warmup covers the extended
program ladder — draft, k+1 verify, and block-copy included — so
traffic never compiles.
"""
import dataclasses
import functools
import json
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from determined_clone_tpu.config import schema
from determined_clone_tpu.config.experiment import (
    ConfigError,
    ServingConfig,
    SpeculativeConfig,
)
from determined_clone_tpu.models import gpt
from determined_clone_tpu.serving import (
    BlockAllocator,
    BucketSpec,
    InferenceEngine,
    KVCacheConfig,
    PrefixCache,
)
from determined_clone_tpu.serving.http import (
    ServingHTTPServer,
    generate_over_http,
)
from determined_clone_tpu.telemetry import flops as flops_mod

CFG = gpt.GPTConfig(vocab_size=97, n_layers=2, d_model=32, n_heads=4,
                    d_ff=64, max_seq_len=48, remat=False,
                    attention_impl="mha")

BUCKETS = BucketSpec.build(4, 16)
CACHE = KVCacheConfig(num_blocks=16, block_size=8)

PROMPTS = [[5, 17, 3, 88, 41], [9] * 11, [1, 2, 3]]


@pytest.fixture(scope="module")
def params():
    return gpt.init(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def draft_params():
    """A draft with the target's architecture but different weights: it
    genuinely disagrees with the target, which is the adversarial case
    for the accepted-prefix rule (and, sharing the target's shapes, it
    rides the already-compiled program ladder)."""
    return gpt.init(jax.random.PRNGKey(7), CFG)


@functools.partial(jax.jit, static_argnums=1)
def _next_token(params, cfg, toks, n):
    return jnp.argmax(gpt.apply(params, cfg, toks)[0, n - 1])


def naive_greedy(params, prompt, max_new, cfg=CFG):
    """Reference decode: full-context uncached forward every step, one
    program for every length: the context is padded on the right to
    ``max_seq_len``, which no causal position on the left can see."""
    toks = list(prompt)
    for _ in range(max_new):
        padded = toks + [0] * (cfg.max_seq_len - len(toks))
        toks.append(int(_next_token(
            params, cfg, jnp.asarray([padded], jnp.int32), len(toks))))
    return toks[len(prompt):]


def make_engine(params, cfg=CFG, **kw):
    kw.setdefault("buckets", BUCKETS)
    kw.setdefault("cache", CACHE)
    return InferenceEngine(params, cfg, **kw)


def assert_pool_accounted(eng):
    """Idle-engine allocator invariant: every block is either free or
    held by exactly the prefix cache."""
    stats = eng.stats()
    assert stats.free_blocks == eng.cache.num_blocks \
        - stats.prefix_cached_entries, stats


# -- allocator refcounts: the substrate COW leans on --------------------------

def test_allocator_refcount_sharing():
    alloc = BlockAllocator(KVCacheConfig(num_blocks=4, block_size=8))
    a = alloc.allocate(16)  # 2 blocks at refcount 1
    assert [alloc.refcount(b) for b in a] == [1, 1]
    alloc.retain(a)         # second owner (a prefix-cache entry, say)
    assert [alloc.refcount(b) for b in a] == [2, 2]

    # never freed while referenced: first release drops a reference but
    # returns nothing to the free list
    alloc.release(a)
    assert alloc.free_blocks() == 2
    assert [alloc.refcount(b) for b in a] == [1, 1]
    alloc.release(a)
    assert alloc.free_blocks() == 4
    assert [alloc.refcount(b) for b in a] == [0, 0]

    # over-release of a now-free block and retain of a dead/bogus block
    # are bookkeeping bugs, not soft errors
    with pytest.raises(ValueError):
        alloc.release(a[:1])
    with pytest.raises(ValueError):
        alloc.retain(a[:1])
    with pytest.raises(ValueError):
        alloc.retain([99])


def test_prefix_cache_match_register_evict():
    cache = KVCacheConfig(num_blocks=8, block_size=8)
    alloc = BlockAllocator(cache)
    pc = PrefixCache(cache, alloc)
    prompt = list(range(1, 21))          # 2 full blocks + 4-token tail
    blocks = alloc.allocate(len(prompt))  # 3 blocks, as a sequence would

    pc.register(prompt, blocks)
    assert len(pc) == 3
    assert [alloc.refcount(b) for b in blocks] == [2, 2, 2]

    # byte-identical prompt hits all three entries, including the tail
    m = pc.match(prompt)
    assert m.blocks == blocks and m.shared_len == 20
    assert [alloc.refcount(b) for b in blocks] == [3, 3, 3]
    alloc.release(m.blocks)

    # a different tail only matches the full blocks (tail keys include
    # the exact tail tokens)
    m = pc.match(prompt[:16] + [55, 56])
    assert m.blocks == blocks[:2] and m.shared_len == 16
    alloc.release(m.blocks)

    # divergence in block 0 shares nothing — chained hashes make a key
    # identify tokens AND absolute position
    m = pc.match([77] + prompt[1:])
    assert m.blocks == [] and m.shared_len == 0

    # retire the sequence: blocks survive on the cache's reference alone
    alloc.release(blocks)
    assert alloc.free_blocks() == 5
    assert [alloc.refcount(b) for b in blocks] == [1, 1, 1]

    # eviction drops cache references until the pool has headroom
    dropped = pc.evict(cache.num_blocks)
    assert dropped == 3 and len(pc) == 0
    assert alloc.free_blocks() == cache.num_blocks

    # flush releases everything it holds (hot-swap invalidation)
    blocks = alloc.allocate(8)
    pc.register(prompt[:8], blocks)
    alloc.release(blocks)
    assert pc.flush() == 1
    assert alloc.free_blocks() == cache.num_blocks


# -- COW prefix sharing through the engine ------------------------------------

@pytest.mark.parametrize("attention_impl", ["mha", "flash"])
def test_prefix_sharing_parity_counters_and_cow(geometry, assert_pool_rows,
                                                attention_impl):
    """Repeat and prefix-sharing prompts alias cached blocks (hit/miss
    counters prove it) and still decode bit-identically — the COW fork
    of the written block is what keeps the aliased copy immutable. A fork
    copies whole pool rows, so a row's padding stays zero through it.
    ``"flash"``: the decode steps read the aliased blocks through the
    table in the paged kernel."""
    cfg, params = geometry
    base = list(range(1, 12))            # 1 full block + 3-token tail
    fork = base[:8] + [61, 62, 63]       # shares the full block only
    expected = {tuple(p): naive_greedy(params, p, 8, cfg)
                for p in (base, fork)}
    cfg = dataclasses.replace(cfg, attention_impl=attention_impl)
    with make_engine(params, cfg, prefix_cache=True) as eng:
        r1 = eng.generate(base, 8)       # cold: everything misses
        assert r1.tokens == expected[tuple(base)]
        assert (r1.prefix_hit_blocks, r1.prefix_miss_blocks) == (0, 2)

        r2 = eng.generate(base, 8)       # exact repeat: full + tail hit
        assert r2.tokens == r1.tokens    # COW fork, not corruption
        assert (r2.prefix_hit_blocks, r2.prefix_miss_blocks) == (2, 0)

        r3 = eng.generate(fork, 8)       # shares the full block only
        assert r3.tokens == expected[tuple(fork)]
        assert (r3.prefix_hit_blocks, r3.prefix_miss_blocks) == (1, 1)

        stats = eng.stats()
        assert stats.prefix_hit_blocks == 3
        assert stats.prefix_miss_blocks == 3
        assert stats.prefix_cached_entries > 0
        assert_pool_accounted(eng)
        assert_pool_rows(eng, cfg)
        dump = eng.registry.dump()   # Prometheus text exposition
    assert "prefix_cache_hit_blocks_total 3" in dump
    assert "prefix_cache_miss_blocks_total 3" in dump


# -- speculative decoding -----------------------------------------------------

@pytest.mark.parametrize("attention_impl", ["mha", "flash"])
def test_speculative_parity_with_disagreeing_draft(params, draft_params,
                                                   attention_impl):
    """A randomly-initialised draft disagrees with the target almost
    everywhere; the accepted-prefix rule must still emit exactly the
    target's greedy tokens — a bad draft only costs speed. ``"flash"``:
    the draft's proposal steps run the paged kernel, over pools that hold
    rejected drafts' rows past a row's length."""
    expected = {i: naive_greedy(params, p, 8)
                for i, p in enumerate(PROMPTS)}
    cfg = dataclasses.replace(CFG, attention_impl=attention_impl)
    with make_engine(params, cfg, speculative_k=3,
                     draft_params=draft_params, draft_cfg=cfg) as eng:
        handles = [eng.submit(p, 8, request_id=str(i))
                   for i, p in enumerate(PROMPTS)]
        results = [h.result(timeout=120.0) for h in handles]
        stats = eng.stats()
    for i, r in enumerate(results):
        assert r.tokens == expected[int(r.request_id)], f"request {i}"
        assert 0 <= r.spec_accepted <= r.spec_proposed
        assert r.spec_proposed > 0
        assert 0.0 <= r.spec_acceptance <= 1.0
    assert stats.spec_tokens_proposed == sum(r.spec_proposed
                                             for r in results)
    assert stats.spec_acceptance_rate == pytest.approx(
        stats.spec_tokens_accepted / stats.spec_tokens_proposed)


def test_identity_extension_and_prefix_slice(params):
    """extend_with_identity_layers is logit-exact (zeroed residual adds
    contribute nothing) and slice_prefix_layers inverts it — the pair
    that builds the bench's perfectly-distilled draft."""
    ext_params, ext_cfg = gpt.extend_with_identity_layers(params, CFG, 2)
    assert ext_cfg.n_layers == 4
    x = jnp.asarray([PROMPTS[1]], jnp.int32)
    assert bool(jnp.array_equal(gpt.apply(ext_params, ext_cfg, x),
                                gpt.apply(params, CFG, x)))
    sliced, scfg = gpt.slice_prefix_layers(ext_params, ext_cfg, 2)
    assert scfg.n_layers == 2
    assert all(bool(jnp.array_equal(a, b)) for a, b in
               zip(jax.tree_util.tree_leaves(sliced),
                   jax.tree_util.tree_leaves(params)))
    with pytest.raises(ValueError):
        gpt.slice_prefix_layers(ext_params, ext_cfg, 0)
    with pytest.raises(ValueError):
        gpt.slice_prefix_layers(ext_params, ext_cfg, 5)


def test_speculative_identity_draft_accepts_everything(params):
    """Target = identity-extended core, draft = its layer-slice ⇒ both
    compute the same function, so every proposal verifies: acceptance
    is exactly 1.0 and output still matches the core's greedy tokens.
    The bench's ≥2x speedup lane is this setup at scale."""
    ext_params, ext_cfg = gpt.extend_with_identity_layers(params, CFG, 2)
    dparams, dcfg = gpt.slice_prefix_layers(ext_params, ext_cfg, 2)
    expected = naive_greedy(params, PROMPTS[0], 8)
    with InferenceEngine(ext_params, ext_cfg, buckets=BUCKETS, cache=CACHE,
                         speculative_k=3, draft_params=dparams,
                         draft_cfg=dcfg) as eng:
        r = eng.generate(PROMPTS[0], 8)
    assert r.tokens == expected
    assert r.spec_acceptance == 1.0


# -- chunked prefill ----------------------------------------------------------

def test_chunked_prefill_long_prompt_parity(params):
    """Chunking lifts the prompt-length admission limit: a prompt longer
    than the largest prefill bucket is served chunk-at-a-time, decoding
    bit-identically, while short co-resident requests keep decoding."""
    long_prompt = [i % 90 + 1 for i in range(20)]   # > max bucket 16
    with make_engine(params) as eng:
        with pytest.raises(ValueError, match="exceeds the largest"):
            eng.submit(long_prompt, 4)
    with pytest.raises(ValueError):
        make_engine(params, chunk_prefill_len=5)    # not a bucket size

    expected_long = naive_greedy(params, long_prompt, 6)
    expected_short = naive_greedy(params, PROMPTS[0], 6)
    with make_engine(params, chunk_prefill_len=8) as eng:
        h_long = eng.submit(long_prompt, 6)
        h_short = eng.submit(PROMPTS[0], 6)
        assert h_long.result(timeout=120.0).tokens == expected_long
        assert h_short.result(timeout=120.0).tokens == expected_short
        assert eng.stats().free_blocks == CACHE.num_blocks


# -- all three at once: budgeted warmup, no mid-traffic compiles --------------

def test_all_features_warmup_budget_and_parity():
    """With prefix sharing + speculation + chunking on, warmup compiles
    EXACTLY the extended program budget (base ladder, draft ladder, k+1
    verify per batch bucket, two block-copies) and traffic adds nothing.
    The jit cache probes are process-global (they key on the underlying
    function, which every engine shares), so the assertion is on the
    warmup DELTA — and the shapes here (vocab 101, 12-block pool,
    1-layer draft) are unique to this test, so the delta is exactly
    this engine's ladder."""
    cfg = gpt.GPTConfig(vocab_size=101, n_layers=2, d_model=32, n_heads=4,
                        d_ff=64, max_seq_len=48, remat=False,
                        attention_impl="mha")
    params = gpt.init(jax.random.PRNGKey(11), cfg)
    # a 1-layer draft: distinct param/pool shapes from the target, so
    # the draft ladder really is its own 9 programs (a same-shape draft
    # would alias the target's cache entries and land under budget)
    draft_cfg = dataclasses.replace(cfg, n_layers=1)
    draft = gpt.init(jax.random.PRNGKey(12), draft_cfg)
    cache = KVCacheConfig(num_blocks=12, block_size=8)
    buckets = BucketSpec.build(2, 16)   # small ladder: 16 programs warmed
    long_prompt = [i % 90 + 1 for i in range(20)]
    expected = naive_greedy(params, long_prompt, 8, cfg=cfg)
    with InferenceEngine(params, cfg, buckets=buckets, cache=cache,
                         prefix_cache=True, chunk_prefill_len=8,
                         speculative_k=3, draft_params=draft,
                         draft_cfg=draft_cfg) as eng:
        budget = eng.program_budget()
        assert budget == buckets.extended_budget(
            speculative=True, prefix_cache=True)
        before = eng.programs_compiled()
        compiled = eng.warmup()
        assert compiled - before == budget
        for _ in range(2):   # second pass hits the prefix cache
            assert eng.generate(long_prompt, 8).tokens == expected
        hs = [eng.submit(p, 4) for p in PROMPTS]
        for h in hs:
            h.result(timeout=120.0)
        assert eng.programs_compiled() == compiled
        assert eng.stats().prefix_hit_blocks > 0
        assert_pool_accounted(eng)


# -- abort accounting with sharing live ---------------------------------------

def test_abort_mid_decode_releases_blocks(params, draft_params):
    """Aborting a shared-prefix speculative request releases exactly the
    sequence's references: cached blocks stay resident (the cache still
    holds them), everything else returns to the free list."""
    with make_engine(params, prefix_cache=True, speculative_k=3,
                     draft_params=draft_params, draft_cfg=CFG,
                     iteration_floor_s=0.05) as eng:
        eng.generate(PROMPTS[1], 4)          # seed the prefix cache
        h = eng.submit(PROMPTS[1], 30)
        time.sleep(0.25)                     # let a few iterations run
        assert eng.abort(h)
        r = h.result(timeout=120.0)
        assert r.finish_reason == "aborted"
        assert len(r.tokens) < 30
        assert not eng.abort(h)              # already finished
        eng.wait_idle(timeout=60.0)
        assert_pool_accounted(eng)


# -- HTTP surface -------------------------------------------------------------

def test_http_exposes_speed_fields_and_metrics(params, draft_params):
    with make_engine(params, prefix_cache=True, speculative_k=3,
                     draft_params=draft_params, draft_cfg=CFG) as eng, \
            ServingHTTPServer(eng) as srv:
        generate_over_http(srv.url, PROMPTS[1], max_new_tokens=5)
        out = generate_over_http(srv.url, PROMPTS[1], max_new_tokens=5)
        lat = out["latency"]
        assert lat["prefix_hit_blocks"] == 2   # full block + exact tail
        assert lat["prefix_miss_blocks"] == 0
        assert lat["spec_proposed"] >= lat["spec_accepted"] >= 0
        assert lat["spec_acceptance"] is None or \
            0.0 <= lat["spec_acceptance"] <= 1.0

        with urllib.request.urlopen(f"{srv.url}/metrics",
                                    timeout=30) as resp:
            metrics = resp.read().decode()
    for name in ("prefix_cache_hit_blocks_total",
                 "prefix_cache_miss_blocks_total",
                 "spec_acceptance_rate",
                 "serving_spec_tokens_proposed_total",
                 "serving_spec_tokens_accepted_total"):
        assert name in metrics, name


# -- FLOPs accounting ---------------------------------------------------------

def test_speculative_flops_hand_checks():
    """d=4, f=8, L=2, V=16 (the suite's worked example): decode at
    context 10 costs 960, at 11 costs 992, so a k=1 verify call is
    1952 — the sum of the two consecutive decode steps it replaces."""
    class _Tiny:
        d_model, d_ff, n_layers, vocab_size = 4, 8, 2, 16

    verify = flops_mod.gpt_verify_flops(_Tiny, 10, 1)
    assert verify["total"] == 1952
    for k in (1, 3):
        assert flops_mod.gpt_verify_flops(_Tiny, 10, k)["total"] == sum(
            flops_mod.gpt_decode_flops_per_token(_Tiny, 10 + i)["total"]
            for i in range(k + 1))

    step = flops_mod.gpt_speculative_step_flops(_Tiny, _Tiny, 10, 3)
    assert step["total"] == step["draft"] + step["verify"]
    assert step["verify"] == flops_mod.gpt_verify_flops(_Tiny, 10, 3)["total"]
    assert step["draft"] == sum(
        flops_mod.gpt_decode_flops_per_token(_Tiny, 10 + i)["total"]
        for i in range(3))

    # prefix sharing: skipping s prefill tokens saves exactly s tokens
    # at full-sequence-length cost, and at least one token always pays
    # (the re-scored last prompt position)
    per_tok = sum(flops_mod.gpt_forward_flops_per_token(_Tiny, 10).values())
    full = flops_mod.gpt_generation_flops(_Tiny, 10, 4)
    shared = flops_mod.gpt_generation_flops(_Tiny, 10, 4, prefill_from=6)
    assert shared == pytest.approx(full - 6 * per_tok)
    assert flops_mod.gpt_generation_flops(_Tiny, 10, 4, prefill_from=10) \
        == flops_mod.gpt_generation_flops(_Tiny, 10, 4, prefill_from=9)


# -- config surface -----------------------------------------------------------

def test_speculative_config_roundtrip_and_validation():
    raw = {"prefix_cache": True, "chunk_prefill_len": 16,
           "speculative": {"enabled": True, "k": 3, "draft_layers": 2,
                           "draft_d_model": 64, "draft_n_heads": 2,
                           "draft_d_ff": 256}}
    scfg = ServingConfig.from_dict(raw)
    assert scfg.prefix_cache and scfg.chunk_prefill_len == 16
    assert scfg.speculative.enabled and scfg.speculative.k == 3
    assert scfg.speculative.draft_layers == 2

    with pytest.raises(ConfigError):
        SpeculativeConfig.from_dict({"k": 0})
    with pytest.raises(ConfigError):
        SpeculativeConfig.from_dict({"k": 17})
    with pytest.raises(ConfigError):
        SpeculativeConfig.from_dict({"draft_d_model": 10,
                                     "draft_n_heads": 4})
    with pytest.raises(ConfigError):
        ServingConfig.from_dict({"chunk_prefill_len": 5})  # not pow2

    good = {"name": "e", "entrypoint": "m:T",
            "searcher": {"name": "single", "metric": "loss",
                         "max_length": {"batches": 1}},
            "serving": dict(raw, max_batch=4)}
    assert schema.validate(good) == []
    bad = json.loads(json.dumps(good))
    bad["serving"]["speculative"]["draught"] = 1
    errors = schema.validate(bad)
    assert any("speculative.draught" in e and "unknown field" in e
               for e in errors)
