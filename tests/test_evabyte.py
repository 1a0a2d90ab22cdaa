"""EvaByte on the serving path (models/evabyte.py, ops/attention.py's EVA
functions, the two-kind cache of serving/kv_cache.py and the engine's model
interface) against the plain reference ``benchmarks/reference/evabyte.py``,
at a small size on the CPU: window 64, chunk 8, 2 layers, 4 heads of 16,
8 prediction heads.
"""
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import evabyte as reference
from determined_clone_tpu.models import evabyte, gpt
from determined_clone_tpu.serving import (
    BucketSpec,
    InferenceEngine,
    KVCacheConfig,
    init_kv_pools,
)
from determined_clone_tpu.serving.kv_cache import (
    CacheLayout,
    WindowSummaryLayout,
)

WINDOW, CHUNK, HEADS = 64, 8, 4
# float32 everywhere, so that what is compared is the cache, the masks and
# the pooling, not rounding: the program then differs from the reference
# only in the order of float32 sums (measured 4e-7 on logits of size 0.5).
# The same program computing in bfloat16 reads 2e-3 and more, so it fails.
TOLERANCE = 2e-5


def _config(dtype):
    return dataclasses.replace(evabyte.EvaByteConfig.tiny(),
                               compute_dtype=dtype, param_dtype=dtype)


@pytest.fixture(scope="module")
def params():
    """Seeded weights with every learned vector away from its trivial
    initial value (norm scales 0, phi and mu tiny), matrices 8 x init_std so
    that attention is far from uniform."""
    cfg = _config(jnp.float32)
    p = jax.jit(functools.partial(evabyte.init, cfg=cfg))(
        jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 8))
    blocks = p["blocks"]
    for name in ("ln1", "ln2"):
        blocks[name]["scale"] = 0.1 * jax.random.normal(
            next(keys), blocks[name]["scale"].shape)
    blocks["eva"]["phi"] = jax.random.normal(next(keys),
                                             blocks["eva"]["phi"].shape)
    blocks["eva"]["mu"] = 0.5 * jax.random.normal(next(keys),
                                                  blocks["eva"]["mu"].shape)
    for name in ("attn_q", "attn_k", "attn_v", "attn_out", "mlp_gate",
                 "mlp_up", "mlp_down"):
        blocks[name]["kernel"] = 8 * blocks[name]["kernel"]
    p["final_norm"]["scale"] = 0.1 * jax.random.normal(
        next(keys), p["final_norm"]["scale"].shape)
    return p


def _reference(params, tokens, **kw):
    return np.asarray(reference.all_head_logits(
        params, list(tokens), n_heads=HEADS, window=WINDOW, chunk=CHUNK,
        **kw))


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(0, 320, size=n).astype(
        np.int32)


class _Paged:
    """The jitted paged forward driven by hand: rows of one batch, each
    with its own blocks, prefilled in slices and then decoded a token at a
    time, every head's logits kept."""

    def __init__(self, cfg, n_rows, totals, *, num_blocks=96):
        self.cfg = cfg
        cache = KVCacheConfig(num_blocks, CHUNK)
        self.layout = cfg.paged_model().cache_layout(cfg, cache)
        self.pools = init_kv_pools(cfg, cache)
        self.tables = np.zeros((n_rows, self.layout.table_width), np.int32)
        nxt = 1  # block 0 is nobody's: padding entries point at it
        for i, total in enumerate(totals):
            need = self.layout.blocks_needed(total)
            self.layout.lay_table(self.tables[i],
                                  list(range(nxt, nxt + need)))
            nxt += need
        assert nxt <= num_blocks
        self.fwd = jax.jit(evabyte.forward_paged_logits, static_argnums=(1,))

    def call(self, params, tok, pos, msk):
        logits, *self.pools = self.fwd(
            params, self.cfg, jnp.asarray(tok), jnp.asarray(pos),
            jnp.asarray(msk), *self.pools, jnp.asarray(self.tables))
        return np.asarray(logits)

    def run(self, params, seqs, prompt_lens, slice_len):
        """Every row's logits [len, P, V]: prompts in slices of
        ``slice_len`` (padded to it), then one token a step, rows that
        have ended masked out."""
        n = len(seqs)
        out = [[] for _ in seqs]
        done = [0] * n
        while any(done[i] < prompt_lens[i] for i in range(n)):
            tok = np.zeros((n, slice_len), np.int32)
            pos = np.zeros((n, slice_len), np.int32)
            msk = np.zeros((n, slice_len), bool)
            cnt = [min(slice_len, prompt_lens[i] - done[i]) for i in range(n)]
            for i in range(n):
                tok[i, :cnt[i]] = seqs[i][done[i]:done[i] + cnt[i]]
                pos[i, :cnt[i]] = np.arange(done[i], done[i] + cnt[i])
                msk[i, :cnt[i]] = True
            logits = self.call(params, tok, pos, msk)
            for i in range(n):
                out[i].append(logits[i, :cnt[i]])
                done[i] += cnt[i]
        while any(done[i] < len(seqs[i]) for i in range(n)):
            live = [done[i] < len(seqs[i]) for i in range(n)]
            tok = np.array([[seqs[i][done[i]] if live[i] else 0]
                            for i in range(n)], np.int32)
            pos = np.array([[done[i] if live[i] else 0] for i in range(n)],
                           np.int32)
            logits = self.call(params, tok, pos,
                               np.array(live)[:, None])
            for i in range(n):
                if live[i]:
                    out[i].append(logits[i])
                    done[i] += 1
        return [np.concatenate(o) for o in out]


def test_uncached_forward_is_the_reference(params):
    tokens = _tokens(0, 200)
    got = np.asarray(jax.jit(evabyte.apply, static_argnums=1)(
        params, _config(jnp.float32), jnp.asarray(tokens[None])))[0]
    want = _reference(params, tokens)
    assert got.shape == want.shape == (200, 8, 320)
    assert np.abs(got - want).max() < TOLERANCE


@pytest.mark.parametrize("num_blocks", [96, 36],
                         ids=["roomy-pool", "pool-of-the-batchs-tables"])
def test_slices_then_decode_across_window_ends_is_the_reference(params,
                                                                num_blocks):
    """Three rows of one batch, each in another window at every step:
    prompts of 40, 100 and 150 (slices of 32, the last partial), decoded to
    88, 168 and 200 positions, so every row crosses at least one window end
    while decoding and two in all. All 8 heads, every position. A decode
    step reads the pool through the table in the kernel
    (``ops/eva_paged_attention.py``, interpreted here), whether the pool is
    roomy or just the batch's tables (36 blocks = 3 rows x 12 entries,
    every block somebody's neighbour)."""
    cfg = _config(jnp.float32)
    prompt_lens, totals = [40, 100, 150], [88, 168, 200]
    seqs = [_tokens(10 + i, n) for i, n in enumerate(totals)]
    paged = _Paged(cfg, 3, totals, num_blocks=num_blocks)
    got = paged.run(params, seqs, prompt_lens, slice_len=32)
    for seq, g in zip(seqs, got):
        want = _reference(params, seq)
        assert g.shape == want.shape
        assert np.abs(g - want).max() < TOLERANCE


def test_a_remainder_shorter_than_a_chunk_is_written_row_by_row(params):
    """A prefill bucket under the chunk (the default ladder starts at 8, the
    published chunk is 16): a prompt's last 3 tokens in a call of 4, which
    are single rows, not a whole block, and complete no chunk."""
    cfg = _config(jnp.float32)
    seq = _tokens(6, 80)
    paged = _Paged(cfg, 1, [80])
    got = paged.run(params, [seq[:64]], [64], slice_len=32)
    tok = np.zeros((1, 4), np.int32)
    tok[0, :3] = seq[64:67]
    pos = np.array([[64, 65, 66, 0]], np.int32)
    got.append(paged.call(params, tok, pos,
                          np.array([[True, True, True, False]]))[0, :3])
    got += [paged.call(params, seq[None, t:t + 1], np.array([[t]], np.int32),
                       np.ones((1, 1), bool))[0] for t in range(67, 80)]
    assert np.abs(np.concatenate(got) - _reference(params, seq)).max() \
        < TOLERANCE


def test_bf16_in_place_of_fp32_fails_the_tolerance(params):
    cfg = _config(jnp.bfloat16)
    low = jax.tree.map(lambda x: x.astype(jnp.bfloat16)
                       if x.ndim == 3 and x.shape[-1] >= 64
                       or x.shape == (320, 64) else x, params)
    seq = _tokens(3, 96)
    got = _Paged(cfg, 1, [96]).run(low, [seq], [50], slice_len=32)[0]
    assert np.abs(got - _reference(params, seq)).max() > 10 * TOLERANCE


def test_summaries_are_seen_and_only_once_their_window_has_ended(params):
    """Position 63 (the last of window 0) and position 64 (the first of
    window 1) see different things of the same chunks: nothing, then all
    eight summaries. Checked by changing a token of chunk 0 and of the
    window-only reference."""
    cfg = _config(jnp.float32)
    seq = _tokens(4, 80)
    other = seq.copy()
    other[3] = (other[3] + 1) % 320
    a, b = (_Paged(cfg, 1, [80]).run(params, [s], [80], slice_len=16)[0]
            for s in (seq, other))
    # inside window 0 position 3 is an exact row: seen by all after it
    assert np.abs(a[40] - b[40]).max() > 1e-3
    # from window 1 it is seen only through chunk 0's summary
    assert np.abs(a[64:] - b[64:]).max() > 1e-4
    window_only = _reference(params, seq, summaries=False)
    assert np.abs(a[:64] - window_only[:64]).max() < TOLERANCE
    assert np.abs(a[64:] - window_only[64:]).max() > 1e-2
    # a summary written for chunk 8 (positions 64..71, complete at 71) is
    # not seen inside its own window: positions 72.. equal a run in which
    # the summary row was never written
    paged = _Paged(cfg, 1, [136])  # reserves window 1's summaries too
    paged.run(params, [seq[:72]], [72], slice_len=8)
    block = paged.tables[0, WINDOW // CHUNK + 8 // CHUNK]   # chunk 8's
    assert np.abs(np.asarray(paged.pools[0])[:, block, 8 % CHUNK]).max() > 0
    paged.pools = [p.at[:, block, 8 % CHUNK].set(0) for p in paged.pools]
    rest = np.concatenate([
        paged.call(params, seq[None, t:t + 1], np.array([[t]], np.int32),
                   np.ones((1, 1), bool))[0] for t in range(72, 80)])
    assert np.abs(rest - a[72:]).max() < TOLERANCE


@pytest.mark.parametrize("total", [1, 8, 63, 64, 65, 127, 128, 129, 200])
def test_reservation_is_the_layouts_on_both_sides_of_a_window_end(total):
    layout = WindowSummaryLayout(KVCacheConfig(64, CHUNK), 256,
                                 window=WINDOW, chunk=CHUNK)
    ring, summaries = layout.blocks_by_kind(total)
    assert ring == min(-(-total // CHUNK), WINDOW // CHUNK)
    # one summary row per chunk of every finished window, in whole blocks
    assert summaries == -(-(total // WINDOW) * (WINDOW // CHUNK) // CHUNK)
    assert layout.blocks_needed(total) == ring + summaries
    row = np.zeros(layout.table_width, np.int32)
    blocks = list(range(100, 100 + ring + summaries))
    layout.lay_table(row, blocks)
    assert list(row[:ring]) == blocks[:ring]
    assert list(row[8:8 + summaries]) == blocks[ring:]
    assert (row[8 + summaries:] == -1).all()
    # rows attended by the query at the last position: window then summary
    w, s = layout.attended_rows(total)
    assert w == (total - 1) % WINDOW + 1
    assert s == (total - 1) // WINDOW * (WINDOW // CHUNK)
    # rows held grow as window + T / chunk, not T
    assert layout.blocks_needed(total) * CHUNK <= WINDOW + total // CHUNK + 2 * CHUNK


def _engine(cfg, params, **kw):
    kw.setdefault("buckets", BucketSpec.build(4, 32))
    kw.setdefault("cache", KVCacheConfig(64, CHUNK))
    kw.setdefault("chunk_prefill_len", 32)
    return InferenceEngine(params, cfg, **kw)


@pytest.mark.parametrize("num_blocks", [64, 48],
                         ids=["roomy-pool", "pool-of-the-batchs-tables"])
def test_engine_serves_the_reference_tokens_and_leaks_no_block(params,
                                                               num_blocks):
    """Through ``submit``: scheduler, allocator, buckets and chunked
    prefill as the GPT cells use them. Greedy bytes are the reference's
    argmax of head 0 wherever its margin is wider than the tolerance. One
    request is shorter than a window (a ring with unreserved entries), and
    the decode batches of 1-4 rows hold padding rows (length 0 to the
    kernel) and, with a pool of 48 blocks (4 rows x 12 entries), block 0's
    owner, whose block every -1 entry names."""
    cfg = _config(jnp.float32)
    prompts = [_tokens(20 + i, n).tolist() for i, n in
               enumerate([40, 100, 70, 130, 9, 5])]
    new = [40, 30, 60, 20, 70, 20]
    with _engine(cfg, params, cache=KVCacheConfig(num_blocks, CHUNK)) as eng:
        layout = eng._layout
        before = eng.programs_compiled()  # the jit is shared in a process
        handles = [eng.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, new)]
        results = [h.result(timeout=300) for h in handles]
        assert eng.programs_compiled() - before <= eng.program_budget()
        eng.assert_kv_balanced(0)
        assert eng.kv_outstanding() == 0
        reg = eng.registry
        rows = [reg.counter("serving_eva_window_rows_total").value,
                reg.counter("serving_eva_summary_rows_total").value]
        in_use = [reg.gauge("serving_kv_blocks_in_use",
                            labels={"kind": k}).value
                  for k in ("window", "summary")]
    assert in_use == [0, 0]
    want_rows = [0, 0]
    for p, n, r in zip(prompts, new, results):
        assert r.finish_reason == "length" and len(r.tokens) == n
        seq = p + r.tokens
        pad = -len(seq) % CHUNK
        logits = _reference(params, seq + [0] * pad, heads=1)[:, 0]
        at = logits[len(p) - 1:len(seq) - 1]
        best = at.max(axis=-1)
        served = at[np.arange(n), r.tokens]
        assert (best - served).max() < TOLERANCE
        for length in range(len(p) + 1, len(seq)):  # decode steps only
            w, s = layout.attended_rows(length)
            want_rows[0] += w
            want_rows[1] += s
    assert rows == want_rows and rows[1] > 0


def test_admission_reserves_both_kinds_and_gauges_them(params):
    cfg = _config(jnp.float32)
    with _engine(cfg, params) as eng:
        h = eng.submit(_tokens(1, 100).tolist(), max_new_tokens=60)
        total = 160
        want = eng._layout.blocks_by_kind(total)
        assert want == (8, 2)
        waited = time.monotonic() + 60
        while eng.kv_outstanding() == 0 and time.monotonic() < waited:
            time.sleep(0.005)
        assert eng.kv_outstanding() == sum(want)
        held = [eng.registry.gauge("serving_kv_blocks_in_use",
                                   labels={"kind": k}).value
                for k in ("window", "summary")]
        assert held == list(want)
        h.result(timeout=300)
        eng.assert_kv_balanced(0)


@pytest.mark.parametrize("feature", [
    {"prefix_cache": True},
    {"prefix_cache": True, "kv_store": object()},
    {"speculative_k": 2, "draft_params": {}, "draft_cfg": object()},
])
def test_features_the_two_kind_cache_cannot_serve_are_refused(params,
                                                              feature):
    with pytest.raises(ValueError, match="evabyte family's cache cannot "
                                         "serve"):
        _engine(_config(jnp.float32), params, **feature)


@pytest.mark.parametrize("chunk_prefill_len", [8, 16, 32])
def test_slice_lengths_that_divide_the_window_are_taken(params,
                                                        chunk_prefill_len):
    with _engine(_config(jnp.float32), params,
                 chunk_prefill_len=chunk_prefill_len):
        pass


def test_slices_that_could_straddle_a_window_are_refused(params):
    cfg = _config(jnp.float32)
    with pytest.raises(ValueError, match="exceeds the attention window"):
        _engine(cfg, params, buckets=BucketSpec.build(4, 128),
                chunk_prefill_len=0)
    layout = cfg.paged_model().cache_layout(cfg, KVCacheConfig(8, CHUNK))
    with pytest.raises(ValueError, match="must divide the attention window"):
        layout.check_prefill(64, 48)
    with pytest.raises(ValueError, match="must be the model's chunk"):
        cfg.paged_model().cache_layout(cfg, KVCacheConfig(8, 16))


def test_hot_swap_across_families_is_refused(params):
    cfg = _config(jnp.float32)
    other = gpt.init(jax.random.PRNGKey(0), gpt.GPTConfig.tiny())
    with _engine(cfg, params) as eng:
        with pytest.raises(ValueError, match="hot_swap across model "
                                             "families"):
            eng.hot_swap(other)
        eng.hot_swap(jax.tree.map(lambda x: x, params))  # its own: taken


def test_the_engine_serves_the_very_buffers_it_was_given(params):
    """EvaByte's paged forward reads every leaf in the type it is held in,
    so its serving form is the tree itself: nothing is cast or copied, by
    the family or by the engine, at construction or at a swap."""
    cfg = _config(jnp.float32)

    def given(tree):
        return all(a is b for a, b in zip(jax.tree.leaves(tree),
                                          jax.tree.leaves(params)))

    assert given(cfg.paged_model().serving_params(params, cfg))
    with _engine(cfg, params) as eng:
        assert given(eng._params)
        eng.hot_swap(params)
        assert given(eng._pending_params or eng._params)


def test_gpt_through_the_interface_is_token_identical_to_uncached():
    """The first implementation of the interface, unchanged in arithmetic:
    greedy decode through the engine equals re-running ``gpt.apply`` on the
    growing sequence, and the family's layout is the uniform one."""
    cfg = gpt.GPTConfig.tiny()
    p = gpt.init(jax.random.PRNGKey(0), cfg)
    model = cfg.paged_model()
    assert model is gpt.PAGED and model.unsupported == ()
    layout = model.cache_layout(cfg, KVCacheConfig(16, 16))
    assert type(layout) is CacheLayout and layout.kinds == ("kv",)
    assert layout.table_width == 8 and layout.blocks_by_kind(33) == (3,)
    prompt = _tokens(5, 12) % cfg.vocab_size
    with InferenceEngine(p, cfg) as eng:
        assert eng._g_kind_blocks == []  # one kind: no gauge by kind
        assert tuple(c.name for c in eng._c_rows) == model.row_counters \
            == ("serving_kv_rows_attended_total",
                "serving_kv_rows_tabled_total")
        got = eng.generate(prompt.tolist(), max_new_tokens=12).tokens
    # one program for every length: the growing sequence padded on the
    # right, which no causal position on the left can see
    apply = jax.jit(gpt.apply, static_argnums=1)
    seq = prompt.tolist()
    for _ in range(12):
        padded = seq + [0] * (len(prompt) + 12 - len(seq))
        logits = apply(p, cfg, jnp.asarray([padded]))
        seq.append(int(jnp.argmax(logits[0, len(seq) - 1])))
    assert got == seq[len(prompt):]
