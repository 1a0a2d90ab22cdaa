"""Trinity (``afmoe``) on the serving path (models/afmoe.py, the two forms of
ops/window_attention.py, ops/moe.py:routed_experts, the blocks-and-a-ring
cache of serving/kv_cache.py:WindowSlotLayout, the engine's counts from the
device) against the plain reference ``benchmarks/reference/afmoe.py``, at a
small size on the CPU: hidden 64, 4 query heads over 2 KV heads of 16, a
window of 16 beside slices of 8 (a ring of 24 positions, six blocks of 4),
16 experts of width 32 of which experts 8..15 are held, 4 a token, five
layers in the published pattern (sliding + dense, sliding + experts, full +
experts, sliding + experts twice), seeded weights, every norm scale away
from 1 and a selection bias of size 0.1.
"""
import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import afmoe as reference
from determined_clone_tpu.models import afmoe
from determined_clone_tpu.ops import moe
from determined_clone_tpu.ops import window_attention as wa
from determined_clone_tpu.serving import (
    BucketSpec,
    InferenceEngine,
    KVCacheConfig,
)
from determined_clone_tpu.serving.kv_cache import (
    StateSlotLayout,
    WindowSlotLayout,
)
from determined_clone_tpu.telemetry import MetricsRegistry, Tracer

BLOCK = 4
# float32 everywhere, so that what is compared is the cache, the ring, the
# window's edges, the grouped heads, the online softmax and the routing,
# not rounding: the program then differs from the reference only in the
# order of float32 sums (measured 3e-6 on logits of size 3; no choice of
# experts has flipped on it). The mildest control, the reference's products
# in bfloat16, reads 2e-2.
TOLERANCE = 5e-5


def _config(dtype=jnp.float32, **kw):
    return dataclasses.replace(afmoe.AfmoeConfig.tiny(),
                               compute_dtype=dtype, param_dtype=dtype, **kw)


CFG = _config()


def _constants(cfg=CFG):
    return dict(experts_per_token=cfg.num_experts_per_tok,
                routed_scale=cfg.route_scale, first_expert=cfg.first_expert,
                rms_eps=cfg.rms_norm_eps, window=cfg.sliding_window,
                rope_theta=cfg.rope_theta, mup=cfg.mup_enabled)


@pytest.fixture(scope="module")
def params():
    """Seeded weights with every norm scale away from 1 and a selection
    bias large enough to change choices."""
    p = jax.jit(functools.partial(afmoe.init, cfg=CFG, bias_std=0.1))(
        jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    for kind in set(CFG.kinds):
        for leaves in p[kind].values():
            if "scale" in leaves:
                leaves["scale"] = 1 + 0.2 * jax.random.normal(
                    next(keys), leaves["scale"].shape)
    p["final_norm"]["scale"] = 1 + 0.2 * jax.random.normal(
        next(keys), p["final_norm"]["scale"].shape)
    return p


def _reference(params, tokens, **kw):
    """(logits [n, V], routed [L_sparse, n, k]) of the whole sequence,
    padded on the right to the toy's longest so that the reference is
    compiled once a precision (padding reaches no position on its left)."""
    n = len(tokens)
    padded = list(tokens) + [0] * (CFG.max_position_embeddings - n)
    logits, routed = reference.forward(
        params, padded, kinds=CFG.kinds, keep_choices=True, n_rows=n,
        **{**_constants(), **kw})
    return logits, routed[:, :n]


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, size=n).astype(np.int32)


class _Paged:
    """The jitted paged forward driven by hand: rows of one batch, each
    with its own blocks and slot (in another order than the rows'),
    prefilled in slices and then decoded a token at a time, the logits at
    every position and the device's counts of every call kept."""

    def __init__(self, cfg, totals, *, num_blocks=64):
        self.cfg = cfg
        cache = KVCacheConfig(num_blocks, BLOCK)
        self.layout = cfg.paged_model().cache_layout(cfg, cache)
        self.pools = afmoe.init_pools(cfg, cache, len(totals))
        self.tables = np.zeros((len(totals), self.layout.table_width),
                               np.int32)
        free = list(range(num_blocks - 1, 0, -1))  # block 0 is nobody's
        for i, total in enumerate(totals):
            need = self.layout.blocks_needed(total)
            blocks = [free.pop() for _ in range(need)][::-1]
            slot = num_blocks + len(totals) - 1 - i    # the last row's: 0
            self.layout.lay_table(self.tables[i], blocks + [slot])
        self.counts = []
        self.fwd = jax.jit(afmoe.forward_paged_logits, static_argnums=(1,))

    def call(self, params, tok, pos, msk):
        logits, *self.pools, counts, _ = self.fwd(
            params, self.cfg, jnp.asarray(tok), jnp.asarray(pos),
            jnp.asarray(msk), *self.pools, jnp.asarray(self.tables))
        self.counts.append(np.asarray(counts))
        return np.asarray(logits)

    def run(self, params, seqs, prompt_lens, width):
        """Every row's logits [len, V]: prompts in slices of ``width`` (a
        row's last padded to it), then one token a step, rows that have
        ended masked out."""
        n = len(seqs)
        out = [[] for _ in seqs]
        done = [0] * n
        while any(done[i] < prompt_lens[i] for i in range(n)):
            tok = np.zeros((n, width), np.int32)
            pos = np.zeros((n, width), np.int32)
            msk = np.zeros((n, width), bool)
            cnt = [min(width, prompt_lens[i] - done[i]) for i in range(n)]
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
            logits = self.call(params, tok, pos, np.array(live)[:, None])
            for i in range(n):
                if live[i]:
                    out[i].append(logits[i, 0])
                    done[i] += 1
        return [np.concatenate([o.reshape(-1, o.shape[-1]) for o in row])
                for row in out]


# -- the two forms of the attention, alone ------------------------------------

def _dense(q, k, v, lo, hi, group):
    """softmax(q . k / sqrt(d)) v over positions [lo, hi) of k, v [S, Hkv,
    d], query head h against KV head h // group: float64 on the host."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    out = np.zeros(q.shape)
    for h in range(q.shape[0]):
        s = k[lo:hi, h // group] @ q[h] / np.sqrt(q.shape[-1])
        w = np.exp(s - s.max())
        out[h] = w / w.sum() @ v[lo:hi, h // group]
    return out


def _cache_of(seed, S, W, n_blocks, Hkv, d, order):
    """K and V of ``S`` positions [S, Hkv, d] laid through a table of ``W``
    entries (a ring where ``W * BLOCK < S``: a later position over the one
    a ring before it) into pools of ``n_blocks`` blocks. A pool row is only
    ever finite, and a position outside ``[lo, hi)`` is masked by its
    weight: the rows of the sequence's blocks that hold no position of it
    (the rest of its last block, and the block after it, which a pass of
    two may take along) hold 100, every other block of the pool NaN."""
    rng = np.random.default_rng(seed)
    k, v = rng.normal(size=(2, S, Hkv, d)).astype(np.float32)
    table = np.asarray(order[:W], np.int32)
    pools = np.full((2, n_blocks, BLOCK, Hkv * d), np.nan, np.float32)
    pools[:, table[:-(-S // BLOCK) + 1]] = 100.0
    for p in range(S):
        at = table[p // BLOCK % W], p % BLOCK
        pools[0][at], pools[1][at] = k[p].reshape(-1), v[p].reshape(-1)
    return k, v, table, jnp.asarray(pools[0]), jnp.asarray(pools[1])


@pytest.mark.parametrize("lo,hi,W", [(0, 37, 10), (21, 37, 10), (0, 3, 10),
                                     (45, 61, 6), (60, 76, 6), (0, 0, 10)],
                         ids=["whole", "window", "inside-a-block",
                              "ring-wrapped-once", "ring-wrapped-thrice",
                              "padding"])
def test_decode_reads_the_blocks_between_first_and_last_and_no_others(
        lo, hi, W):
    """One query position over grouped heads through a table or a ring,
    two blocks a pass: the numbers of a dense softmax over positions ``[lo,
    hi)``. Every block the row does not own holds NaN, and so does every
    block of its table past the one after its last position: a pass that
    read a whole table, or another row's block, would carry it into the
    sums, since a position is masked by its weight."""
    S = max(hi, 1)
    k, v, table, k_pool, v_pool = _cache_of(
        3, S, W, 16, 2, 8, [5, 11, 2, 7, 13, 1, 9, 4, 14, 6])
    q = np.random.default_rng(4).normal(size=(6, 8)).astype(np.float32)
    got = np.asarray(wa.decode_rows(
        jnp.asarray(q)[None], k_pool, v_pool, jnp.asarray(table)[None],
        jnp.asarray([lo]), jnp.asarray([hi]), key_blocks=2))[0]
    if hi == 0:
        assert (got == 0).all()
        return
    assert np.abs(got - _dense(q, k, v, lo, hi, 3)).max() < 1e-5


@pytest.mark.parametrize("start,n,T,window,W", [
    (0, 8, 8, None, 12), (16, 5, 8, None, 12), (0, 8, 8, 6, 12),
    (24, 7, 8, 16, 6), (40, 8, 8, 16, 6), (8, 12, 16, 5, 12)],
    ids=["first-slice", "padded-later-slice", "window-inside-the-slice",
         "ring-at-its-wrap", "ring-wrapped", "two-query-blocks"])
def test_a_slice_attends_its_own_rows_and_the_window_before_them(
        start, n, T, window, W):
    """``n`` real queries of a bucket of ``T`` at positions ``start ..``,
    their own rows already cached, eight queries and two blocks a pass:
    each query's numbers are a dense softmax's over ``max(0, i - window +
    1) .. i`` (``0 .. i`` without a window); padding queries give zeros.
    NaN in every block that is not the row's."""
    S = start + n
    k, v, table, k_pool, v_pool = _cache_of(
        5, S, W, 16, 2, 8, [5, 11, 2, 7, 13, 1, 9, 4, 14, 6, 3, 12])
    q = np.random.default_rng(6).normal(size=(T, 6, 8)).astype(np.float32)
    pos = np.where(np.arange(T) < n, start + np.arange(T), 0)
    got = np.asarray(wa.slice_rows(
        jnp.asarray(q)[None], k_pool, v_pool, jnp.asarray(table)[None],
        jnp.asarray(pos)[None], jnp.asarray(np.arange(T) < n)[None],
        window=window, q_block=8, key_blocks=2))[0]
    assert (got[n:] == 0).all()
    for t in range(n):
        i = start + t
        lo = max(0, i - window + 1) if window else 0
        assert np.abs(got[t] - _dense(q[t], k, v, lo, i + 1, 3)).max() \
            < 1e-5, t


# -- the model through its cache against the reference -------------------------

@pytest.mark.parametrize("width,prompt_lens,totals", [
    (8, [10, 6], [30, 14]), (8, [40, 33], [44, 36]), (4, [20, 9], [60, 30]),
    (8, [8, 21], [52, 25])],
    ids=["prompts-inside-a-window", "prompts-past-window-and-slice",
         "decode-across-the-wrap-twice", "one-slice-then-decode"])
def test_slices_then_decode_through_the_cache_are_the_reference(
        params, width, prompt_lens, totals):
    """Two rows of one batch, prefilled in slices (a row's last padded to
    the bucket; the shorter row idle, all padding, while the longer one
    finishes) and decoded a token at a time: prompts shorter than the
    window of 16, for which a sliding layer is a full one; prompts longer
    than window + slice = the ring of 24, whose slices are written over the
    ring's oldest rows; decode steps that cross the ring's wrap at 24 and
    48. Logits at every position against the reference's full forward."""
    seqs = [_tokens(10 + i, n) for i, n in enumerate(totals)]
    paged = _Paged(CFG, totals)
    got = paged.run(params, seqs, prompt_lens, width)
    for seq, g in zip(seqs, got):
        want, _ = _reference(params, seq)
        assert g.shape == want.shape
        assert np.abs(g - want).max() < TOLERANCE
    # the device's counts: no more pairs than tokens x k x sparse layers
    assert all(0 <= c[1] <= c[0] for c in paged.counts)


@pytest.mark.parametrize("control", ["bf16", "fp8", "no_window",
                                     "rope_on_full", "no_gate"])
def test_each_control_fails_the_tolerance(params, control):
    """The reference in a lower precision, with the window taken away
    (sliding layers attending everything), with rotary put on the full
    layer, or without the output gate is not the reference: each moves a
    logit by far more than the tolerance (2e-2 at the least), over a
    sequence three windows long."""
    seq = _tokens(30, 48)
    want, _ = _reference(params, seq)
    got, _ = _reference(params, seq, precision=control)
    assert np.abs(got - want).max() > 100 * TOLERANCE
    if control == "no_window":   # a prompt inside one window cannot tell
        assert np.abs(got[:16] - want[:16]).max() < TOLERANCE


def test_rows_of_a_large_prefill_run_one_at_a_time(params, monkeypatch):
    """Over ``PREFILL_TOKENS_PER_PASS`` tokens a call's rows go through
    the layers one after another (``models/paged.py:run_rows``), the pools
    handed from row to row: the same logits, and every row's counts."""
    totals = [20, 16]
    seqs = [_tokens(40 + i, n) for i, n in enumerate(totals)]
    whole = _Paged(CFG, totals)
    want = whole.run(params, seqs, totals, 8)
    monkeypatch.setattr(afmoe, "PREFILL_TOKENS_PER_PASS", 8)
    by_row = _Paged(CFG, totals)
    got = by_row.run(params, seqs, totals, 8)
    for a, b in zip(got, want):
        assert np.abs(a - b).max() < 1e-5
    assert (np.sum(by_row.counts, axis=0)[0]
            == np.sum(whole.counts, axis=0)[0])


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer(
        params):
    """The test that ties the share to the model: the routed parts that
    the eight members of the group compute, each from ``routed_experts``
    with its own ``first_expert`` (0, 2, .., 14) and its own two experts'
    weights, plus the shared expert once, are the reference's ``F`` over
    all sixteen experts, and the layer is the reference's with the
    post-norm over that sum. Float32; 2e-5 of outputs of size 1 is the
    order of the sums."""
    lp = jax.tree.map(lambda w: w[0], params["sliding_sparse"])
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    whole = {name: {"kernel": 0.125 * jax.random.normal(
        key, (16, *lp[name]["kernel"].shape[1:]))}
        for name, key in zip(("experts_gate", "experts_up", "experts_down"),
                             keys)}
    x = jnp.asarray(np.random.default_rng(4).normal(size=(80, 64)),
                    jnp.float32)
    uncut = {**lp, **whole}
    want = reference.layer_ffn(uncut, x, experts=range(16), whole=False,
                               **_constants())
    m32 = afmoe._norm(CFG, lp["ln_pre_mlp"], x, jnp.float32)
    total, pairs = np.asarray(afmoe._swiglu(CFG, lp, m32, "shared")), 0
    member = jax.jit(functools.partial(
        moe.routed_experts, n_held=2, n_experts=16,
        k=CFG.num_experts_per_tok, scale=CFG.route_scale,
        compute_dtype=jnp.float32), static_argnames=("first_expert",))
    for first in range(0, 16, 2):
        share = {**lp, **{name: {"kernel": w["kernel"][first:first + 2]}
                          for name, w in whole.items()}}
        y, counts, _ = member(share, m32, first_expert=first)
        total, pairs = total + np.asarray(y), pairs + int(counts[0])
    assert pairs == 80 * 4                  # every pair fell to one member
    assert np.abs(total - want).max() < 2e-5
    layer = np.asarray(x + afmoe._norm(CFG, lp["ln_post_mlp"],
                                       jnp.asarray(total), jnp.float32))
    assert np.abs(layer - reference.layer_ffn(
        uncut, x, experts=range(16), **_constants())).max() < 2e-5
    # and the reference's own share is the member's: the program's layer
    # with experts 8..15 held
    mine = reference.layer_ffn(lp, x, experts=range(8, 16), **_constants())
    got, _, _ = afmoe._mlp(CFG, "sliding_sparse", lp, jnp.int32(0),
                           x[None], jnp.ones((1, 80), bool))
    assert np.abs(np.asarray(got)[0] - mine).max() < 2e-5


# -- the layout, the engine ------------------------------------------------------

@pytest.mark.parametrize("total", [1, 4, 17, 24, 100, 128])
def test_reservation_is_blocks_and_one_ring_and_a_window_of_rows_is_read(
        total):
    cache = KVCacheConfig(64, BLOCK)
    layout = afmoe.PAGED.cache_layout(CFG, cache)
    assert type(layout) is WindowSlotLayout
    assert isinstance(layout, StateSlotLayout)
    assert layout.kinds == ("kv", "state") and layout.state_slots == 1
    assert (layout.window, layout.slice_len, layout.ring) == (16, 8, 24)
    assert layout.table_width == 128 // BLOCK + 1
    # the full layer's blocks grow; the ring is one slot whatever the length
    assert layout.blocks_by_kind(total) == (-(-total // BLOCK), 1)
    assert layout.blocks_needed(total) == -(-total // BLOCK)
    assert layout.row_args == ("kv_rows", "window_rows")
    assert layout.attended_rows(total) == (total, min(total, 16))
    assert layout.step_rows([total, 3], 4) == (total + 3,
                                               min(total, 16) + 3)
    row = np.zeros(layout.table_width, np.int32)
    layout.lay_table(row, [5, 9, 64 + 3])
    assert list(row[:3]) == [5, 9, 0] and row[-1] == 3
    with pytest.raises(ValueError, match="whole cache blocks"):
        layout.check_prefill(8, 6)
    with pytest.raises(ValueError, match="exceeds the 8 positions"):
        layout.check_prefill(16, 8)
    layout.check_prefill(8, 8)
    with pytest.raises(ValueError, match="not whole cache blocks"):
        WindowSlotLayout(cache, 128, window=16, slice_len=6)


def test_pools_are_four_and_a_sequences_sliding_cache_stops_at_the_ring():
    cfg = _config(jnp.bfloat16)
    k, v, k_w, v_w = afmoe.init_pools(cfg, KVCacheConfig(10, BLOCK), 3)
    assert k.shape == v.shape == (1, 10, BLOCK, 32) and k.dtype == jnp.bfloat16
    assert k_w.shape == v_w.shape == (4, 3, 24, 32)
    assert cfg.kinds == ("sliding_dense", "sliding_sparse", "full_sparse",
                         "sliding_sparse", "sliding_sparse")
    assert cfg.runs() == [("sliding_dense", 0, 1, 0),
                          ("sliding_sparse", 0, 1, 1),
                          ("full_sparse", 0, 1, 0),
                          ("sliding_sparse", 1, 3, 2)]
    published = afmoe.AfmoeConfig()
    assert published.kinds.count("full_sparse") == 14 \
        and published.kinds.count("sliding_sparse") == 40 \
        and published.kinds[:6] == ("sliding_dense",) * 3 \
        + ("full_dense",) + ("sliding_dense",) * 2
    assert published.row_width == 1024 and published.ring == 6144
    # 16 sequences of 34816 positions: 3.9 GB window-aware, 11.4 uniform
    bytes_a_row = 2 * published.row_width * 2
    assert 16 * (34816 + 4 * 6144) * bytes_a_row == 3892314112
    assert 16 * 5 * 34816 * bytes_a_row == 11408506880
    with pytest.raises(ValueError, match="layer_types"):
        _config(layer_types=("sliding_attention",) * 4)
    with pytest.raises(ValueError, match="whole groups"):
        _config(num_key_value_heads=3)


def _engine(params, **kw):
    kw.setdefault("buckets", BucketSpec.build(2, 8, min_prefill_len=4))
    kw.setdefault("cache", KVCacheConfig(66, BLOCK))
    kw.setdefault("chunk_prefill_len", 8)
    return InferenceEngine(params, CFG, **kw)


def test_engine_serves_the_reference_tokens_and_reads_the_devices_counts(
        params):
    """Through ``InferenceEngine.submit``: chunked prefill in slices of 8
    between decode steps, two rows a batch. Five requests over two batch
    rows, so blocks and slots are used again by a later request (whose
    ring holds the earlier one's rows, none of which it may attend): a
    prompt inside a window, prompts past the ring, decodes that cross the
    wrap. Every served token is the reference's first **by its logits**;
    no block and no slot is outstanding at the end. The decode step's
    spans carry the rows from the lengths (``window_rows`` at most a window
    a row), ``decode_commit`` and ``serving_prefill`` what only the device
    knew, a prefill's span its real tokens and the key rows its queries
    had to attend; a result carries the experts each of its positions was
    routed to."""
    registry = MetricsRegistry()
    tracer = Tracer(enabled=True)
    telemetry = type("T", (), {"registry": registry, "tracer": tracer})()
    sizes = [(50, 12), (10, 20), (27, 30), (70, 6), (20, 6)]
    prompts = [_tokens(20 + i, n).tolist() for i, (n, _) in enumerate(sizes)]
    with _engine(params, telemetry=telemetry) as eng:
        before = eng.programs_compiled()
        handles = [eng.submit(p, max_new_tokens=m)
                   for p, (_, m) in zip(prompts, sizes)]
        results = [h.result(timeout=600) for h in handles]
        assert eng.kv_outstanding() == 0
        eng.assert_kv_balanced(0)
        assert eng.programs_compiled() - before <= eng.program_budget()
        row_args = eng._layout.row_args
    for p, (_, m), r in zip(prompts, sizes, results):
        assert r.finish_reason == "length" and len(r.tokens) == m
        logits, routed = _reference(params, p + r.tokens)
        at = logits[len(p) - 1:-1]
        assert (at.max(axis=-1) - at[np.arange(m), r.tokens]
                ).max() < TOLERANCE
        n = len(p) + m - 1
        assert r.token_records.shape == (n, 4 * CFG.num_experts_per_tok)
        mine = r.token_records.reshape(n, 4, -1).transpose(1, 0, 2)
        assert (np.sort(mine, -1) == np.sort(routed[:, :n], -1)).all()
    events = tracer.events()
    steps = [e["args"] for e in events
             if e.get("name") == "serving_decode_step"]
    assert steps and all(
        a["rows"] <= a["window_rows"] <= 16 * a["rows"]
        and a["window_rows"] <= a["kv_rows"] for a in steps)
    assert any(a["window_rows"] < a["kv_rows"] for a in steps)
    for name, arg in zip(afmoe.PAGED.row_counters, row_args):
        assert registry.counter(name).value == sum(a[arg] for a in steps)
    commits = [e["args"] for e in events if e.get("name") == "decode_commit"]
    prefills = [e["args"] for e in events
                if e.get("name") == "serving_prefill"]
    assert len(commits) == len(steps)
    for a in commits + prefills:
        assert 0 <= a["expert_hits"] <= 4 * 8
        assert a["expert_hits"] <= a["expert_pairs"]
    assert all(a["expert_pairs"] <= a["rows"] * 4 * 4 for a in commits)
    assert sum(a["tokens"] for a in prefills) == sum(n for n, _ in sizes)
    # the key rows a prompt's queries attend: position i sees i + 1 of the
    # full layer and min(i + 1, 16) of a sliding one
    assert sum(a["full_key_rows"] for a in prefills) \
        == sum(n * (n + 1) // 2 for n, _ in sizes)
    assert sum(a["window_key_rows"] for a in prefills) == sum(
        sum(min(i + 1, 16) for i in range(n)) for n, _ in sizes)
    for name in afmoe.PAGED.step_counters:
        assert registry.counter(f"serving_{name}_total").value \
            == sum(a[name] for a in commits + prefills)


def test_engine_refuses_by_name_what_this_cache_cannot_serve(params):
    with pytest.raises(ValueError, match="afmoe.*prefix_cache"):
        _engine(params, prefix_cache=True)
    with pytest.raises(ValueError, match="afmoe.*speculative"):
        _engine(params, speculative_k=2, draft_params=params, draft_cfg=CFG)
    with pytest.raises(ValueError, match="exceeds the 8 positions"):
        _engine(params, buckets=BucketSpec.build(2, 16, min_prefill_len=8),
                chunk_prefill_len=16)
    assert afmoe.PAGED.unsupported == ("prefix_cache", "kv_store",
                                       "speculative")
    assert afmoe.PAGED.pool_names == ("k_pool", "v_pool", "k_window_pool",
                                      "v_window_pool")
    assert afmoe.PAGED.step_counters == ("expert_pairs", "expert_hits")
    assert afmoe.PAGED.token_records


def test_serving_params_are_bf16_matrices_and_fp32_vectors_and_router(
        params):
    served = afmoe.serving_params(params, _config(jnp.bfloat16))
    for path, leaf in jax.tree_util.tree_leaves_with_path(served):
        name = jax.tree_util.keystr(path)
        matrix = ("kernel" in name or "table" in name) \
            and "router" not in name
        assert leaf.dtype == (jnp.bfloat16 if matrix else jnp.float32), name
    again = afmoe.serving_params(served, _config(jnp.bfloat16))
    assert all(a is b for a, b in zip(jax.tree.leaves(served),
                                      jax.tree.leaves(again)))
    # the embedding is drawn so that the muP multiplier hands the first
    # layer a stream of unit size
    table = np.asarray(params["embed"]["table"])
    assert abs(table.std() * CFG.hidden_size ** 0.5 - 1) < 0.05


# -- what this family shares with the served cells stays what they compiled -----

def _digest(fn, *args):
    return hashlib.sha256(str(jax.make_jaxpr(fn)(*args)).encode()
                          ).hexdigest()[:16]


def test_gpt_decode_step_and_the_expert_layer_keep_their_jaxprs():
    """The two GPT serve cells' decode program (``models/gpt.py`` through
    the paged kernel) and ``routed_experts`` as the two other expert cells
    call it are, at a small size, the jaxprs they were before this family
    came (digests taken on the parent commit, PR 46): grouped KV heads, the
    window and the ring live in ``ops/window_attention.py`` and touch
    neither. A PR that changes one of them on purpose changes its digest
    here, and measures those cells."""
    from determined_clone_tpu.models import gpt

    cfg = dataclasses.replace(gpt.GPTConfig.tiny(), attention_impl="flash")
    cache = KVCacheConfig(8, 16)
    params = jax.eval_shape(
        lambda k: gpt.serving_params(gpt.init(k, cfg), cfg),
        jax.random.PRNGKey(0))
    pools = jax.eval_shape(lambda: gpt.PAGED.init_pools(cfg, cache, 2))
    width = gpt.PAGED.cache_layout(cfg, cache).table_width

    def decode(params, tokens, positions, mask, last, k, v, tables):
        return gpt.forward_paged(params, cfg, tokens, positions, mask, last,
                                 k, v, tables)

    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    assert _digest(decode, params, i32((2, 1)), i32((2, 1)),
                   jax.ShapeDtypeStruct((2, 1), jnp.bool_), i32((2,)),
                   *pools, i32((2, width))) == GPT_DECODE_DIGEST

    lp = jax.eval_shape(lambda: {
        "router": {"kernel": jnp.zeros((64, 16)), "bias": jnp.zeros((16,))},
        **{f"experts_{n}": {"kernel": jnp.zeros(s, jnp.bfloat16)}
           for n, s in (("gate", (8, 64, 32)), ("up", (8, 64, 32)),
                        ("down", (8, 32, 64)))}})

    def routed(lp, h, mask):
        return moe.routed_experts(lp, h, first_expert=8, n_held=8,
                                  n_experts=16, k=4, scale=2.5,
                                  token_mask=mask, first_row=0)

    assert _digest(routed, lp, jax.ShapeDtypeStruct((24, 64), jnp.float32),
                   jax.ShapeDtypeStruct((24,), jnp.bool_)) \
        == ROUTED_EXPERTS_DIGEST


GPT_DECODE_DIGEST = "d6c241a167327805"
ROUTED_EXPERTS_DIGEST = "b760c7b5db1325ad"
