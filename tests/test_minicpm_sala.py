"""MiniCPM-SALA on the serving path (models/minicpm_sala.py, the two ops
ops/lightning_attention.py and ops/sparse_attention.py, the cache that
differs by layer kind in serving/kv_cache.py and the engine's pools as the
family's own) against the plain reference
``benchmarks/reference/minicpm_sala.py``, at a small size on the CPU: the
published kernel 32, stride 16 and block 64, a small top-k 4, window 64 and
dense_len 256, 4 query heads of 16 over 2 KV groups, layers sparse,
lightning, lightning, sparse.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import minicpm_sala as reference
from determined_clone_tpu.models import minicpm_sala as sala
from determined_clone_tpu.ops import lightning_attention as lightning
from determined_clone_tpu.ops import sparse_attention as sparse
from determined_clone_tpu.serving import (
    BucketSpec,
    InferenceEngine,
    KVCacheConfig,
)
from determined_clone_tpu.serving.kv_cache import (
    BlockAllocator,
    SparseStateLayout,
)
from determined_clone_tpu.telemetry import MetricsRegistry, Tracer

BLOCK = 64
# float32 everywhere, so that what is compared is the cache, the masks, the
# chunked recurrence and the selection, not rounding: the program then
# differs from the reference only in the order of float32 sums (measured
# 3e-7 on logits of size 0.8; a top-k choice has not flipped on it). The
# same program computing in bfloat16 reads 1e-2 and more.
TOLERANCE = 2e-5


def _config(dtype=jnp.float32, **kw):
    return dataclasses.replace(sala.MiniCPMSALAConfig.tiny(),
                               compute_dtype=dtype, param_dtype=dtype, **kw)


CFG = _config()
SP = CFG.sparse


@pytest.fixture(scope="module")
def params():
    """Seeded weights with every learned vector away from its initial
    value (norm scales 1) and a decay table that is not the convention's,
    so that the table the op is handed is the one that counts."""
    p = jax.jit(functools.partial(sala.init, cfg=CFG))(jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 16))
    for stack in (p["sparse"], p["lightning"]):
        for name in ("ln1", "ln2", "q_norm", "k_norm"):
            stack[name]["scale"] = 1 + 0.2 * jax.random.normal(
                next(keys), stack[name]["scale"].shape)
    p["lightning"]["out_norm"]["scale"] = 1 + 0.2 * jax.random.normal(
        next(keys), p["lightning"]["out_norm"]["scale"].shape)
    p["lightning"]["decay"] = jax.random.uniform(
        next(keys), p["lightning"]["decay"].shape, minval=0.5, maxval=1.0)
    p["final_norm"]["scale"] = 1 + 0.2 * jax.random.normal(
        next(keys), p["final_norm"]["scale"].shape)
    return p


def _reference(params, tokens, **kw):
    """(logits, chosen) of the whole sequence, padded to whole blocks."""
    n = len(tokens)
    padded = list(tokens) + [0] * (-n % BLOCK)
    logits, chosen = reference.forward(
        params, padded, n_heads=CFG.n_heads, mixers=CFG.mixer_types,
        dim_model_base=CFG.dim_model_base,
        published_layers=CFG.n_published_layers, topk=SP.topk,
        window=SP.window, dense_len=SP.dense_len, n_rows=n, **kw)
    return logits, chosen


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, size=n).astype(np.int32)


class _Paged:
    """The jitted paged forward driven by hand: rows of one batch, each
    with its own blocks and state slot, prefilled in slices and then
    decoded a token at a time, the logits at every position kept."""

    def __init__(self, cfg, totals, *, num_blocks=40):
        self.cfg = cfg
        cache = KVCacheConfig(num_blocks, BLOCK)
        self.layout = cfg.paged_model().cache_layout(cfg, cache)
        self.pools = sala.init_pools(cfg, cache, len(totals))
        self.tables = np.zeros((len(totals), self.layout.table_width),
                               np.int32)
        nxt = 1  # block 0 is nobody's: padding entries point at it
        for i, total in enumerate(totals):
            need = self.layout.blocks_needed(total)
            # slots in another order than the rows, ids past the blocks'
            self.layout.lay_table(
                self.tables[i], list(range(nxt, nxt + need))
                + [num_blocks + len(totals) - 1 - i])
            nxt += need
        assert nxt <= num_blocks
        self.fwd = jax.jit(sala.forward_paged_logits, static_argnums=(1,))

    def call(self, params, tok, pos, msk):
        logits, *self.pools = self.fwd(
            params, self.cfg, jnp.asarray(tok), jnp.asarray(pos),
            jnp.asarray(msk), *self.pools, jnp.asarray(self.tables))
        return np.asarray(logits)

    def run(self, params, seqs, prompt_lens, slice_len):
        """Every row's logits [len, V]: prompts in slices of ``slice_len``
        (the last padded to it), then one token a step, rows that have
        ended masked out."""
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
            logits = self.call(params, tok, pos, np.array(live)[:, None])
            for i in range(n):
                if live[i]:
                    out[i].append(logits[i])
                    done[i] += 1
        return [np.concatenate(o) for o in out]


# -- (a) (c) (d): the model through its cache is the reference -------------

@pytest.mark.parametrize("slice_len,prompt_lens", [
    (512, [512, 448]), (128, [384, 200]), (64, [64, 100])],
    ids=["one-slice", "several-slices", "mostly-decode"])
def test_slices_then_decode_through_the_cache_are_the_reference(
        params, slice_len, prompt_lens):
    """Two rows of one batch, prefilled in one slice or in several (the
    last padded to its bucket: the state must neither decay nor be fed by
    the padding) and decoded a token at a time to 520 and 460 positions:
    both cross dense_len 256 (while decoding, in the last two cases) and
    then select 4 of up to 9 blocks at every position. Logits at every
    position against the reference's full forward."""
    totals = [520, 460]
    seqs = [_tokens(10 + i, n) for i, n in enumerate(totals)]
    got = _Paged(CFG, totals).run(params, seqs, prompt_lens, slice_len)
    for seq, g in zip(seqs, got):
        want, chosen = _reference(params, seq)
        assert g.shape == want.shape
        assert np.abs(g - want).max() < TOLERANCE
        # past dense_len the selection did select: 4 of the 8 blocks
        assert (chosen[:, :, len(seq) - 1].sum(-1) == SP.topk).all()


def test_rows_of_a_large_prefill_run_one_at_a_time(params, monkeypatch):
    """Over ``PREFILL_TOKENS_PER_PASS`` tokens a prefill call scans its
    rows, the pools carried from row to row (the real cell's slices of
    2048 at two rows and more): three rows of 128 against a limit of 64
    here, shapes no other test compiles."""
    monkeypatch.setattr(sala, "PREFILL_TOKENS_PER_PASS", 64)
    totals = [400, 330, 290]
    seqs = [_tokens(30 + i, n) for i, n in enumerate(totals)]
    got = _Paged(CFG, totals).run(params, seqs, [384, 300, 270], 128)
    for seq, g in zip(seqs, got):
        assert np.abs(g - _reference(params, seq)[0]).max() < TOLERANCE


def test_bf16_in_place_of_fp32_fails_the_tolerance(params):
    cfg = _config(jnp.bfloat16)
    low = sala.serving_params(params, cfg)
    seq = _tokens(3, 320)
    got = _Paged(cfg, [320]).run(low, [seq], [256], 128)[0]
    assert np.abs(got - _reference(params, seq)[0]).max() > 50 * TOLERANCE


def test_selection_left_out_or_state_dropped_is_not_the_reference(
        params, monkeypatch):
    """What the harness's controls leave out, seen at the logits: every
    block attended past dense_len, and a state that starts every slice
    from zero."""
    seq = _tokens(4, 448)
    want = _reference(params, seq)[0]
    dense = _config(sparse=dataclasses.replace(SP, dense_len=4096))
    got = _Paged(dense, [448]).run(params, [seq], [448], 128)[0]
    assert np.abs(got[:256] - want[:256]).max() < TOLERANCE
    assert np.abs(got[256:] - want[256:]).max() > 100 * TOLERANCE

    real = sala._lightning_layer
    monkeypatch.setattr(
        sala, "_lightning_layer",
        lambda *a: real(*a[:-1], jnp.ones_like(a[-1])))  # fresh: always
    got = _Paged(_config(rope_theta=1e4 + 1), [448]).run(
        params, [seq], [448], 128)[0]
    assert np.abs(got[:128] - want[:128]).max() < 1e-3
    assert np.abs(got[128:] - want[128:]).max() > 1e-2


# -- (b): the selection and the attention given a choice -------------------

def _paged_cache(k, v, rng):
    """K/V [T, G, d] laid into a pool of shuffled blocks; (k_blocks,
    v_blocks, kc [1, J, R], table [1, W])."""
    T, G, d = k.shape
    W = T // BLOCK
    table = rng.permutation(np.arange(1, 2 * W + 1))[:W].astype(np.int32)
    pool = np.zeros((2, 2 * W + 1, BLOCK, G * d), np.float32)
    pool[0, table] = np.asarray(k, np.float32).reshape(W, BLOCK, G * d)
    pool[1, table] = np.asarray(v, np.float32).reshape(W, BLOCK, G * d)
    kc = sparse.compressed_keys(k.reshape(1, T, G * d), SP)
    kc = jnp.pad(kc, ((0, 0), (0, W * SP.keys_per_block - kc.shape[1]),
                      (0, 0)))
    return (jnp.asarray(pool[0], k.dtype), jnp.asarray(pool[1], k.dtype),
            kc, jnp.asarray(table[None]))


def test_attention_given_the_programs_choice_and_the_choices_overlap():
    """Random q, k, v in bfloat16 (the serving path's type) against the
    reference in float32. Given the program's block choice, attention
    agrees to bfloat16's rounding (1e-2 of outputs of size 0.4: operands
    rounded to 8 bits, fp32 sums); left to its own float32 scores, the
    reference chooses the same blocks but where two blocks' scores lie
    within that rounding, so at least 90 % of a query's chosen blocks are
    shared (measured 97 %), and the forced blocks are in every choice."""
    T, H, G, d = 768, 4, 2, 16
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(T, n, d)), jnp.bfloat16)
               for n in (H, G, G))
    k_blocks, v_blocks, kc, table = _paged_cache(k, v, rng)
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    real = jnp.ones((1, T), bool)
    mask, _ = jax.jit(lambda *a: sparse.sparse_select(*a, real, SP))(
        q[None], kc, pos)
    got = jax.jit(lambda *a: sparse.sparse_attend(
        *a, (mask, None), pos, real, SP))(q[None], k_blocks, v_blocks, table)
    constants = dict(kernel=SP.kernel, stride=SP.stride, block=SP.block,
                     topk=SP.topk, init_blocks=SP.init_blocks,
                     window=SP.window, dense_len=SP.dense_len,
                     precision="f32")
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    want, _ = reference.sparse_attention(*f32, choice=mask[0], **constants)
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 1e-2
    _, own = reference.sparse_attention(*f32, **constants)
    mine, own = np.asarray(mask[0]), np.asarray(own)
    t = np.arange(T)
    assert (mine.sum(-1) == np.minimum(SP.topk, t // BLOCK + 1)
            )[:, SP.dense_len:].all()
    shared = (mine & own).sum(-1) / own.sum(-1)
    assert shared[:, SP.dense_len:].min() >= 0.5       # of 4: 2 are forced
    assert shared[:, SP.dense_len:].mean() >= 0.9
    for chosen in (mine, own):
        assert chosen[:, :, 0].all()                             # init
        assert chosen[:, t, t // BLOCK].all()                    # its own
        assert chosen[:, t, np.maximum(t - SP.window + 1, 0) // BLOCK].all()
    # a decode step's choice is the slice's choice for the same query
    for at in (255, 256, 300, 767):
        choice, valid = sparse.sparse_select(
            q[None, at:at + 1], kc, pos[:, at:at + 1], real[:, :1], SP)
        blocks = np.zeros_like(mine[:, 0])
        for g in range(G):
            blocks[g, np.asarray(choice[0, g])[np.asarray(valid[0, g])]] = 1
        assert (blocks == mine[:, at]).all(), at
        step = sparse.block_sparse_attention(
            q[None, at:at + 1], kc, k_blocks, v_blocks, table,
            pos[:, at:at + 1], real[:, :1], SP)
        assert np.abs(np.asarray(step[0, 0]) - np.asarray(got[0, at])
                      ).max() < 1e-2


# -- (d) (f): lightning attention ------------------------------------------

def _recurrence(q, k, v, decay, mask):
    state = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], q.shape[3]))
    out = []
    for t in range(q.shape[1]):
        o, state = lightning.lightning_attention(
            q[:, t:t + 1], k[:, t:t + 1], v[:, t:t + 1], state, decay,
            mask[:, t:t + 1])
        out.append(o)
    return jnp.concatenate(out, axis=1), state


@pytest.mark.parametrize("chunk", [16, 64, 256])
def test_chunked_lightning_attention_is_the_token_recurrence(chunk):
    """A random decay table in (0.3, 1], two rows, one of them padded from
    position 70 of 192: the chunked slice form and the one-token form
    applied 192 times give the same outputs at the real positions and the
    same state; the padding neither decays the state nor feeds it. Both
    are float32 sums in another order (measured 2e-6 on outputs of size
    3)."""
    B, T, H, d = 2, 192, 4, 16
    rng = np.random.default_rng(chunk)
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, H, d)), jnp.float32)
               for _ in range(3))
    decay = jnp.asarray(rng.uniform(0.3, 1.0, size=H), jnp.float32)
    mask = jnp.asarray(np.arange(T)[None] < np.array([[T], [70]]))
    start = jnp.asarray(rng.normal(size=(B, H, d, d)), jnp.float32)
    want, _ = _recurrence(q, k, v, decay, mask)
    got, state = lightning.lightning_attention(
        q, k, v, jnp.zeros((B, H, d, d)), decay, mask, chunk=chunk)
    assert np.abs(np.where(mask[..., None, None], got - want, 0)).max() < 2e-5
    # the padded row's state is the state after its 70 real tokens
    _, short = lightning.lightning_attention(
        q[1:, :64], k[1:, :64], v[1:, :64], jnp.zeros((1, H, d, d)), decay,
        mask[1:, :64], chunk=chunk)
    for t in range(64, 70):
        _, short = lightning.lightning_attention(
            q[1:, t:t + 1], k[1:, t:t + 1], v[1:, t:t + 1], short, decay,
            mask[1:, t:t + 1])
    assert np.abs(state[1] - short[0]).max() < 2e-5
    # a state handed in is carried: two halves are the whole
    first, mid = lightning.lightning_attention(
        q[:, :128], k[:, :128], v[:, :128], start, decay, mask[:, :128],
        chunk=chunk)
    second, end = lightning.lightning_attention(
        q[:, 128:], k[:, 128:], v[:, 128:], mid, decay, mask[:, 128:],
        chunk=chunk)
    whole, whole_end = lightning.lightning_attention(
        q, k, v, start, decay, mask, chunk=chunk)
    assert np.abs(jnp.concatenate([first, second], 1)[0] - whole[0]
                  ).max() < 2e-5
    assert np.abs(end - whole_end).max() < 2e-5


def test_decay_table_is_the_conventions_by_published_layer():
    got = np.asarray(sala.decay_table(CFG))
    want = reference.decay_table([10, 11], CFG.n_heads, 32)
    assert got.shape == (2, CFG.n_heads)
    assert np.abs(got - want).max() < 1e-6
    assert (np.diff(got, axis=1) > 0).all() and (got > 0).all() \
        and (got < 1).all()


# -- the layout, the allocator and the engine ------------------------------

@pytest.mark.parametrize("total", [1, 64, 65, 256, 257, 300, 1024])
def test_reservation_is_blocks_that_grow_and_one_state_slot(total):
    cache = KVCacheConfig(40, BLOCK)
    layout = SparseStateLayout(cache, 1024, topk=4, dense_len=256)
    assert layout.kinds == ("kv", "state")
    blocks = -(-total // BLOCK)
    assert layout.blocks_by_kind(total) == (blocks, 1)
    assert layout.blocks_needed(total) == blocks      # a slot is no block
    assert layout.table_width == 16 + 1
    row = np.zeros(layout.table_width, np.int32)
    layout.lay_table(row, list(range(5, 5 + blocks)) + [40 + 3])
    assert list(row[:blocks]) == list(range(5, 5 + blocks))
    assert row[-1] == 3 and (row[blocks:-1] == 0).all()
    cached, selected, slots = layout.attended_rows(total)
    assert (cached, slots) == (total, 1)
    assert selected == (total if total <= 256 else
                        (min(4, blocks) - 1) * BLOCK + (total - 1) % BLOCK + 1)
    assert layout.row_args == ("kv_rows", "selected_rows", "state_slots")
    with pytest.raises(ValueError, match="whole cache blocks"):
        layout.check_prefill(128, 96)
    layout.check_prefill(128, 128)


def test_state_slots_are_the_allocators_ids_past_the_blocks():
    alloc = BlockAllocator(KVCacheConfig(4, BLOCK), slots=2)
    assert (alloc.free_blocks(), alloc.free_slots()) == (4, 2)
    blocks, slots = alloc.allocate_blocks(3), alloc.allocate_slots(2)
    assert sorted(slots) == [4, 5] and alloc.outstanding() == 5
    with pytest.raises(MemoryError, match="state slots"):
        alloc.allocate_slots(1)
    alloc.release(blocks + slots[:1])
    assert (alloc.free_blocks(), alloc.free_slots()) == (4, 1)
    with pytest.raises(AssertionError):
        alloc.assert_balanced(0)
    alloc.release(slots[1:])
    alloc.assert_balanced(0)
    with pytest.raises(ValueError, match="double/bogus"):
        alloc.release([5])
    assert BlockAllocator(KVCacheConfig(4, BLOCK)).free_slots() == 0


def _engine(params, **kw):
    kw.setdefault("buckets", BucketSpec.build(2, 128, min_prefill_len=64))
    kw.setdefault("cache", KVCacheConfig(34, BLOCK))
    kw.setdefault("chunk_prefill_len", 128)
    return InferenceEngine(params, CFG, **kw)


def test_engine_serves_the_reference_tokens_and_a_reused_slot_starts_empty(
        params):
    """Through ``InferenceEngine.submit``: chunked prefill in slices of 128
    between decode steps, two rows a batch, prompts that end under and past
    dense_len. Five requests over two batch rows: a slot and its blocks are
    used again by a later request, whose tokens are the reference's all the
    same (a state left over would show at its first token). Nothing is
    outstanding at the end: no block, no slot."""
    registry = MetricsRegistry()
    tracer = Tracer(enabled=True)
    telemetry = type("T", (), {"registry": registry, "tracer": tracer})()
    sizes = [(300, 12), (150, 8), (200, 70), (420, 6), (260, 6)]
    prompts = [_tokens(20 + i, n).tolist() for i, (n, _) in enumerate(sizes)]
    with _engine(params, telemetry=telemetry) as eng:
        assert eng.kv_outstanding() == 0
        before = eng.programs_compiled()  # the jit is shared in a process
        handles = [eng.submit(p, max_new_tokens=m)
                   for p, (_, m) in zip(prompts, sizes)]
        results = [h.result(timeout=600) for h in handles]
        assert eng.kv_outstanding() == 0
        eng.assert_kv_balanced(0)
        assert eng.programs_compiled() - before <= eng.program_budget()
        in_use = [registry.gauge("serving_kv_blocks_in_use",
                                 labels={"kind": k}).value
                  for k in ("kv", "state")]
    assert in_use == [0, 0]
    for p, (_, m), r in zip(prompts, sizes, results):
        assert r.finish_reason == "length" and len(r.tokens) == m
        # every served token is the reference's first, to the tolerance
        at = _reference(params, p + r.tokens)[0][len(p) - 1:-1]
        assert (at.max(axis=-1) - at[np.arange(m), r.tokens]
                ).max() < TOLERANCE
    # the span args and counters of the cache that differs by layer kind
    steps = [e["args"] for e in tracer.events()
             if e.get("name") == "serving_decode_step"]
    assert steps and all(
        {"kv_rows", "selected_rows", "state_slots"} <= set(a) for a in steps)
    assert all(a["state_slots"] == a["rows"] for a in steps)
    assert all(a["selected_rows"] <= a["kv_rows"] for a in steps)
    assert any(a["selected_rows"] < a["kv_rows"] for a in steps)
    for name, arg in zip(sala.PAGED.row_counters, eng._layout.row_args):
        assert registry.counter(name).value == sum(a[arg] for a in steps)


def test_engine_refuses_by_name_what_this_cache_cannot_serve(params):
    with pytest.raises(ValueError, match="minicpm_sala.*prefix_cache"):
        _engine(params, prefix_cache=True)
    with pytest.raises(ValueError, match="minicpm_sala.*speculative"):
        _engine(params, speculative_k=2, draft_params=params, draft_cfg=CFG)
    with pytest.raises(ValueError, match="whole cache blocks"):
        _engine(params, buckets=BucketSpec.build(2, 128, min_prefill_len=32),
                chunk_prefill_len=32)
    assert sala.PAGED.unsupported == ("prefix_cache", "kv_store",
                                      "speculative")
    assert sala.PAGED.pool_names == ("k_pool", "v_pool", "index_pool",
                                     "state_pool")


def test_serving_params_are_bf16_matrices_and_fp32_vectors(params):
    served = sala.serving_params(params, _config(jnp.bfloat16))
    for path, leaf in jax.tree_util.tree_leaves_with_path(served):
        name = jax.tree_util.keystr(path)
        want = jnp.bfloat16 if ("kernel" in name or "table" in name) \
            else jnp.float32
        assert leaf.dtype == want, name
    again = sala.serving_params(served, _config(jnp.bfloat16))
    assert all(a is b for a, b in zip(jax.tree.leaves(served),
                                      jax.tree.leaves(again)))
