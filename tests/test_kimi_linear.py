"""Kimi-Linear on the serving path (models/kimi_linear.py, the ops
ops/kda.py, ops/mla_attention.py's dense decode and causal slice and
ops/moe.py:routed_experts, the blocks-and-a-slot cache of
serving/kv_cache.py, the engine's counts from the device) against the plain
reference ``benchmarks/reference/kimi_linear.py``, at a small size on the
CPU: hidden 64, KDA of 4 heads of 16 with a 4-tap convolution, MLA of 4
heads of 12 + 4 / 16 over a 16 + 4 wide latent, 16 experts of width 32 of
which experts 8..15 are held, 4 a token, five layers in the published
pattern (KDA + dense, KDA + experts twice, MLA + experts, KDA + experts),
seeded weights and a selection bias of size 0.1.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import kimi_linear as reference
from determined_clone_tpu.models import kimi_linear as kl
from determined_clone_tpu.ops import kda as ops_kda
from determined_clone_tpu.ops import mla_attention as mla
from determined_clone_tpu.ops import moe
from determined_clone_tpu.serving import (
    BucketSpec,
    InferenceEngine,
    KVCacheConfig,
)
from determined_clone_tpu.serving.kv_cache import (
    SparseStateLayout,
    StateSlotLayout,
)
from determined_clone_tpu.telemetry import MetricsRegistry, Tracer

BLOCK = 16
# float32 everywhere, so that what is compared is the cache, the chunked
# delta rule, the carried tails, the absorbed products and the routing, not
# rounding: the program then differs from the reference only in the order
# of float32 sums (measured 2e-6 on logits of size 3; no choice of experts
# has flipped on it). The same program with its state held in bfloat16
# reads 1e-2, computing in bfloat16 throughout 1e-1.
TOLERANCE = 5e-5


def _config(dtype=jnp.float32, **kw):
    return dataclasses.replace(kl.KimiLinearConfig.tiny(),
                               compute_dtype=dtype, param_dtype=dtype, **kw)


CFG = _config()


def _constants(cfg=CFG):
    return dict(experts_per_token=cfg.num_experts_per_token,
                routed_scale=cfg.routed_scaling_factor,
                first_expert=cfg.first_expert, rms_eps=cfg.rms_norm_eps,
                l2_eps=kl.L2_EPS)


@pytest.fixture(scope="module")
def params():
    """Seeded weights with every norm scale away from 1 and a selection
    bias large enough to change choices."""
    p = jax.jit(functools.partial(kl.init, cfg=CFG, bias_std=0.1))(
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
    """(logits [n, V], routed [L_sparse, n, k]) of the whole sequence."""
    return reference.forward(params, list(tokens), kinds=CFG.kinds,
                             keep_choices=True, **{**_constants(), **kw})


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, size=n).astype(np.int32)


class _Paged:
    """The jitted paged forward driven by hand: rows of one batch, each
    with its own blocks and slot (in another order than the rows'),
    prefilled in slices and then decoded a token at a time, the logits at
    every position and the device's counts of every call kept."""

    def __init__(self, cfg, totals, *, num_blocks=48, forward=None):
        self.cfg = cfg
        cache = KVCacheConfig(num_blocks, BLOCK)
        self.layout = cfg.paged_model().cache_layout(cfg, cache)
        self.pools = kl.init_pools(cfg, cache, len(totals))
        self.tables = np.zeros((len(totals), self.layout.table_width),
                               np.int32)
        free = list(range(num_blocks - 1, 0, -1))  # block 0 is nobody's
        for i, total in enumerate(totals):
            need = self.layout.blocks_needed(total)
            blocks = [free.pop() for _ in range(need)][::-1]
            slot = num_blocks + len(totals) - 1 - i    # the last row's: 0
            self.layout.lay_table(self.tables[i], blocks + [slot])
        self.counts = []
        self.fwd = jax.jit(forward or kl.forward_paged_logits,
                           static_argnums=(1,))

    def call(self, params, tok, pos, msk):
        logits, *self.pools, counts, _ = self.fwd(
            params, self.cfg, jnp.asarray(tok), jnp.asarray(pos),
            jnp.asarray(msk), *self.pools, jnp.asarray(self.tables))
        self.counts.append(np.asarray(counts))
        return np.asarray(logits)

    def run(self, params, seqs, prompt_lens, slice_lens):
        """Every row's logits [len, V]: prompts in slices whose buckets
        are ``slice_lens`` in turn (the last repeated; a row's last slice
        padded to the bucket), then one token a step, rows that have ended
        masked out."""
        n = len(seqs)
        out = [[] for _ in seqs]
        done = [0] * n
        slice_lens = list(slice_lens)
        while any(done[i] < prompt_lens[i] for i in range(n)):
            width = slice_lens.pop(0) if len(slice_lens) > 1 \
                else slice_lens[0]
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


# -- (a): the delta rule's two forms and the reference's scan ---------------

def _delta_inputs(seed, B, T, H, d):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v = (jax.random.normal(key, (B, T, H, d)) for key in ks[:3])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    # log decays from -20 (a channel forgets at once) to -1e-3
    g = -jnp.exp(jax.random.uniform(ks[3], (B, T, H, d), minval=-7.0,
                                    maxval=3.0))
    b = jax.nn.sigmoid(2 * jax.random.normal(ks[4], (B, T, H)))
    state = jax.random.normal(ks[5], (B, H, d, d))
    return q, k, v, g, b, state


@pytest.mark.parametrize("T,real", [(128, (128, 128)), (150, (150, 77)),
                                    (64, (64, 1)), (70, (5, 70)),
                                    (144, (79, 80)), (144, (81, 127)),
                                    (136, (129, 63))],
                         ids=["whole-chunks", "ragged", "one-chunk",
                              "under-a-chunk", "ends-at-15-and-16",
                              "ends-at-17-and-63", "ends-at-65-and-63"])
def test_chunk_form_is_the_one_token_form_is_the_references_scan(T, real):
    """``ops/kda.py``'s chunk form (the Pallas kernel, interpreted here)
    against its one-token form applied position by position against
    ``reference.delta_rule``, outputs at the real positions and the state
    after them, from a state that is not zero, for lengths that are and are
    not whole chunks of 64 and that end on either side of a sub-chunk's
    edge (15, 16, 17, 63 and 65 of a chunk), decays from 1 - 1e-3 down to
    exp(-20). Float32; 2e-5 of outputs of size 1 is the order of the sums
    (the solve of 64 rows among them)."""
    q, k, v, g, b, state = _delta_inputs(T, 2, T, 3, 16)
    mask = jnp.arange(T)[None, :] < jnp.asarray(real)[:, None]
    o_chunk, s_chunk = jax.jit(ops_kda.kda)(q, k, v, g, b, state, mask)
    s_step, outs = state, []
    step = jax.jit(ops_kda.kda)
    for t in range(T):
        o, s_step = step(q[:, t:t + 1], k[:, t:t + 1], v[:, t:t + 1],
                         g[:, t:t + 1], b[:, t:t + 1], s_step,
                         mask[:, t:t + 1])
        outs.append(o)
    o_step = jnp.concatenate(outs, axis=1)
    assert np.isfinite(np.asarray(o_chunk)).all()
    for row, n in enumerate(real):
        o_ref, s_ref = reference.delta_rule(
            q[row, :n], k[row, :n], v[row, :n], g[row, :n], b[row, :n],
            state=state[row])
        for got in (o_chunk, o_step):
            assert np.abs(got[row, :n] - o_ref).max() < 2e-5
        for got in (s_chunk, s_step):
            assert np.abs(got[row] - s_ref).max() < 2e-5


def test_every_exponent_is_a_non_positive_difference():
    """Decays that underflow (a channel at exp(-80) a token, sixty-four
    tokens a chunk) give zeros, not infinities or NaN: no power is formed
    as a quotient of powers."""
    q, k, v, g, b, state = _delta_inputs(3, 1, 128, 2, 16)
    g = jnp.where(jnp.arange(16) < 8, -80.0, g)
    mask = jnp.ones((1, 128), bool)
    o, s = jax.jit(ops_kda.kda)(q, k, v, g, b, state, mask)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(s)
                                                            ).all()
    o_ref, s_ref = reference.delta_rule(q[0], k[0], v[0], g[0], b[0],
                                        state=state[0])
    assert np.abs(o[0] - o_ref).max() < 2e-5
    assert np.abs(s[0] - s_ref).max() < 2e-5


@pytest.mark.parametrize("first", [16, 17, 31, 48, 70],
                         ids=lambda n: f"forgets-from-{n}")
def test_a_decay_that_underflows_after_a_sub_chunks_first_row(first):
    """Half the channels keep everything up to position ``first`` (g = 0)
    and forget at exp(-80) a token from there on: for a later position of
    the same sub-chunk or chunk the decay back to the sub-chunk's
    reference row underflows while the reference row's decay back to the
    earlier positions is 1. The product of the two is the reference's
    value (0 where it underflows there, finite everywhere)."""
    q, k, v, g, b, state = _delta_inputs(first, 1, 128, 2, 16)
    g = jnp.where((jnp.arange(16) < 8)[None, None, None, :],
                  jnp.where(jnp.arange(128) < first, 0.0,
                            -80.0)[None, :, None, None], g)
    o, s = jax.jit(ops_kda.kda)(q, k, v, g, b, state,
                                jnp.ones((1, 128), bool))
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(s)
                                                            ).all()
    o_ref, s_ref = reference.delta_rule(q[0], k[0], v[0], g[0], b[0],
                                        state=state[0])
    assert np.abs(o[0] - o_ref).max() < 2e-5
    assert np.abs(s[0] - s_ref).max() < 2e-5


@pytest.mark.parametrize("cuts", [(64, 128), (48, 113), (17, 81)],
                         ids=["whole-chunks", "ragged", "sub-chunk-edges"])
def test_a_state_handed_through_three_slices_is_one_pass_over_them(cuts):
    """192 tokens in three calls, each padded to 128 with the state of the
    one before, against one call over all of them."""
    q, k, v, g, b, state = _delta_inputs(7, 2, 192, 3, 16)
    slice_form = jax.jit(ops_kda.kda)
    o_one, s_one = slice_form(q, k, v, g, b, state, jnp.ones((2, 192), bool))
    outs, s = [], state
    for lo, hi in zip((0, *cuts), (*cuts, 192)):
        pad = ((0, 0), (0, 128 - (hi - lo)), (0, 0), (0, 0))
        o, s = slice_form(*(jnp.pad(x[:, lo:hi], pad) for x in (q, k, v, g)),
                          jnp.pad(b[:, lo:hi], pad[:3]), s,
                          jnp.arange(128)[None, :] < jnp.full((2, 1),
                                                              hi - lo))
        outs.append(o[:, :hi - lo])
    assert np.abs(jnp.concatenate(outs, axis=1) - o_one).max() < 2e-5
    assert np.abs(s - s_one).max() < 2e-5


@pytest.mark.parametrize("H,group", [(12, 6), (16, 8), (5, 5), (11, 1)])
def test_more_heads_than_a_grid_step_takes_are_each_their_own(H, group):
    """A grid step takes ``heads_a_step`` heads; with more, every head of
    every group reads its own columns, write strengths and state: each
    head alone gives what it gives among the others."""
    assert ops_kda.heads_a_step(H) == group
    q, k, v, g, b, state = _delta_inputs(H, 2, 80, H, 16)
    mask = jnp.arange(80)[None, :] < jnp.asarray([80, 33])[:, None]
    slice_form = jax.jit(ops_kda.kda)
    o, s = slice_form(q, k, v, g, b, state, mask)
    for h in (0, group - 1, H - group, H - 1):
        at = slice(h, h + 1)
        o_h, s_h = slice_form(q[:, :, at], k[:, :, at], v[:, :, at],
                              g[:, :, at], b[:, :, at], state[:, at], mask)
        assert np.abs(o[:, :, at] - o_h).max() < 1e-6
        assert np.abs(s[:, at] - s_h).max() < 1e-6


def test_short_conv_carries_its_tail_over_real_rows_only():
    """Four taps over positions in two calls with the tail between them is
    the reference's sum of four shifted rows over the whole; rows of
    padding after the real ones do not shift the tail, and a call with no
    real row hands it on as it was."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 24))
    taps = jax.random.normal(jax.random.PRNGKey(1), (4, 24))
    want = np.stack([np.asarray(reference.short_conv(x[i], taps))
                     for i in range(2)])
    zero = jnp.zeros((2, 3, 24))
    first, tail = ops_kda.short_conv(
        jnp.pad(x[:, :25], ((0, 0), (0, 7), (0, 0))), taps, zero,
        jnp.array([25, 25]))
    assert np.abs(first[:, :25] - want[:, :25]).max() < 1e-6
    assert np.array_equal(tail, x[:, 22:25])
    _, same = ops_kda.short_conv(x[:, :8], taps, tail, jnp.array([0, 0]))
    assert np.array_equal(same, tail)
    second, tail = ops_kda.short_conv(x[:, 25:], taps, tail,
                                      jnp.array([15, 1]))
    assert np.abs(second - want[:, 25:]).max() < 1e-6
    assert np.array_equal(tail[0], x[0, 37:40])
    assert np.array_equal(tail[1], x[1, 23:26])


# -- (b) (c): the model through its cache is the reference ------------------

@pytest.mark.parametrize("slice_lens,prompt_lens", [
    ([128], [128, 112]), ([32], [96, 50]), ([64, 16, 32], [100, 90]),
    ([16], [16, 20])],
    ids=["one-slice", "several-slices", "slices-of-three-lengths",
         "mostly-decode"])
def test_slices_then_decode_through_the_cache_are_the_reference(
        params, slice_lens, prompt_lens):
    """Two rows of one batch, prefilled in one slice or in several of any
    allowed lengths (a row's last padded to its bucket; the shorter row
    idle, all padding, while the longer one finishes) and decoded a token
    at a time to 140 and 120 positions: the four KDA layers' states and
    tails pass from call to call through their slots, the MLA layer reads
    every cached latent. Logits at every position against the reference's
    full forward."""
    totals = [140, 120]
    seqs = [_tokens(10 + i, n) for i, n in enumerate(totals)]
    got = _Paged(CFG, totals).run(params, seqs, prompt_lens, slice_lens)
    for seq, g in zip(seqs, got):
        want, _ = _reference(params, seq)
        assert g.shape == want.shape
        assert np.abs(g - want).max() < TOLERANCE


def test_a_padded_bucket_leaves_state_and_tail_where_the_last_real_token_did(
        params):
    """A prompt of 70 in a bucket of 128, and the same prompt in a bucket
    of 80: the state and tail pools are the same to the bit where it
    matters (the same real tokens went through the same chunks: 64 and 6),
    a call whose row is all padding changes neither, and a slot no row
    names stays zero."""
    seq = _tokens(5, 70)
    pools = []
    for width in (128, 80):
        paged = _Paged(CFG, [70, 70])
        tok = np.zeros((2, width), np.int32)
        tok[0, :70] = seq
        pos = np.zeros((2, width), np.int32)
        pos[0, :70] = np.arange(70)
        msk = np.zeros((2, width), bool)
        msk[0, :70] = True
        paged.call(params, tok, pos, msk)
        pools.append([np.asarray(p) for p in paged.pools])
        # nothing real: the pools come back as they went
        paged.call(params, np.zeros((2, 16), np.int32),
                   np.zeros((2, 16), np.int32), np.zeros((2, 16), bool))
        for before, after in zip(pools[-1], paged.pools):
            assert np.array_equal(before, np.asarray(after))
    (_, state_a, tail_a), (_, state_b, tail_b) = pools
    assert np.abs(state_a - state_b).max() < 1e-6
    assert np.array_equal(tail_a, tail_b)
    # row 0 holds slot 1 (``_Paged``), row 1 (all padding) slot 0
    assert np.abs(state_a[:, 1]).max() > 1e-3 and not state_a[:, 0].any()
    assert np.abs(tail_a[:, 1]).max() > 1e-3 and not tail_a[:, 0].any()


def test_rows_of_a_large_prefill_run_one_at_a_time(params, monkeypatch):
    """Over ``PREFILL_TOKENS_PER_PASS`` tokens a prefill call scans its
    rows, pools and counts carried from row to row (the real cell's slices
    of 2048 at two rows and more): three rows of 32 against a limit of 16
    here."""
    monkeypatch.setattr(kl, "PREFILL_TOKENS_PER_PASS", 16)
    totals = [100, 80, 70]
    seqs = [_tokens(30 + i, n) for i, n in enumerate(totals)]
    paged = _Paged(CFG, totals)
    got = paged.run(params, seqs, [96, 70, 40], [32])
    for seq, g in zip(seqs, got):
        assert np.abs(g - _reference(params, seq)[0]).max() < TOLERANCE
    first = paged.counts[0]
    assert 0 < first[0] <= 3 * 32 * 4 * 4 and 4 * 4 < first[1] <= 3 * 4 * 8


def _with_delta_rule(replacement):
    """``forward_paged_logits`` with another delta rule in the model."""
    def forward(*args):
        real, kl.kda = kl.kda, replacement
        try:
            return kl.forward_paged_logits(*args)
        finally:
            kl.kda = real

    return forward


def test_state_or_decay_in_bfloat16_or_no_delta_correction_fails_the_tolerance(
        params):
    """The program as it is holds the logits to ``TOLERANCE``; with the
    state rounded to bfloat16 wherever it is handed on, with its decays
    held in bfloat16, with everything computed in bfloat16, or with the
    delta correction left out, it does not, by far. 96 tokens in three
    slices of 32: the state is handed on twice and every chunk form's
    sub-chunk edges are crossed, in one program a control (a decode step
    would be a second program a control for the same rounding)."""
    seq = _tokens(3, 96)
    want = _reference(params, seq)[0]

    def rounded(*args, **kw):
        o, state = ops_kda.kda(*args, **kw)
        return o, jax.lax.reduce_precision(state, 8, 7)

    got = _Paged(CFG, [96], forward=_with_delta_rule(rounded)).run(
        params, [seq], [96], [32])[0]
    assert np.abs(got - want).max() > 20 * TOLERANCE

    def decays_rounded(q, k, v, g, b, state, token_mask, **kw):
        a = jax.lax.reduce_precision(jnp.exp(g), 8, 7)
        return ops_kda.kda(q, k, v, jnp.log(a), b, state, token_mask, **kw)

    got = _Paged(CFG, [96], forward=_with_delta_rule(decays_rounded)).run(
        params, [seq], [96], [32])[0]
    assert np.abs(got - want).max() > 20 * TOLERANCE
    cfg = _config(jnp.bfloat16)
    got = _Paged(cfg, [96]).run(kl.serving_params(params, cfg), [seq], [96],
                                [32])[0]
    assert np.abs(got - want).max() > 50 * TOLERANCE
    # the reference's own controls move its logits as far
    for control, least in (("bf16_state", 20), ("bf16_decay", 20),
                           ("no_delta", 1000)):
        moved = _reference(params, seq, precision=control)[0]
        assert np.abs(moved - want).max() > least * TOLERANCE, control


# -- latent attention read whole ---------------------------------------------

def _latents(seed, n_blocks, R):
    return jax.random.normal(jax.random.PRNGKey(seed), (n_blocks, BLOCK, R))


def test_dense_decode_reads_each_rows_blocks_to_its_length_and_no_further():
    """``mla_decode_dense`` over rows of lengths 0, 1, 16, 17 and 75, two
    blocks of positions a pass, against ``mla_decode`` over the same
    positions gathered by id. Positions past a row's length inside its
    last pass hold 1e4 and weigh nothing; every block no row owns (but
    block 0, which an unfilled table entry names) holds NaN and is never
    read: a row's passes end with its length, not with its table. A row of
    length 0 reads nothing and gives zeros."""
    H, R = 3, 128
    lengths = np.array([75, 0, 17, 16, 1])
    pool = np.array(_latents(0, 24, R))
    tables = np.zeros((5, 6), np.int32)
    free = list(range(1, 24))
    rng = np.random.default_rng(0)
    rng.shuffle(free)
    used = np.zeros((24, BLOCK), bool)
    for i, n in enumerate(lengths):
        for w in range(-(-n // BLOCK)):
            tables[i, w] = free.pop()
            used[tables[i, w], :min(BLOCK, n - w * BLOCK)] = True
    pool[~used] = 1e4
    pool[[b for b in free if b]] = np.nan
    tables[2, 2:] = free[0]  # past row 2's one pass: never reached
    q = jax.random.normal(jax.random.PRNGKey(1), (5, 1, H, R))
    got = jax.jit(lambda *a: mla.mla_decode_dense(*a, scale=0.3,
                                                  key_blocks=2))(
        q, jnp.asarray(pool), jnp.asarray(tables), jnp.asarray(lengths))
    assert np.isfinite(np.asarray(got)).all()
    assert not np.asarray(got[1]).any()
    positions = np.arange(80)[None, :]
    ids = tables[:, positions[0] // BLOCK] * BLOCK + positions % BLOCK
    valid = positions < lengths[:, None]
    want = mla.mla_decode(q, jnp.asarray(np.nan_to_num(pool)).reshape(-1, R),
                          jnp.asarray(ids), jnp.asarray(valid), scale=0.3)
    assert np.abs(got - want).max() < 1e-5


def test_a_slice_under_the_causal_mask_alone_is_the_slice_given_that_mask():
    """``mla_slice(allowed=None)`` is ``mla_slice`` handed the causal mask
    over real queries: the path GLM's selection takes, unedited."""
    B, T, H, R, W = 2, 32, 3, 128, 5
    blocks = _latents(2, 12, R)
    tables = jnp.asarray([[3, 7, 1, 9, 0], [2, 4, 6, 8, 10]], jnp.int32)
    positions = jnp.asarray([16, 32])[:, None] + jnp.arange(T)[None]
    mask = jnp.arange(T)[None] < jnp.asarray([T, 20])[:, None]
    q = jax.random.normal(jax.random.PRNGKey(3), (B, T, H, R))
    causal = (jnp.arange(W * BLOCK)[None, None, :] <= positions[..., None]) \
        & mask[..., None]
    kw = dict(scale=0.2, key_blocks=2, query_block=16)
    want = mla.mla_slice(q, blocks, tables, causal, positions, mask, **kw)
    got = mla.mla_slice(q, blocks, tables, None, positions, mask, **kw)
    real = np.asarray(mask)[..., None, None]
    assert np.abs(np.where(real, got - want, 0)).max() < 1e-6


# -- (d): the two shares of an expert layer ----------------------------------

def test_the_two_shares_of_an_expert_layer_add_up_to_the_uncut_layer(params):
    """The test that ties the share to the model: the routed parts that
    the two members of the pair compute, each from ``routed_experts`` with
    its own ``first_expert`` (0 and 8) and its own eight experts' weights,
    plus the shared expert once, are the reference's layer over all
    sixteen experts. Float32; 2e-5 of outputs of size 1 is the order of
    the sums."""
    lp = jax.tree.map(lambda w: w[0], params["kda_sparse"])
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    whole = {name: {"kernel": 0.125 * jax.random.normal(
        key, (16, *lp[name]["kernel"].shape[1:]))}
        for name, key in zip(("experts_gate", "experts_up", "experts_down"),
                             keys)}
    x = jnp.asarray(np.random.default_rng(4).normal(size=(80, 64)),
                    jnp.float32)
    want = reference.layer_ffn({**lp, **whole}, x, experts=range(16),
                               **_constants())
    h = kl._norm(CFG, lp["ln2"], x, jnp.float32)
    total, pairs = np.asarray(x + kl._swiglu(CFG, lp, h, "shared")), 0

    def routed(share, first):
        y, counts, _ = moe.routed_experts(
            share, h, first_expert=first, n_held=8, n_experts=16,
            k=CFG.num_experts_per_token, scale=CFG.routed_scaling_factor,
            compute_dtype=jnp.float32)
        return np.asarray(y), np.asarray(counts)

    for first in (0, 8):
        share = {**lp, **{name: {"kernel": w["kernel"][first:first + 8]}
                          for name, w in whole.items()}}
        y, counts = routed(share, first)
        assert np.abs(y).max() > 1e-2       # a member alone is not the layer
        total, pairs = total + y, pairs + counts[0]
    assert pairs == 80 * 4                  # every pair fell to one member
    assert np.abs(total - want).max() < 2e-5
    # and the reference's own share is the member's
    mine = reference.layer_ffn(lp, x, experts=range(8, 16), **_constants())
    y, _ = routed(lp, CFG.first_expert)
    assert np.abs(np.asarray(x + kl._swiglu(CFG, lp, h, "shared")) + y
                  - mine).max() < 2e-5


# -- the layout, the engine ---------------------------------------------------

@pytest.mark.parametrize("total", [1, 16, 17, 100, 512])
def test_reservation_is_blocks_and_one_slot_and_every_row_is_read(total):
    cache = KVCacheConfig(64, BLOCK)
    layout = kl.PAGED.cache_layout(CFG, cache)
    assert type(layout) is StateSlotLayout
    assert layout.kinds == ("kv", "state") and layout.state_slots == 1
    assert layout.table_width == 512 // BLOCK + 1
    assert layout.blocks_by_kind(total) == (-(-total // BLOCK), 1)
    assert layout.blocks_needed(total) == -(-total // BLOCK)
    assert layout.row_args == ("kv_rows", "selected_rows", "state_slots")
    assert layout.attended_rows(total) == (total, total, 1)
    assert layout.step_rows([total, 3], 4) == (total + 3, total + 3, 2)
    row = np.zeros(layout.table_width, np.int32)
    layout.lay_table(row, [5, 9, 64 + 3])
    assert list(row[:3]) == [5, 9, 0] and row[-1] == 3
    with pytest.raises(ValueError, match="whole cache blocks"):
        layout.check_prefill(64, 24)
    # the sparse family's layout is this one with its selection's counts
    sparse = SparseStateLayout(cache, 512, topk=2, dense_len=32)
    assert isinstance(sparse, StateSlotLayout)
    assert sparse.row_args == layout.row_args
    assert sparse.table_width == layout.table_width
    assert sparse.attended_rows(100) == (100, 16 + 4, 1)
    assert sparse.attended_rows(20) == (20, 20, 1)


def test_pools_are_three_and_runs_are_by_kind():
    cfg = _config(jnp.bfloat16)
    latent, state, tail = kl.init_pools(cfg, KVCacheConfig(10, BLOCK), 3)
    assert latent.shape == (1, 10, BLOCK, 128) and latent.dtype == jnp.bfloat16
    assert state.shape == (4, 3, 4, 16, 16) and state.dtype == jnp.float32
    assert tail.shape == (4, 3, 3, 192) and tail.dtype == jnp.bfloat16
    assert cfg.kinds == ("kda_dense", "kda_sparse", "kda_sparse",
                         "mla_sparse", "kda_sparse")
    assert cfg.runs() == [("kda_dense", 0, 1, 0), ("kda_sparse", 0, 2, 1),
                          ("mla_sparse", 0, 1, 0), ("kda_sparse", 2, 3, 3)]
    published = kl.KimiLinearConfig()
    assert published.kinds.count("mla_sparse") == 7 \
        and published.kinds.count("kda_sparse") == 19 \
        and published.kinds[0] == "kda_dense" and published.row_width == 640
    assert [k.split("_")[0] for k in published.kinds[:8]] \
        == ["kda"] * 3 + ["mla"] + ["kda"] * 3 + ["mla"]
    with pytest.raises(ValueError, match="once each"):
        _config(kda_layers=(1, 2, 3), full_attn_layers=(4,))


def _engine(params, **kw):
    kw.setdefault("buckets", BucketSpec.build(2, 32, min_prefill_len=16))
    kw.setdefault("cache", KVCacheConfig(66, BLOCK))
    kw.setdefault("chunk_prefill_len", 32)
    return InferenceEngine(params, CFG, **kw)


def test_engine_serves_the_reference_tokens_and_reads_the_devices_counts(
        params):
    """Through ``InferenceEngine.submit``: chunked prefill in slices of 32
    between decode steps, two rows a batch. Five requests over two batch
    rows, so blocks and slots are used again by a later request (a slot's
    state and tail are read as zero by the call that holds position 0).
    Every served token is the reference's first **by its logits**; nothing
    is outstanding at the end. The decode step's spans carry the rows from
    the lengths, ``decode_commit`` and ``serving_prefill`` what only the
    device knew, which the counters add up, a prefill's span its real
    tokens; a result carries the experts each of its positions was routed
    to."""
    registry = MetricsRegistry()
    tracer = Tracer(enabled=True)
    telemetry = type("T", (), {"registry": registry, "tracer": tracer})()
    sizes = [(100, 12), (20, 20), (70, 30), (150, 6), (40, 6)]
    prompts = [_tokens(20 + i, n).tolist() for i, (n, _) in enumerate(sizes)]
    with _engine(params, telemetry=telemetry) as eng:
        before = eng.programs_compiled()
        handles = [eng.submit(p, max_new_tokens=m)
                   for p, (_, m) in zip(prompts, sizes)]
        results = [h.result(timeout=600) for h in handles]
        assert eng.kv_outstanding() == 0
        eng.assert_kv_balanced(0)
        assert eng.programs_compiled() - before <= eng.program_budget()
    for p, (_, m), r in zip(prompts, sizes, results):
        assert r.finish_reason == "length" and len(r.tokens) == m
        logits, routed = _reference(params, p + r.tokens)
        at = logits[len(p) - 1:-1]
        assert (at.max(axis=-1) - at[np.arange(m), r.tokens]
                ).max() < TOLERANCE
        n = len(p) + m - 1
        assert r.token_records.shape == (n, 4 * CFG.num_experts_per_token)
        mine = r.token_records.reshape(n, 4, -1).transpose(1, 0, 2)
        assert (np.sort(mine, -1) == np.sort(routed[:, :n], -1)).all()
    events = tracer.events()
    steps = [e["args"] for e in events
             if e.get("name") == "serving_decode_step"]
    assert steps and all(
        a["kv_rows"] == a["selected_rows"] >= 20 * a["rows"]
        and a["state_slots"] == a["rows"] for a in steps)
    for name, arg in zip(kl.PAGED.row_counters, eng._layout.row_args):
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
    assert all(0 < a["tokens"] <= a["batch"] * a["length"] for a in prefills)
    for name in kl.PAGED.step_counters:
        assert registry.counter(f"serving_{name}_total").value \
            == sum(a[name] for a in commits + prefills)


def test_engine_refuses_by_name_what_this_cache_cannot_serve(params):
    with pytest.raises(ValueError, match="kimi_linear.*prefix_cache"):
        _engine(params, prefix_cache=True)
    with pytest.raises(ValueError, match="kimi_linear.*speculative"):
        _engine(params, speculative_k=2, draft_params=params, draft_cfg=CFG)
    with pytest.raises(ValueError, match="whole cache blocks"):
        _engine(params, buckets=BucketSpec.build(2, 32, min_prefill_len=8),
                chunk_prefill_len=8)
    assert kl.PAGED.unsupported == ("prefix_cache", "kv_store",
                                    "speculative")
    assert kl.PAGED.pool_names == ("latent_pool", "state_pool", "tail_pool")
    assert kl.PAGED.step_counters == ("expert_pairs", "expert_hits")
    assert kl.PAGED.token_records


def test_serving_params_are_bf16_matrices_and_fp32_vectors_and_router(
        params):
    served = kl.serving_params(params, _config(jnp.bfloat16))
    for path, leaf in jax.tree_util.tree_leaves_with_path(served):
        name = jax.tree_util.keystr(path)
        matrix = ("kernel" in name or "table" in name) \
            and "router" not in name
        assert leaf.dtype == (jnp.bfloat16 if matrix else jnp.float32), name
    again = kl.serving_params(served, _config(jnp.bfloat16))
    assert all(a is b for a, b in zip(jax.tree.leaves(served),
                                      jax.tree.leaves(again)))


def test_seeded_decays_spread_over_the_unit_interval(params):
    """``init`` draws ``A_log`` and ``dt_bias`` so that a channel's decay
    at a zero gate input lies anywhere in (0, 1), not all near 1."""
    big = jax.jit(functools.partial(kl.init, cfg=dataclasses.replace(
        CFG, kda_num_heads=16, kda_head_dim=64, hidden_size=64)))(
        jax.random.PRNGKey(3))
    decay = big["kda_sparse"]["kda_decay"]
    a = np.exp(-np.exp(np.asarray(decay["log_a"]))[..., None]
               * np.asarray(jax.nn.softplus(decay["dt_bias"])
                            ).reshape(3, 16, 64))
    assert a.min() < 0.05 and a.max() > 0.995
    assert 0.2 < np.mean(a > 0.9) < 0.8
