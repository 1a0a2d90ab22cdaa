"""GLM-5.2 on the serving path (models/glm_moe_dsa.py, the ops
ops/mla_attention.py, ops/dsa_index.py and ops/moe.py:routed_experts, the
latent cache with an index beside it in serving/kv_cache.py, the engine's
counts from the device) against the plain reference
``benchmarks/reference/glm_moe_dsa.py``, at a small size on the CPU: hidden
64, 4 heads of 12 + 4 / 16 over a 16 + 4 wide latent (``q_lora_rank`` 32), an
indexer of 2 heads x 8 that picks 32 positions, 16 experts of width 32 of
which experts 4..7 are held, 4 a token, one dense layer and four expert
layers in the published pattern ``full, shared, shared, shared, full``,
seeded weights and a selection bias of size 0.1.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import glm_moe_dsa as reference
from determined_clone_tpu.models import glm_moe_dsa as glm
from determined_clone_tpu.ops import dsa_index, mla_attention as mla
from determined_clone_tpu.ops import moe
from determined_clone_tpu.serving import (
    BucketSpec,
    InferenceEngine,
    KVCacheConfig,
)
from determined_clone_tpu.serving.kv_cache import LatentIndexLayout
from determined_clone_tpu.telemetry import MetricsRegistry, Tracer

BLOCK = 16
# float32 everywhere, so that what is compared is the cache, the absorbed
# products, the selection and the routing, not rounding: the program then
# differs from the reference only in the order of float32 sums (measured
# 4e-6 on logits of size 3; no top-k choice, of positions or of experts,
# has flipped on it). The same program computing in bfloat16 reads 3e-2.
TOLERANCE = 5e-5


def _config(dtype=jnp.float32, **kw):
    return dataclasses.replace(glm.GLMMoeDsaConfig.tiny(),
                               compute_dtype=dtype, param_dtype=dtype, **kw)


CFG = _config()
TOPK = CFG.index_topk


def _constants(cfg):
    return dict(mlp_types=cfg.mlp_layer_types,
                indexer_types=cfg.indexer_types,
                index_topk=cfg.index_topk,
                experts_per_token=cfg.num_experts_per_tok,
                routed_scale=cfg.routed_scaling_factor,
                first_expert=cfg.first_expert, rope_theta=cfg.rope_theta,
                rms_eps=cfg.rms_norm_eps,
                index_norm_eps=cfg.index_norm_eps)


@pytest.fixture(scope="module")
def params():
    """Seeded weights with every learned vector away from its initial
    value (norm scales 1, LayerNorm bias 0), and a selection bias large
    enough to change choices."""
    p = jax.jit(functools.partial(glm.init, cfg=CFG, bias_std=0.1))(
        jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    for kind in set(CFG.kinds):
        for name, leaves in p[kind].items():
            for leaf in ("scale", "bias"):
                if leaf in leaves and name != "router":
                    leaves[leaf] = (leaf == "scale") + 0.2 \
                        * jax.random.normal(next(keys), leaves[leaf].shape)
    p["final_norm"]["scale"] = 1 + 0.2 * jax.random.normal(
        next(keys), p["final_norm"]["scale"].shape)
    return p


def _reference(params, tokens, cfg=CFG, **kw):
    """(logits [n, V], allowed [L_full, n', n'], routed [L_sparse, n', k])
    of the whole sequence, padded to whole blocks."""
    n = len(tokens)
    padded = list(tokens) + [0] * (-n % BLOCK)
    return reference.forward(params, padded, n_rows=n, keep_choices=True,
                             **{**_constants(cfg), **kw})


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, size=n).astype(np.int32)


def _allowed_from_positions(chosen, n_positions):
    """[B, T, K] chosen positions (-1: none) as a mask [B, T, S]: for the
    tests, which compare a decode step's choice with a slice's."""
    B, T, K = chosen.shape
    hit = jnp.zeros((B, T, n_positions + 1), bool)
    at = jnp.where(chosen >= 0, chosen, n_positions)
    hit = hit.at[jnp.arange(B)[:, None, None], jnp.arange(T)[None, :, None],
                 at].set(True)
    return hit[..., :n_positions]


def _mla_expanded(q_nope: jax.Array, q_rope: jax.Array, c: jax.Array,
                 k_rope: jax.Array, w_uk: jax.Array, w_uv: jax.Array,
                 allowed: jax.Array, *, scale: float) -> jax.Array:
    """The expanded form: every head's keys and values are
    formed from the latents c [B, S, rank] and k_rope [B, S, r], and
    attended under ``allowed`` [B, T, S]. q_nope [B, T, H, n], q_rope [B,
    T, H, r]. Returns [B, T, H, v] fp32. What ``absorbed_query``,
    ``mla_decode`` / ``mla_slice`` and ``expand_values`` compute together,
    without the reassociation."""
    f32 = jnp.float32
    k_nope = jnp.einsum("bsc,hnc->bshn", c.astype(f32), w_uk.astype(f32))
    v = jnp.einsum("bsc,hcv->bshv", c.astype(f32), w_uv.astype(f32))
    scores = (jnp.einsum("bthn,bshn->bhts", q_nope.astype(f32), k_nope)
              + jnp.einsum("bthr,bsr->bhts", q_rope.astype(f32),
                           k_rope.astype(f32))) * scale
    probs = jax.nn.softmax(jnp.where(allowed[:, None], scores, -1e30), -1)
    return jnp.einsum("bhts,bshv->bthv", probs, v)


class _Paged:
    """The jitted paged forward driven by hand: rows of one batch, each
    with its own blocks (in another order than the rows'), prefilled in
    slices and then decoded a token at a time, the logits at every
    position and the device's counts of every call kept."""

    def __init__(self, cfg, totals, *, num_blocks=48):
        self.cfg = cfg
        cache = KVCacheConfig(num_blocks, BLOCK)
        self.layout = cfg.paged_model().cache_layout(cfg, cache)
        self.pools = glm.init_pools(cfg, cache, len(totals))
        self.tables = np.zeros((len(totals), self.layout.table_width),
                               np.int32)
        free = list(range(num_blocks - 1, 0, -1))  # block 0 is nobody's
        for i, total in enumerate(totals):
            need = self.layout.blocks_needed(total)
            self.layout.lay_table(self.tables[i],
                                  [free.pop() for _ in range(need)][::-1])
        self.counts, self.routing = [], []
        self.fwd = jax.jit(glm.forward_paged_logits, static_argnums=(1,))

    def call(self, params, tok, pos, msk):
        logits, *self.pools, counts, routing = self.fwd(
            params, self.cfg, jnp.asarray(tok), jnp.asarray(pos),
            jnp.asarray(msk), *self.pools, jnp.asarray(self.tables))
        self.counts.append(np.asarray(counts))
        self.routing.append(np.asarray(routing))
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
                    out[i].append(logits[i, 0])
                    done[i] += 1
        return [np.concatenate([o.reshape(-1, o.shape[-1]) for o in row])
                for row in out]


# -- (a) (c): the model through its cache is the reference -----------------

@pytest.mark.parametrize("slice_len,prompt_lens", [
    (128, [128, 112]), (32, [96, 50]), (16, [16, 20])],
    ids=["one-slice", "several-slices", "mostly-decode"])
def test_slices_then_decode_through_the_cache_are_the_reference(
        params, slice_len, prompt_lens):
    """Two rows of one batch, prefilled in one slice or in several (the
    last padded to its bucket) and decoded a token at a time to 140 and
    120 positions: both cross ``index_topk`` 32 (inside a slice in the
    first two cases, **while decoding** in the last) and then attend 32 of
    up to 140 positions at every query, in the ``shared`` layers those the
    ``full`` layer before them chose. Logits at every position against the
    reference's full forward."""
    totals = [140, 120]
    seqs = [_tokens(10 + i, n) for i, n in enumerate(totals)]
    got = _Paged(CFG, totals).run(params, seqs, prompt_lens, slice_len)
    for seq, g in zip(seqs, got):
        want, allowed, _ = _reference(params, seq)
        assert g.shape == want.shape
        assert np.abs(g - want).max() < TOLERANCE
        # past index_topk the selection did select, in both full layers
        t = np.arange(len(seq))
        assert (allowed[:, t, :].sum(-1)
                == np.minimum(t + 1, TOPK)[None]).all()


def test_rows_of_a_large_prefill_run_one_at_a_time(params, monkeypatch):
    """Over ``PREFILL_TOKENS_PER_PASS`` tokens a prefill call scans its
    rows, pools and counts carried from row to row (the real cell's slices
    of 2048 at two rows and more): three rows of 32 against a limit of 16
    here."""
    monkeypatch.setattr(glm, "PREFILL_TOKENS_PER_PASS", 16)
    totals = [100, 80, 70]
    seqs = [_tokens(30 + i, n) for i, n in enumerate(totals)]
    paged = _Paged(CFG, totals)
    got = paged.run(params, seqs, [96, 70, 40], 32)
    for seq, g in zip(seqs, got):
        assert np.abs(g - _reference(params, seq)[0]).max() < TOLERANCE
    # the counts are summed over the rows' passes: pairs of real tokens
    # only, and in each pass no more experts hit than four layers hold
    first = paged.counts[0]
    assert 0 < first[0] <= 3 * 32 * 4 * 4 and 4 * 4 < first[1] <= 3 * 4 * 4


def test_bf16_in_place_of_fp32_fails_the_tolerance(params):
    cfg = _config(jnp.bfloat16)
    low = glm.serving_params(params, cfg)
    seq = _tokens(3, 96)
    got = _Paged(cfg, [96]).run(low, [seq], [64], 32)[0]
    assert np.abs(got - _reference(params, seq)[0]).max() > 50 * TOLERANCE


# -- (b): what is attended --------------------------------------------------

def _one_slice(params, cfg, seq, **kw):
    """The backbone over one slice that is the whole sequence: (logits [T,
    V], allowed [L_full, T, T], routing [L_sparse, T, k])."""
    T = len(seq)
    cache = KVCacheConfig(T // BLOCK + 1, BLOCK)
    pools = glm.init_pools(cfg, cache, 1)
    tables = jnp.arange(1, T // BLOCK + 1, dtype=jnp.int32)[None]

    def run(params, pools):
        x, _, _, _, routing, chosen = glm._paged_backbone(
            params, cfg, jnp.asarray(seq)[None],
            jnp.arange(T, dtype=jnp.int32)[None], jnp.ones((1, T), bool),
            *pools, tables, collect=True, **kw)
        h = glm._norm(cfg, params["final_norm"], x)
        return glm._matmul(h, params["lm_head"])[0], chosen[:, 0], \
            routing[0].reshape(T, -1, cfg.num_experts_per_tok)

    logits, chosen, routing = jax.jit(run)(params, pools)
    return np.asarray(logits), np.asarray(chosen), \
        np.asarray(routing).transpose(1, 0, 2)


def test_shared_layers_attend_the_set_of_the_full_layer_before(params,
                                                                monkeypatch):
    """The reference handed the program's two choices (one a ``full``
    layer) attends, in its three ``shared`` layers, the first of them:
    logits agree to the float32 tolerance, and the choices are the
    reference's own, position for position. Broken on purpose, neither
    holds: a choice not carried from the run of the ``full`` layer into the
    run of ``shared`` layers (they attend nothing), and ``shared`` layers
    that attend the *second* ``full`` layer's choice."""
    seq = _tokens(5, 128)
    got, mine, _ = _one_slice(params, CFG, seq)
    want, own, _ = _reference(params, seq)
    assert (mine == own).all()
    assert (mine[0] != mine[1]).any()
    given = _reference(params, seq, choices=mine)[0]
    assert np.abs(got - given).max() < TOLERANCE
    assert np.abs(got - want).max() < TOLERANCE
    real = jax.lax.scan

    def forgetful(body, state, xs):
        """The scan over a run of layers, its carried choice emptied."""
        if isinstance(state, tuple) and len(state) == 5:
            state = state[:3] + (jnp.zeros_like(state[3]),) + state[4:]
        return real(body, state, xs)

    monkeypatch.setattr(jax.lax, "scan", forgetful)
    broken = _one_slice(params, CFG, seq)[0]
    monkeypatch.undo()
    assert np.abs(broken[:TOPK] - want[:TOPK]).max() > 1e-2
    swapped = _reference(params, seq, choices=mine[::-1])[0]
    assert np.abs(swapped[:TOPK] - want[:TOPK]).max() < TOLERANCE  # all seen
    assert np.abs(swapped[TOPK:] - want[TOPK:]).max() > 1e-2


def test_selection_left_out_or_index_dropped_is_not_the_reference(params):
    """Every cached position attended past ``index_topk`` too, as a model
    with plain latent attention would; and an index that does not outlive
    its slice (the keys of earlier slices gone, so a query chooses among
    its own slice's positions only)."""
    seq = _tokens(4, 112)
    want = _reference(params, seq)[0]
    dense = _config(index_topk=4096)
    got = _Paged(dense, [112]).run(params, [seq], [112], 32)[0]
    assert np.abs(got[:TOPK] - want[:TOPK]).max() < TOLERANCE
    assert np.abs(got[TOPK:] - want[TOPK:]).max() > 1e-2

    paged = _Paged(CFG, [112])
    real = paged.call

    def forgetful(params, tok, pos, msk):
        paged.pools = [paged.pools[0], jnp.zeros_like(paged.pools[1])]
        return real(params, tok, pos, msk)

    paged.call = forgetful
    got = paged.run(params, [seq], [112], 32)[0]
    assert np.abs(got[:32] - want[:32]).max() < TOLERANCE
    assert np.abs(got[64:] - want[64:]).max() > 1e-2


def test_absorbed_form_is_the_expanded_form():
    """Random latents and queries in float32, a random allowed set of 24
    positions a query: folding ``W_UK`` into the query and applying
    ``W_UV`` after the sum over padded rows gives what forming every
    head's keys and values gives (sums in another order: 1e-5 of outputs
    of size 1), for the slice form over shuffled blocks and, a query at a
    time, for the decode form over gathered rows."""
    B, T, H, nope, rope, rank, v, R = 2, 48, 4, 12, 4, 16, 16, 128
    rng = np.random.default_rng(0)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    q_n, q_r = normal(B, T, H, nope), normal(B, T, H, rope)
    c, k_r = normal(B, T, rank), normal(B, T, rope)
    w_uk, w_uv = normal(H, nope, rank) * 0.3, normal(H, rank, v) * 0.3
    allowed = np.zeros((B, T, T), bool)
    for b in range(B):
        for t in range(T):
            allowed[b, t, rng.permutation(t + 1)[:24]] = True
    scale = (nope + rope) ** -0.5
    want = np.asarray(_mla_expanded(q_n, q_r, c, k_r, w_uk, w_uv,
                                       jnp.asarray(allowed), scale=scale))
    W = T // BLOCK
    tables = rng.permutation(np.arange(1, 3 * W + 1))[:B * W].reshape(
        B, W).astype(np.int32)
    rows = np.zeros((3 * W + 1, BLOCK, R), np.float32)
    for b in range(B):
        rows[tables[b], :, :rank + rope] = np.concatenate(
            [c[b], k_r[b]], -1).reshape(W, BLOCK, rank + rope)
    rows = jnp.asarray(rows)
    q = mla.absorbed_query(q_n, q_r, w_uk, R, jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    got = mla.expand_values(mla.mla_slice(
        q, rows, jnp.asarray(tables), jnp.asarray(allowed), pos,
        jnp.ones((B, T), bool), scale=scale, key_blocks=2), w_uv)
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    for t in (0, 23, 24, 47):
        chosen = np.stack([np.pad(np.flatnonzero(allowed[b, t]),
                                  (0, 24))[:24] for b in range(B)])
        valid = np.arange(24)[None] < allowed[:, t].sum(-1)[:, None]
        row_ids = np.take_along_axis(tables, chosen // BLOCK, 1) * BLOCK \
            + chosen % BLOCK
        step = mla.expand_values(mla.mla_decode(
            q[:, t:t + 1], rows.reshape(-1, R), jnp.asarray(row_ids),
            jnp.asarray(valid), scale=scale), w_uv)
        assert np.abs(np.asarray(step[:, 0]) - want[:, t]).max() < 1e-5


def test_bf16_choices_overlap_the_references_and_attention_agrees_given_them(
        params):
    """One ``full`` layer's attention in the serving path's type against
    the reference's in float32, over a random residual stream (a whole
    model in bfloat16 is no tight comparison at this size: a routing flip
    at a near-tie moves a toy logit by 1). Left to its own float32 scores
    the reference chooses the same positions but where two scores lie
    within bfloat16's rounding (an indexer of 2 heads x 8 here): 97 % of a
    query's 32 chosen positions are shared on average and 85 % at the
    least (measured 99.7-99.9 % and 96.9 %; the CPU's bfloat16 sums are not
    the same from run to run). Handed the program's choice, the
    reference's attention agrees to bfloat16's rounding: 2e-2 of a layer's
    output of size 3.7 (measured 4-7e-3), where attending its own choice
    reads 5e-2 and more at the queries whose choice flipped."""
    cfg = _config(jnp.bfloat16)
    T = 128
    lp32 = jax.tree.map(lambda w: w[0], params["dense_full"])
    lp = jax.tree.map(lambda w: w[0],
                      glm.serving_params(params, cfg)["dense_full"])
    x = jnp.asarray(np.random.default_rng(6).normal(size=(T, 64)),
                    jnp.float32)
    cache = KVCacheConfig(T // BLOCK + 1, BLOCK)
    latent, index = glm.init_pools(cfg, cache, 1)
    tables = jnp.arange(1, T // BLOCK + 1, dtype=jnp.int32)[None]
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    real = jnp.ones((1, T), bool)

    def layer(lp, x, latent, index):
        out, _, _, allowed = glm._attention(
            cfg, "dense_full", lp, x[None], pos, real,
            latent[0].reshape(-1, latent.shape[-1]),
            index[0].reshape(-1, index.shape[-1]), 0, 0, tables,
            glm._write_indices(pos, real, tables, BLOCK),
            jnp.zeros((1, T, T), bool))
        return out[0], allowed[0]

    got, mine = map(np.asarray, jax.jit(layer)(lp, x, latent, index))
    c = {**reference.CONSTANTS, **{k: v for k, v in _constants(cfg).items()
                                   if k in reference.CONSTANTS}}
    want, own = map(np.asarray, reference.attention(
        lp32, x, None, c, "f32", True, None))
    t = np.arange(T)
    assert (mine.sum(-1) == np.minimum(t + 1, TOPK)).all()
    assert (own.sum(-1) == np.minimum(t + 1, TOPK)).all()
    shared = (mine & own).sum(-1) / own.sum(-1)
    assert shared.mean() >= 0.97 and shared.min() >= 0.85
    assert (mine != own).any()
    given, _ = reference.attention(lp32, x, None, c, "f32", True,
                                   jnp.asarray(mine))
    assert np.abs(got - np.asarray(given)).max() < 2e-2
    flipped = (mine != own).any(-1)
    assert np.abs(got - want)[flipped].max() > 3e-2
    assert np.abs(got - want)[~flipped].max() < 2e-2
    # while t + 1 <= topk every position is in, the query's own among them
    assert mine[t[:TOPK], t[:TOPK]].all()


def test_a_decode_steps_choice_is_the_slices_choice_for_the_same_query():
    """A decode step is handed positions (``select``) and a slice a mask
    (``select_mask``: the K-th value found bit by bit, no sort): the same
    set, and the set ``lax.top_k`` takes, ties at the K-th value included (scores quantised
    to four values, negative ones and both zeros among them, so that most
    of a row ties)."""
    rng = np.random.default_rng(1)
    S, K = 96, 32
    scores = rng.choice(np.array([-1.5, -0.0, 0.0, 2.5], np.float32),
                        size=(2, 5, S))
    last = np.array([[10, 31, 32, 60, 95]] * 2)
    scores = jnp.where(np.arange(S)[None, None] <= last[..., None],
                       jnp.asarray(scores), -jnp.inf)
    chosen = jax.jit(dsa_index.select, static_argnums=1)(scores, K)
    allowed = jax.jit(dsa_index.select_mask, static_argnums=1)(scores, K)
    assert (np.asarray(_allowed_from_positions(chosen, S))
            == np.asarray(allowed)).all()
    # what lax.top_k takes (-0.0 counted as 0.0), in order of position
    vals, idx = jax.lax.top_k(jnp.where(scores == 0, 0.0, scores), K)
    want = np.sort(np.where(np.asarray(vals) > -np.inf, np.asarray(idx), S),
                   axis=-1)
    assert (np.where(np.asarray(chosen) < 0, S, np.asarray(chosen))
            == want).all()
    assert (np.asarray(allowed).sum(-1) == np.minimum(last + 1, K)).all()
    # exact: nothing left out scores higher than anything chosen, and a
    # tie goes to the lower position
    a, s = np.asarray(allowed), np.asarray(scores)
    for b, t in np.ndindex(2, 5):
        out = ~a[b, t] & (np.arange(S) <= last[b, t])
        if out.any():
            lowest = s[b, t][a[b, t]].min()
            assert s[b, t][out].max() <= lowest
            ties_out = np.flatnonzero(out & (s[b, t] == lowest))
            ties_in = np.flatnonzero(a[b, t] & (s[b, t] == lowest))
            assert not len(ties_out) or ties_in.max() < ties_out.min()


def test_the_programs_record_of_its_routing_is_what_the_reference_replays(
        params):
    """Every program returns, last, each token's chosen experts in every
    sparse layer (``PagedModel.token_records``). In float32 they are the
    experts the reference's own scores choose, and the reference handed
    them (``forward(routing=)``) computes what it computes alone. Handed
    another choice it computes something else, by far more than rounding:
    the experts given are taken, whatever the scores say; and a -1 leaves a
    position to the reference's own."""
    seq = _tokens(6, 96)
    got, _, mine = _one_slice(params, CFG, seq)
    want, _, own = _reference(params, seq)
    assert mine.shape == own.shape == (4, 96, CFG.num_experts_per_tok)
    assert (np.sort(mine, -1) == np.sort(own, -1)).all()
    replayed = _reference(params, seq, routing=mine)[0]
    assert np.abs(replayed - want).max() < TOLERANCE
    # every token sent to the held experts 4..7: not what the scores chose
    held = np.broadcast_to(np.arange(4, 8), mine.shape)
    assert (np.sort(mine, -1) != held).any(-1).mean() > 0.9
    forced = _reference(params, seq, routing=held)[0]
    assert np.abs(forced - want).max() > 1e-2
    half = np.where(np.arange(96)[None, :, None] < 48, held, -1)
    mixed = _reference(params, seq, routing=half)[0]
    assert np.abs(mixed[:48] - forced[:48]).max() < TOLERANCE
    # past 48 a position's own FFN is the reference's again; what it attends
    # of the first 48 positions' latents still differs
    assert np.abs(mixed[48:] - want[48:]).max() \
        < np.abs(forced[48:] - want[48:]).max()


# -- (d) (e): the expert layer ----------------------------------------------

def _expert_layer(params, layer=0):
    """One sparse layer's leaves, no stack dimension, in float32."""
    return jax.tree.map(lambda w: w[layer], params["sparse_shared"])


def _routed(lp, h, cfg=CFG, **kw):
    kw = {**dict(first_expert=cfg.first_expert, n_held=cfg.n_routed_experts,
                 n_experts=cfg.published_n_routed_experts,
                 k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
                 compute_dtype=jnp.float32), **kw}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(moe, "PAIR_TILE", 8)   # several tiles an expert
        y, counts, _ = jax.jit(
            lambda lp, h: moe.routed_experts(lp, h, **kw))(lp, h)
    return np.asarray(y), np.asarray(counts)


def test_the_bias_chooses_and_does_not_weigh(params):
    """With a selection bias of size 0.1 a quarter and more of the tokens
    choose other experts than their scores alone would; the gates of the
    chosen are ``2.5 s / sum of the chosen s`` all the same, the bias
    nowhere in them, and sum to 2.5."""
    router = _expert_layer(params)["router"]
    h = jnp.asarray(np.random.default_rng(2).normal(size=(64, 64)),
                    jnp.float32)
    experts, gates = moe.route(router, h, k=4, scale=2.5)
    plain, plain_gates = moe.route(
        {**router, "bias": jnp.zeros_like(router["bias"])}, h, k=4,
        scale=2.5)
    moved = (np.sort(experts, -1) != np.sort(plain, -1)).any(-1)
    assert moved.mean() > 0.25
    s = np.asarray(jax.nn.sigmoid(h @ router["kernel"]))
    chosen = np.take_along_axis(s, np.asarray(experts), -1)
    assert np.abs(gates - 2.5 * chosen / chosen.sum(-1, keepdims=True)
                  ).max() < 1e-6
    assert np.abs(np.asarray(gates).sum(-1) - 2.5).max() < 1e-5
    # a token whose choice did not move has the gates it had
    same = ~moved & (np.asarray(experts) == np.asarray(plain)).all(-1)
    assert same.any()
    assert np.abs(np.asarray(gates - plain_gates)[same]).max() < 1e-6


@pytest.mark.parametrize("where,pairs", [("all-held", 96 * 4), ("none", 0),
                                         ("one-expert", 96)],
                         ids=lambda v: str(v))
def test_no_pair_is_dropped_whatever_the_routing(params, where, pairs):
    """A selection bias that sends every token's four pairs to the four
    held experts (the worst case the static shapes are sized for: 384
    pairs, 96 to each expert, twelve tiles of 8 each), one that sends none
    (zeros, not NaN, and no expert read), and one that sends every token
    to one held expert and three absent ones: the routed part is the
    reference's, so no pair was dropped and none counted twice. 97 tokens,
    the last of them padding."""
    lp = _expert_layer(params)
    bias = np.full(16, -5.0, np.float32)
    bias[{"all-held": slice(4, 8), "none": slice(8, 12),
          "one-expert": [5, 9, 10, 11]}[where]] = 5.0
    lp = {**lp, "router": {**lp["router"], "bias": jnp.asarray(bias)}}
    x = jnp.asarray(np.random.default_rng(3).normal(size=(97, 64)),
                    jnp.float32)
    h = glm._norm(CFG, lp["ln2"], x, jnp.float32)
    y, counts = _routed(lp, h, token_mask=jnp.arange(97) < 96)
    no_shared = jax.tree.map(jnp.zeros_like, {k: lp[k] for k in (
        "shared_gate", "shared_up", "shared_down")})
    want = reference.layer_ffn(
        {**lp, **no_shared}, x, experts=range(4, 8),
        **{k: v for k, v in _constants(CFG).items()
           if k not in ("mlp_types", "indexer_types")}) - np.asarray(x)
    assert np.isfinite(y).all()
    assert counts[0] == pairs
    assert counts[1] == {"all-held": 4, "none": 0, "one-expert": 1}[where]
    assert (y[96] == 0).all()                        # padding routes nowhere
    assert np.abs(y[:96] - want[:96]).max() < 2e-5
    assert (np.abs(want[:96]).max() > 0.1) == (where != "none")
    if where == "none":
        assert (y == 0).all()


def test_the_shares_of_an_expert_parallel_group_add_up_to_the_layer(params):
    """The test that ties the share to the model: the routed parts that
    the four members of a group of four compute, each from
    ``routed_experts`` with its own ``first_expert`` and its own four
    experts' weights, plus the shared expert once, are the reference's
    layer over all sixteen experts. Float32; 2e-5 of outputs of size 1 is
    the order of the sums."""
    lp = _expert_layer(params)
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    whole = {name: {"kernel": 0.125 * jax.random.normal(
        key, (16, *lp[name]["kernel"].shape[1:]))}
        for name, key in zip(("experts_gate", "experts_up", "experts_down"),
                             keys)}
    x = jnp.asarray(np.random.default_rng(4).normal(size=(80, 64)),
                    jnp.float32)
    constants = {k: v for k, v in _constants(CFG).items()
                 if k not in ("mlp_types", "indexer_types")}
    want = reference.layer_ffn({**lp, **whole}, x, experts=range(16),
                               **constants)
    h = glm._norm(CFG, lp["ln2"], x, jnp.float32)
    total, pairs = np.asarray(x + glm._swiglu(CFG, lp, h, "shared")), 0
    for first in range(0, 16, 4):
        share = {**lp, **{name: {"kernel": w["kernel"][first:first + 4]}
                          for name, w in whole.items()}}
        y, counts = _routed(share, h, first_expert=first)
        # a member alone is not the layer
        assert np.abs(y).max() > 1e-2
        total, pairs = total + y, pairs + counts[0]
    assert pairs == 80 * 4                  # every pair fell to one member
    assert np.abs(total - want).max() < 2e-5
    # and the reference's own share is the member's
    mine = reference.layer_ffn(lp, x, experts=range(4, 8), **constants)
    y, _ = _routed(lp, h)
    assert np.abs(np.asarray(x + glm._swiglu(CFG, lp, h, "shared")) + y
                  - mine).max() < 2e-5


def test_the_normaliser_runs_over_all_the_chosen_not_the_held(params):
    lp = _expert_layer(params)
    h = jnp.asarray(np.random.default_rng(5).normal(size=(64, 64)),
                    jnp.float32)
    experts, gates = moe.route(lp["router"], h, k=4, scale=2.5)
    held = (np.asarray(experts) >= 4) & (np.asarray(experts) < 8)
    some = held.any(-1) & ~held.all(-1)
    assert some.any()
    # what the held experts of such a token weigh is less than the scale
    assert (np.where(held, gates, 0).sum(-1)[some] < 2.5 - 1e-3).all()


# -- the layout, the engine -------------------------------------------------

@pytest.mark.parametrize("total", [1, 16, 17, 32, 33, 100, 256])
def test_reservation_is_one_growing_kind_and_rows_are_by_length(total):
    cache = KVCacheConfig(40, BLOCK)
    layout = LatentIndexLayout(cache, 256, topk=32)
    assert layout.kinds == ("kv",)
    blocks = -(-total // BLOCK)
    assert layout.blocks_by_kind(total) == (blocks,)
    assert layout.blocks_needed(total) == blocks
    assert layout.table_width == 16
    row = np.zeros(layout.table_width, np.int32)
    layout.lay_table(row, list(range(5, 5 + blocks)))
    assert list(row[:blocks]) == list(range(5, 5 + blocks))
    assert (row[blocks:] == 0).all()
    assert layout.attended_rows(total) == (total, min(total, 32))
    assert layout.step_rows([total, 40], 2) == (total + 40,
                                                min(total, 32) + 32)
    assert layout.row_args == ("kv_rows", "selected_rows")
    with pytest.raises(ValueError, match="whole cache blocks"):
        layout.check_prefill(32, 24)
    layout.check_prefill(32, 32)


def test_pools_are_found_by_one_block_id_and_runs_are_by_kind():
    cache = KVCacheConfig(40, BLOCK)
    latent, index = glm.init_pools(CFG, cache, 2)
    # 16 + 4 numbers a position, in one whole lane tile; keys in full
    # layers only
    assert latent.shape == (5, 40, BLOCK, 128) and CFG.row_width == 128
    assert index.shape == (2, 40, BLOCK, 8)
    assert CFG.kinds == ("dense_full", "sparse_shared", "sparse_shared",
                         "sparse_shared", "sparse_full")
    assert CFG.runs() == [("dense_full", 0, 1, 0, 0),
                          ("sparse_shared", 0, 3, 1, 1),
                          ("sparse_full", 0, 1, 4, 1)]
    published = glm.GLMMoeDsaConfig()
    assert published.n_layers == 78 and published.n_full == 3 + 18
    assert published.row_width == 640
    assert [r[:3] for r in published.runs()[:4]] == [
        ("dense_full", 0, 3), ("sparse_shared", 0, 3),
        ("sparse_full", 0, 1), ("sparse_shared", 3, 6)]
    with pytest.raises(ValueError, match="choose its own"):
        _config(indexer_types=("shared",) * 5)
    with pytest.raises(ValueError, match="not among the published"):
        _config(first_expert=14)


def _engine(params, **kw):
    kw.setdefault("buckets", BucketSpec.build(2, 32, min_prefill_len=16))
    kw.setdefault("cache", KVCacheConfig(34, BLOCK))
    kw.setdefault("chunk_prefill_len", 32)
    return InferenceEngine(params, CFG, **kw)


def test_engine_serves_the_reference_tokens_and_reads_the_devices_counts(
        params):
    """Through ``InferenceEngine.submit``: chunked prefill in slices of 32
    between decode steps, two rows a batch, prompts that end under and past
    ``index_topk``. Five requests over two batch rows, so blocks are used
    again by a later request. Every served token is the reference's first;
    nothing is outstanding at the end. The decode step's spans carry the
    rows from the lengths, and ``decode_commit`` and ``serving_prefill``
    what only the device knew, which the counters add up; a result carries
    the experts each of its positions was routed to."""
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
        assert registry.gauge("serving_kv_blocks_in_use",
                              labels={"kind": "kv"}).value == 0
    for p, (_, m), r in zip(prompts, sizes, results):
        assert r.finish_reason == "length" and len(r.tokens) == m
        logits, _, routed = _reference(params, p + r.tokens)
        at = logits[len(p) - 1:-1]
        assert (at.max(axis=-1) - at[np.arange(m), r.tokens]
                ).max() < TOLERANCE
        # the request's record: the experts of every position that was
        # run (all but the last token), slices and steps in order
        n = len(p) + m - 1
        assert r.token_records.shape == (n, 4 * CFG.num_experts_per_tok)
        mine = r.token_records.reshape(n, 4, -1).transpose(1, 0, 2)
        assert (np.sort(mine, -1) == np.sort(routed[:, :n], -1)).all()
    events = tracer.events()
    steps = [e["args"] for e in events
             if e.get("name") == "serving_decode_step"]
    assert steps and all({"kv_rows", "selected_rows"} <= set(a)
                         for a in steps)
    assert all(a["selected_rows"] <= a["kv_rows"] for a in steps)
    assert any(a["selected_rows"] < a["kv_rows"] for a in steps)
    assert any(a["selected_rows"] == a["kv_rows"] for a in steps)
    for name, arg in zip(glm.PAGED.row_counters, eng._layout.row_args):
        assert registry.counter(name).value == sum(a[arg] for a in steps)
    commits = [e["args"] for e in events if e.get("name") == "decode_commit"]
    prefills = [e["args"] for e in events
                if e.get("name") == "serving_prefill"]
    assert len(commits) == len(steps)
    for a in commits + prefills:
        assert 0 <= a["expert_hits"] <= 4 * 4
        assert a["expert_hits"] <= a["expert_pairs"]
    # a decode step of r rows routes r x 4 pairs in each of four layers;
    # a quarter of them fall here were the routing even
    assert all(a["expert_pairs"] <= a["rows"] * 4 * 4 for a in commits)
    assert 0.1 < sum(a["expert_pairs"] for a in commits) \
        / sum(a["rows"] * 4 * 4 for a in commits) < 0.5
    for name in glm.PAGED.step_counters:
        assert registry.counter(f"serving_{name}_total").value \
            == sum(a[name] for a in commits + prefills)


def test_engine_refuses_by_name_what_this_cache_cannot_serve(params):
    with pytest.raises(ValueError, match="glm_moe_dsa.*prefix_cache"):
        _engine(params, prefix_cache=True)
    with pytest.raises(ValueError, match="glm_moe_dsa.*speculative"):
        _engine(params, speculative_k=2, draft_params=params, draft_cfg=CFG)
    with pytest.raises(ValueError, match="whole cache blocks"):
        _engine(params, buckets=BucketSpec.build(2, 32, min_prefill_len=8),
                chunk_prefill_len=8)
    assert glm.PAGED.unsupported == ("prefix_cache", "kv_store",
                                     "speculative")
    assert glm.PAGED.pool_names == ("latent_pool", "index_pool")
    assert glm.PAGED.step_counters == ("expert_pairs", "expert_hits")
    assert glm.PAGED.token_records


def test_other_families_name_no_step_counters():
    """Their programs return logits and pools and no more, as the parent's
    did; the engine then reads the sampled tokens as it did."""
    from determined_clone_tpu.models import evabyte, gpt, minicpm_sala

    for paged in (gpt.PAGED, evabyte.PAGED, minicpm_sala.PAGED):
        assert paged.step_counters == () and not paged.token_records


def test_serving_params_are_bf16_matrices_and_fp32_vectors_and_router(
        params):
    served = glm.serving_params(params, _config(jnp.bfloat16))
    for path, leaf in jax.tree_util.tree_leaves_with_path(served):
        name = jax.tree_util.keystr(path)
        matrix = ("kernel" in name or "table" in name) \
            and "router" not in name
        assert leaf.dtype == (jnp.bfloat16 if matrix else jnp.float32), name
    again = glm.serving_params(served, _config(jnp.bfloat16))
    assert all(a is b for a, b in zip(jax.tree.leaves(served),
                                      jax.tree.leaves(again)))
