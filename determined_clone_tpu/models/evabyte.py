"""EvaByte — a byte-level decoder with EVA attention, on the serving path.

The block, from the published configuration
(https://huggingface.co/EvaByte/EvaByte/blob/main/config.json) and the EVA /
EvaByte description; ``benchmarks/reference/evabyte.py`` is the same
mathematics over a whole sequence, with no cache:

- pre-norm decoder, the residual stream fp32 (``fp32_skip_add``); RMSNorm
  multiplies by ``1 + w`` (``norm_add_unit_offset``); no biases;
- q, k, v of H heads, rotary (half-split, base ``rope_theta``, absolute
  positions) on q and k;
- **EVA attention.** Token t lies in window ``t // window`` and chunk
  ``t // chunk``. It attends exactly to the tokens ``j <= t`` of its own
  window and, under the same softmax, to one summary ``(K_c, V_c)`` of every
  chunk of every *earlier* window (``ops/attention.py:eva_chunk_summary``,
  pooled from rotated keys by two learned vectors per head, ``phi`` and
  ``mu``). Windows are blocks, not sliding: the first token of a window sees
  itself and summaries only. The softmax is fp32 (``mixedp_attn``);
- SwiGLU MLP; after the last layer RMSNorm and an untied head of
  ``n_pred_heads`` x vocab_size fp32 logits (``fp32_logits``): head i
  predicts byte t + 1 + i. Generation commits head 0's byte; drafting from
  the other heads is ROADMAP M6.

**The cache** is of two kinds in one pool (``serving/kv_cache.py:
WindowSummaryLayout``): a ring of exact K/V rows for the current window and
one summary row per finished chunk, so a sequence holds and a step reads
``window + T / chunk`` rows, not ``T``. ``forward_paged`` covers a prefill
slice (inside one window, starting on a chunk boundary) and a decode step:
scatter the new rows into the ring; pool and scatter the summary of every
chunk the call completes (the chunk is one block of the ring); then one
joint softmax over what the row attends. A slice gathers the ring and the
summary blocks, by block, and masks (``eva_attention``). A decode step
gathers nothing: it reads the pool through the table at each row's real
window and summary lengths, in one Pallas kernel
(``ops/eva_paged_attention.py``) that streams only the blocks those rows
fill, K and V each once; shapes the kernel cannot take (``fits``) fall back
to the slice's gathered form.

**Weights** are held in ``param_dtype`` (bfloat16) and read as they lie: no
program casts a matrix. The norm scales, ``phi``, ``mu`` and the head are
fp32 (a few MB), so ``fp32_logits`` needs no cast either.

There is no training path yet (ROADMAP B-M): ``apply`` is the uncached
forward for tests.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from determined_clone_tpu.models.paged import PagedModel, run_rows
from determined_clone_tpu.ops.attention import (
    eva_attention,
    eva_chunk_summary,
    rotary_embedding,
)
from determined_clone_tpu.ops.layers import rmsnorm

Params = Dict[str, Any]

# a prefill call over more tokens than this runs a row at a time, so that
# its fp32 temporaries are one row's (weights are re-read once per row: a
# tenth of a 2048-token slice's time at the published widths)
PREFILL_TOKENS_PER_PASS = 4096


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    """The source's sizes under this repo's names (its key in brackets)."""
    vocab_size: int = 320
    n_layers: int = 32                 # num_hidden_layers
    d_model: int = 4096                # hidden_size
    n_heads: int = 32                  # num_attention_heads
    d_ff: int = 11008                  # intermediate_size
    window_size: int = 2048
    chunk_size: int = 16
    max_seq_len: int = 32768           # max_position_embeddings
    n_pred_heads: int = 8              # num_pred_heads
    rope_theta: float = 1e5
    rms_norm_eps: float = 1e-5
    init_std: float = 0.01275
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @staticmethod
    def tiny() -> "EvaByteConfig":
        return EvaByteConfig(n_layers=2, d_model=64, n_heads=4, d_ff=128,
                             window_size=64, chunk_size=8, max_seq_len=256)

    def paged_model(self) -> PagedModel:
        """The family on the serving path (``models/paged.py``)."""
        return PAGED


def init(key: jax.Array, cfg: EvaByteConfig) -> Params:
    """Stacked-block params: matrices normal(0, init_std) in
    ``param_dtype``, norm scales 0 (the norm multiplies by 1 + w), ``phi``
    and ``mu`` a standard normal clipped to [-1, 1] times init_std (how the
    source is understood to draw them), the head fp32."""
    L, D, F, H = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.n_heads
    keys = iter(jax.random.split(key, 11))
    f32 = jnp.float32

    def matrices(shape):
        # a layer at a time: the fp32 draw of all layers of the published
        # widths at once would be several GB beside the weights
        return jax.lax.map(
            lambda k: (cfg.init_std * jax.random.normal(k, shape, f32)
                       ).astype(cfg.param_dtype),
            jax.random.split(next(keys), L))

    def adaptive():
        return cfg.init_std * jnp.clip(
            jax.random.normal(next(keys), (L, H, cfg.head_dim), f32), -1, 1)

    return {
        "embed": {"table": (cfg.init_std * jax.random.normal(
            next(keys), (cfg.vocab_size, D), f32)).astype(cfg.param_dtype)},
        "blocks": {
            "ln1": {"scale": jnp.zeros((L, D), f32)},
            "attn_q": {"kernel": matrices((D, D))},
            "attn_k": {"kernel": matrices((D, D))},
            "attn_v": {"kernel": matrices((D, D))},
            "attn_out": {"kernel": matrices((D, D))},
            "eva": {"phi": adaptive(), "mu": adaptive()},
            "ln2": {"scale": jnp.zeros((L, D), f32)},
            "mlp_gate": {"kernel": matrices((D, F))},
            "mlp_up": {"kernel": matrices((D, F))},
            "mlp_down": {"kernel": matrices((F, D))},
        },
        "final_norm": {"scale": jnp.zeros((D,), f32)},
        "lm_head": {"kernel": cfg.init_std * jax.random.normal(
            next(keys), (D, cfg.n_pred_heads * cfg.vocab_size), f32)},
    }


def _norm(cfg: EvaByteConfig, p: Params, x: jax.Array) -> jax.Array:
    """x fp32 -> the matmuls' operand dtype."""
    return rmsnorm(p, x, cfg.rms_norm_eps, unit_offset=True,
                   dtype=cfg.compute_dtype)


def _matmul(x: jax.Array, p: Params) -> jax.Array:
    """x @ kernel as the kernel lies, summed and returned in fp32."""
    return jnp.matmul(x, p["kernel"], preferred_element_type=jnp.float32)


def _qkv(cfg: EvaByteConfig, bp: Params, x: jax.Array,
         positions: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, T, _ = x.shape
    H, hd = cfg.n_heads, cfg.head_dim
    h = _norm(cfg, bp["ln1"], x)
    q = rotary_embedding(_matmul(h, bp["attn_q"]).reshape(B, T, H, hd),
                         positions, base=cfg.rope_theta)
    k = rotary_embedding(_matmul(h, bp["attn_k"]).reshape(B, T, H, hd),
                         positions, base=cfg.rope_theta)
    v = _matmul(h, bp["attn_v"]).reshape(B, T, H, hd)
    dt = cfg.compute_dtype
    return q.astype(dt), k.astype(dt), v.astype(dt)


def _mlp(cfg: EvaByteConfig, bp: Params, x: jax.Array) -> jax.Array:
    with jax.named_scope("mlp"):
        h = _norm(cfg, bp["ln2"], x)
        act = jax.nn.silu(_matmul(h, bp["mlp_gate"])) * _matmul(
            h, bp["mlp_up"])
        return x + _matmul(act.astype(cfg.compute_dtype), bp["mlp_down"])


def _heads(cfg: EvaByteConfig, params: Params, x: jax.Array) -> jax.Array:
    """x [..., D] fp32, normed -> logits [..., n_pred_heads, V] fp32."""
    logits = jnp.matmul(x, params["lm_head"]["kernel"],
                        precision=jax.lax.Precision.HIGHEST)
    return logits.reshape(*x.shape[:-1], cfg.n_pred_heads, cfg.vocab_size)


# ---------------------------------------------------------------------------
# the uncached forward (tests)
# ---------------------------------------------------------------------------

def eva_mask(cfg: EvaByteConfig, positions: jax.Array, n_window_slots: int,
             n_summary_slots: int) -> jax.Array:
    """[..., T, n_window_slots + n_summary_slots] bool for queries at
    ``positions`` [..., T]: window slot j (position ``j`` of the query's
    own window) is seen iff ``j <= t % window``; summary slot c (chunk c of
    the sequence) iff its window is finished, ``c < chunks_per_window *
    (t // window)``."""
    W = cfg.window_size
    t = positions[..., None]
    window = jnp.arange(n_window_slots) <= t % W
    summary = jnp.arange(n_summary_slots) < (t // W) * (W // cfg.chunk_size)
    return jnp.concatenate([window, summary], axis=-1)


def apply(params: Params, cfg: EvaByteConfig, tokens: jax.Array
          ) -> jax.Array:
    """tokens int32 [B, T], T whole chunks -> logits fp32
    [B, T, n_pred_heads, V]: the whole sequence at once, a window at a
    time, summaries pooled from the sequence itself. No cache."""
    B, T = tokens.shape
    W, C, D = cfg.window_size, cfg.chunk_size, cfg.d_model
    if T % C:
        raise ValueError(f"{T} positions are not whole chunks of {C}")
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"]["table"], tokens,
                     axis=0).astype(jnp.float32)

    def block(x, bp):
        with jax.named_scope("attn"):
            q, k, v = _qkv(cfg, bp, x, positions)
            k, v = k.reshape(B, T, D), v.reshape(B, T, D)
            sum_k, sum_v = eva_chunk_summary(
                k.reshape(B, T // C, C, D), v.reshape(B, T // C, C, D),
                bp["eva"]["phi"], bp["eva"]["mu"])
            out = []
            for lo in range(0, T, W):
                hi = min(lo + W, T)
                ctx_k = jnp.concatenate([k[:, lo:hi], sum_k], axis=1)
                ctx_v = jnp.concatenate([v[:, lo:hi], sum_v], axis=1)
                mask = eva_mask(cfg, positions[:, lo:hi], hi - lo, T // C)
                out.append(eva_attention(q[:, lo:hi], ctx_k, ctx_v, mask,
                                         q_block=hi - lo))
            attn = jnp.concatenate(out, axis=1).reshape(B, T, D)
            x = x + _matmul(attn, bp["attn_out"])
        return _mlp(cfg, bp, x), None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    with jax.named_scope("logits"):
        x = rmsnorm(params["final_norm"], x, cfg.rms_norm_eps,
                    unit_offset=True)
        return _heads(cfg, params, x)


# ---------------------------------------------------------------------------
# the paged forward (serving)
# ---------------------------------------------------------------------------

def _block_paged(cfg: EvaByteConfig, bp: Params, x: jax.Array,
                 positions: jax.Array, k_rows: jax.Array, v_rows: jax.Array,
                 scatter_idx: jax.Array, chunk_blocks: jax.Array,
                 summary_idx: jax.Array, gather_blocks: jax.Array,
                 attend: Any):
    """One block over the two-kind cache. x: [B, T, D] fp32, the new tokens
    only. k_rows/v_rows: the whole pool as rows [L*N*bs, R]. All indices
    are this layer's: ``scatter_idx`` where the new K/V go, [B*T] rows or,
    for a slice of whole blocks, [B*T/bs] blocks (past the pool =
    dropped); ``chunk_blocks`` [B, nC] the ring blocks holding the
    chunks this call may complete and ``summary_idx`` [B*nC] the rows
    their summaries go to (past the pool where the chunk is not completed
    or not reserved); ``gather_blocks`` [B, Wt] the ring then the summary
    blocks, which ``attend(q, k blocks, v blocks, gather_blocks)`` reads
    (``_paged_backbone`` says how: the kernel, or gather and mask)."""
    B, T, D = x.shape
    R = k_rows.shape[1]
    bs = cfg.chunk_size
    pad = ((0, 0), (0, R - D))  # columns D..R stay zero

    with jax.named_scope("attn"):
        q, k, v = _qkv(cfg, bp, x, positions)
        with jax.named_scope("kv_cache"):
            # a slice of whole blocks is written a block a piece, as it is
            # gathered (2048 single rows took 0.6 ms a layer on v5e)
            piece = (bs, R) if T % bs == 0 else (R,)
            k_rows = k_rows.reshape(-1, *piece).at[scatter_idx].set(
                jnp.pad(k.reshape(B * T, D), pad).reshape(-1, *piece),
                mode="drop").reshape(-1, R)
            v_rows = v_rows.reshape(-1, *piece).at[scatter_idx].set(
                jnp.pad(v.reshape(B * T, D), pad).reshape(-1, *piece),
                mode="drop").reshape(-1, R)
        with jax.named_scope("eva_summarize"):
            with jax.named_scope("kv_cache"):
                chunk_k = k_rows.reshape(-1, bs, R)[chunk_blocks]
                chunk_v = v_rows.reshape(-1, bs, R)[chunk_blocks]
            sum_k, sum_v = eva_chunk_summary(   # [B, nC, bs, R] -> [B, nC, R]
                chunk_k, chunk_v, bp["eva"]["phi"], bp["eva"]["mu"])
            with jax.named_scope("kv_cache"):
                k_rows = k_rows.at[summary_idx].set(
                    sum_k.reshape(-1, R), mode="drop")
                v_rows = v_rows.at[summary_idx].set(
                    sum_v.reshape(-1, R), mode="drop")
        attn = attend(q, k_rows.reshape(-1, bs, R),
                      v_rows.reshape(-1, bs, R), gather_blocks)
        x = x + _matmul(attn.reshape(B, T, D), bp["attn_out"])
    return _mlp(cfg, bp, x), k_rows, v_rows


def decode_rows(cfg: EvaByteConfig, positions: jax.Array,
                token_mask: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """What a decode step's queries attend, from its ``positions`` and
    ``token_mask`` [B, 1]: int32 [B] rows of the ring (the step's own row
    among them: it is written before it is read) and summary rows, each
    from its table's first; both 0 for a row with no real token. The
    numbers ``WindowSummaryLayout.attended_rows`` gives the step's span."""
    W = cfg.window_size
    pos, live = positions[:, 0], token_mask[:, 0]
    return (jnp.where(live, pos % W + 1, 0).astype(jnp.int32),
            jnp.where(live, pos // W * (W // cfg.chunk_size),
                      0).astype(jnp.int32))


def _paged_backbone(params: Params, cfg: EvaByteConfig, tokens: jax.Array,
                    positions: jax.Array, token_mask: jax.Array,
                    k_pool: jax.Array, v_pool: jax.Array,
                    block_tables: jax.Array):
    """Embed -> paged stack -> final norm: ``(x [B, T, D] fp32, k_pool,
    v_pool)``. The pools ride the layer scan as rows and are updated in
    place, as in ``models/gpt.py``."""
    B, T = tokens.shape
    L, N, bs, R = k_pool.shape
    W, C = cfg.window_size, cfg.chunk_size
    if bs != C:
        raise ValueError(f"pool block {bs} is not the chunk {C}")
    WB = W // bs                           # entries of the ring
    SB = block_tables.shape[1] - WB        # entries of the summary table
    ring, summaries = block_tables[:, :WB], block_tables[:, WB:]
    nowhere = L * N * bs                   # a row no layer's offset reaches

    # where the new rows go: single rows, or whole blocks where the slice
    # is whole blocks (it starts on a block boundary and its real tokens
    # come first, so a block's first token says whether it holds any; the
    # padding rows of a row's last block land in slots its sequence has not
    # reached, which are masked until a later call writes them)
    by_block = T % bs == 0
    at, real = ((positions[:, ::bs], token_mask[:, ::bs]) if by_block
                else (positions, token_mask))
    slot = at % W
    blk = jnp.take_along_axis(ring, slot // bs, axis=1)
    scatter_idx = jnp.where(real, blk if by_block else blk * bs + slot % bs,
                            L * N if by_block else nowhere).reshape(-1)

    # the chunks this call's tokens lie in (a slice starts on a chunk
    # boundary, a decode step is one token), and which of them it
    # completes: those whose last position it writes
    nC = -(-T // C)
    first, n_real = positions[:, :1], jnp.sum(token_mask, axis=1)[:, None]
    chunk = first // C + jnp.arange(nC)[None]                     # [B, nC]
    last = chunk * C + C - 1
    completed = (last >= first) & (last < first + n_real)
    chunk_blocks = jnp.take_along_axis(ring, chunk % WB, axis=1)
    entry = jnp.take_along_axis(summaries,
                                jnp.minimum(chunk // bs, SB - 1), axis=1)
    reserved = (chunk // bs < SB) & (entry >= 0)   # -1: not this request's
    summary_idx = jnp.where(completed & reserved, entry * bs + chunk % bs,
                            nowhere).reshape(B * nC)

    gather_blocks = jnp.maximum(block_tables, 0)
    attend = None
    if T == 1:
        # imported where a decode step is traced, as models/gpt.py imports
        # its kernels: a module with Pallas kernels costs a process a second
        from determined_clone_tpu.ops import eva_paged_attention as eva_paged

        if eva_paged.fits(block_tables.shape[1], bs, cfg.n_heads, R,
                          k_pool.dtype):
            rows = decode_rows(cfg, positions, token_mask)

            def attend(q, k_blocks, v_blocks, blocks):
                # through the table, only the blocks the lengths reach
                with jax.named_scope("eva_attn"):
                    return eva_paged.eva_paged_attention(
                        q, k_blocks, v_blocks, blocks, *rows,
                        window_blocks=WB)
    if attend is None:
        mask = eva_mask(cfg, positions, W, SB * bs) & token_mask[:, :, None]

        def attend(q, k_blocks, v_blocks, blocks):
            with jax.named_scope("kv_cache"):
                # by block, as models/gpt.py gathers: the ring's slot j is
                # position j of the window, summary slot c is chunk c
                ctx_k = k_blocks[blocks].reshape(B, -1, R)
                ctx_v = v_blocks[blocks].reshape(B, -1, R)
            with jax.named_scope("eva_attn"):
                return eva_attention(q, ctx_k, ctx_v, mask)

    with jax.named_scope("embed"):
        x = jnp.take(params["embed"]["table"], tokens,
                     axis=0).astype(jnp.float32)

    def scan_body(carry, layer_in):
        x, k_rows, v_rows = carry
        bp, first_block = layer_in
        off = first_block * bs

        def here(idx):  # ``nowhere`` stays nowhere under any offset
            return jnp.where(idx < nowhere, off + idx, nowhere)

        x, k_rows, v_rows = _block_paged(
            cfg, bp, x, positions, k_rows, v_rows,
            jnp.where(scatter_idx < L * N, first_block + scatter_idx, L * N)
            if by_block else here(scatter_idx),
            first_block + chunk_blocks, here(summary_idx),
            first_block + gather_blocks, attend)
        return (x, k_rows, v_rows), None

    (x, k_rows, v_rows), _ = jax.lax.scan(
        scan_body,
        (x, k_pool.reshape(L * N * bs, R), v_pool.reshape(L * N * bs, R)),
        (params["blocks"], jnp.arange(L, dtype=jnp.int32) * N))
    with jax.named_scope("logits"):
        x = rmsnorm(params["final_norm"], x, cfg.rms_norm_eps,
                    unit_offset=True)
    return x, k_rows.reshape(L, N, bs, R), v_rows.reshape(L, N, bs, R)


def _paged_logits(params: Params, cfg: EvaByteConfig, tokens: jax.Array,
                  positions: jax.Array, token_mask: jax.Array,
                  last_index: Any, k_pool: jax.Array, v_pool: jax.Array,
                  block_tables: jax.Array):
    """All heads' logits at ``last_index`` [B] of each row ([B, P, V]) or,
    with None, at every position ([B, T, P, V]); the batch in one pass or,
    over ``PREFILL_TOKENS_PER_PASS`` tokens, a row at a time."""
    def run(tokens, positions, token_mask, tables, last, k_pool, v_pool):
        x, k_pool, v_pool = _paged_backbone(
            params, cfg, tokens, positions, token_mask, k_pool, v_pool,
            tables)
        if last is not None:
            x = jnp.take_along_axis(
                x, last[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        with jax.named_scope("logits"):
            return _heads(cfg, params, x), k_pool, v_pool

    return run_rows(run, tokens, positions, token_mask, block_tables,
                    last_index, (k_pool, v_pool),
                    tokens_per_pass=PREFILL_TOKENS_PER_PASS)


def forward_paged(params: Params, cfg: EvaByteConfig, tokens: jax.Array,
                  positions: jax.Array, token_mask: jax.Array,
                  last_index: jax.Array, k_pool: jax.Array,
                  v_pool: jax.Array, block_tables: jax.Array):
    """A prefill slice or a decode step over the two-kind cache; the
    argument contract of ``models/gpt.py:forward_paged`` with two
    differences. ``block_tables`` [B, Wt] is the ring of window blocks
    then the summary blocks, as ``WindowSummaryLayout.lay_table`` writes a
    row (-1 = a summary entry the request did not reserve). And a row's
    real tokens are consecutive positions inside one window; a slice of
    more than one token starts on a chunk boundary (the engine's slices
    do: ``WindowSummaryLayout.check_prefill``).

    Returns ``(logits [B, V] fp32 of prediction head 0 at each row's last
    real token, k_pool, v_pool)``. All ``n_pred_heads`` heads are one
    product; heads 1.. are for a head-drafted step (ROADMAP M6).
    """
    logits, k_pool, v_pool = _paged_logits(
        params, cfg, tokens, positions, token_mask, last_index, k_pool,
        v_pool, block_tables)
    return logits[:, 0], k_pool, v_pool


def forward_paged_logits(params: Params, cfg: EvaByteConfig,
                         tokens: jax.Array, positions: jax.Array,
                         token_mask: jax.Array, k_pool: jax.Array,
                         v_pool: jax.Array, block_tables: jax.Array):
    """``forward_paged`` returning every head's logits at every position:
    ``(logits [B, T, n_pred_heads, V] fp32, k_pool, v_pool)``. The tests
    compare it with the reference; the engine's speculative verify step
    is refused for this family until a step can commit several bytes."""
    return _paged_logits(params, cfg, tokens, positions, token_mask, None,
                         k_pool, v_pool, block_tables)


def _cache_layout(cfg: EvaByteConfig, cache: Any) -> Any:
    # imported here: serving/ imports the models at import time
    from determined_clone_tpu.serving.kv_cache import WindowSummaryLayout

    return WindowSummaryLayout(cache, cfg.max_seq_len,
                               window=cfg.window_size, chunk=cfg.chunk_size)


PAGED = PagedModel(
    family="evabyte", forward_paged=forward_paged,
    forward_paged_logits=forward_paged_logits, init=init,
    cache_layout=_cache_layout,
    # prefix sharing, spill and a draft's verify all address the cache as
    # one row per position, which this one is not (ROADMAP B-M)
    unsupported=("prefix_cache", "kv_store", "speculative"),
    row_counters=("serving_eva_window_rows_total",
                  "serving_eva_summary_rows_total"))
