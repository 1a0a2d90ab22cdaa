"""MiniCPM-SALA — a decoder whose layers are of two kinds, on the serving
path.

From the published configuration
(https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json:
``mixer_types`` names each layer ``minicpm4`` or ``lightning-attn``) and
the descriptions of its two mixers (InfLLM-V2, MiniCPM4 report
arXiv:2506.07900; Lightning Attention);
``benchmarks/reference/minicpm_sala.py`` is the same mathematics over a
whole sequence, with no cache, and lists what the configuration does not
state. ``x`` is the fp32 residual stream, ``rs = scale_depth /
sqrt(published layers)``:

- ``x_0 = scale_emb * E[token]``; every layer ``x += rs * Mixer(norm(x))``
  then ``x += rs * W_down(silu(W_gate h) * W_up h)``, ``h = norm(x)``
  (RMSNorm, scale ``w``); the head is untied: ``W_head (norm(x) /
  (hidden_size / dim_model_base))``;
- **``lightning-attn``**: H heads; ``q, k`` RMS-normed per head then
  rotary, ``v`` plain; per head a constant decay and a state ``[d, d]``
  (``ops/lightning_attention.py``); ``y = W_o (norm(o) * sigmoid(W_g
  h))``, the norm over all heads' outputs side by side;
- **``minicpm4``**: H query heads share G key/value heads, no positions;
  ``q, k`` RMS-normed per head; block-sparse attention with a
  compressed-key index past ``dense_len`` (``ops/sparse_attention.py``);
  ``y = W_o (o * sigmoid(W_g h))``.

**Two stacks, one interleave.** The parameters are two stacks,
``params["sparse"]`` and ``params["lightning"]``, each layer kind's leaves
stacked in the order its layers appear; ``mixer_types`` (static) says how
they interleave, and the forward is a ``lax.scan`` over each run of one
kind.

**The cache differs by layer kind** (``serving/kv_cache.py:
SparseStateLayout``); the pools are this family's own (``init_pools``):

- ``k_pool``, ``v_pool`` ``[Ls, N, block, G * d]``: exact rows of the
  sparse layers, one a position, the G key/value heads side by side;
- ``index_pool`` ``[Ls, N, keys_per_block * G * d]``: the compressed keys
  that start in each block, side by side, so the block id that finds a
  block's rows finds its compressed keys;
- ``state_pool`` ``[Ll, slots, H, d, d]`` fp32: a lightning layer's state
  of each sequence; the slot is the last entry of the sequence's table
  row. It is read as zero by the call that holds the sequence's position
  0, carried from one prefill slice to the next, advanced over real tokens
  only, and written back by every call.

**Weights** are held in ``param_dtype`` (bfloat16) and read as they lie;
norm scales and the decay table are fp32.

There is no training path (ROADMAP B-M).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from determined_clone_tpu.models.paged import (
    PagedModel,
    cast_leaves,
    run_rows,
)
from determined_clone_tpu.ops.attention import rotary_embedding
from determined_clone_tpu.ops.layers import rmsnorm
from determined_clone_tpu.ops.lightning_attention import (
    lightning_attention,
    lightning_decay,
)
from determined_clone_tpu.ops.sparse_attention import (
    SparseConfig,
    compressed_keys,
    sparse_attend,
    sparse_select,
)

Params = Dict[str, Any]

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"

# a prefill call over more tokens than this runs a row at a time, so that
# its fp32 temporaries (the MLP's, the selection's scores) are one row's
PREFILL_TOKENS_PER_PASS = 2048

_PUBLISHED_MIXERS = (
    (SPARSE,) + (LIGHTNING,) * 8 + (SPARSE,) + (LIGHTNING,) * 6
    + (SPARSE,) * 2 + (LIGHTNING,) * 4 + (SPARSE,) + (LIGHTNING,) * 6
    + (SPARSE,) * 3)


@dataclasses.dataclass(frozen=True)
class MiniCPMSALAConfig:
    """The source's sizes under this repo's names (its key in brackets)."""
    vocab_size: int = 73448
    # the layers held, in order (mixer_types), and the index each has in
    # the published model (the decay table is by published index)
    mixer_types: Tuple[str, ...] = _PUBLISHED_MIXERS
    first_layer: int = 0
    n_published_layers: int = 32        # residual scale, decay table
    d_model: int = 4096                 # hidden_size
    n_heads: int = 32                   # num_attention_heads, lightning_nh
    n_kv_heads: int = 2                 # num_key_value_heads
    head_dim: int = 128                 # head_dim, lightning_head_dim
    d_ff: int = 16384                   # intermediate_size
    max_seq_len: int = 524288           # max_position_embeddings
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    sparse: SparseConfig = SparseConfig()
    init_std: float = 0.02
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self) -> None:
        unknown = set(self.mixer_types) - {SPARSE, LIGHTNING}
        if unknown:
            raise ValueError(f"unknown mixer types {sorted(unknown)}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_heads} query heads are not whole "
                             f"groups over {self.n_kv_heads} KV heads")

    @property
    def n_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def n_sparse(self) -> int:
        return self.mixer_types.count(SPARSE)

    @property
    def n_lightning(self) -> int:
        return self.mixer_types.count(LIGHTNING)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / self.n_published_layers ** 0.5

    def runs(self) -> List[Tuple[str, int, int]]:
        """``(kind, lo, hi)`` of every run of one kind: its layers are
        ``[lo, hi)`` of that kind's stack."""
        out: List[Tuple[str, int, int]] = []
        seen = {SPARSE: 0, LIGHTNING: 0}
        for kind in self.mixer_types:
            if out and out[-1][0] == kind:
                out[-1] = (kind, out[-1][1], out[-1][2] + 1)
            else:
                out.append((kind, seen[kind], seen[kind] + 1))
            seen[kind] += 1
        return out

    @staticmethod
    def tiny() -> "MiniCPMSALAConfig":
        """A toy with the published constants that shape the index
        (kernel 32, stride 16, block 64) and 2 KV groups."""
        return MiniCPMSALAConfig(
            vocab_size=128,
            mixer_types=(SPARSE, LIGHTNING, LIGHTNING, SPARSE),
            first_layer=9, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
            d_ff=128, max_seq_len=1024, dim_model_base=16, init_std=0.1,
            sparse=SparseConfig(topk=4, window=64, dense_len=256))

    def paged_model(self) -> PagedModel:
        """The family on the serving path (``models/paged.py``)."""
        return PAGED


def decay_table(cfg: MiniCPMSALAConfig) -> jax.Array:
    """``lam`` [Ll, H] of the held lightning layers, by published index."""
    layers = [cfg.first_layer + i for i, kind in enumerate(cfg.mixer_types)
              if kind == LIGHTNING]
    return jnp.stack([lightning_decay(cfg.n_heads, layer,
                                      cfg.n_published_layers)
                      for layer in layers])


def init(key: jax.Array, cfg: MiniCPMSALAConfig) -> Params:
    """Matrices normal(0, init_std) in ``param_dtype``, a layer at a time;
    norm scales 1; the decay table by the Lightning Attention
    convention."""
    D, F, V = cfg.d_model, cfg.d_ff, cfg.vocab_size
    Q, KV = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    keys = iter(jax.random.split(key, 32))
    f32 = jnp.float32

    def matrices(n, shape):
        return {"kernel": jax.lax.map(
            lambda k: (cfg.init_std * jax.random.normal(k, shape, f32)
                       ).astype(cfg.param_dtype),
            jax.random.split(next(keys), n))}

    def ones(n, width):
        return {"scale": jnp.ones((n, width), f32)}

    def stack(n, kv_width):
        return {
            "ln1": ones(n, D), "attn_q": matrices(n, (D, Q)),
            "attn_k": matrices(n, (D, kv_width)),
            "attn_v": matrices(n, (D, kv_width)),
            "attn_gate": matrices(n, (D, Q)),
            "attn_out": matrices(n, (Q, D)),
            "q_norm": ones(n, cfg.head_dim), "k_norm": ones(n, cfg.head_dim),
            "ln2": ones(n, D), "mlp_gate": matrices(n, (D, F)),
            "mlp_up": matrices(n, (D, F)), "mlp_down": matrices(n, (F, D)),
        }

    lightning = stack(cfg.n_lightning, Q)
    lightning["out_norm"] = ones(cfg.n_lightning, Q)
    lightning["decay"] = decay_table(cfg)
    return {
        "embed": {"table": (cfg.init_std * jax.random.normal(
            next(keys), (V, D), f32)).astype(cfg.param_dtype)},
        "sparse": stack(cfg.n_sparse, KV),
        "lightning": lightning,
        "final_norm": {"scale": jnp.ones((D,), f32)},
        "lm_head": {"kernel": (cfg.init_std * jax.random.normal(
            next(keys), (D, V), f32)).astype(cfg.param_dtype)},
    }


_MATRIX = re.compile(r"(^|/)(kernel|table)$")


def serving_params(params: Params, cfg: MiniCPMSALAConfig) -> Params:
    """Every matrix and the embedding in ``compute_dtype``, which the
    products read them in; norm scales and the decay table fp32."""
    return cast_leaves(
        params, lambda path: cfg.compute_dtype if _MATRIX.search(path)
        else jnp.float32)


def init_pools(cfg: MiniCPMSALAConfig, cache: Any, max_batch: int
               ) -> Tuple[jax.Array, ...]:
    """``(k_pool, v_pool, index_pool, state_pool)``, zeroed (the module's
    doc-string has their shapes), with a state slot per batch row."""
    sp = cfg.sparse
    if cache.block_size != sp.block:
        raise ValueError(f"the cache block ({cache.block_size}) must be "
                         f"the selection's block ({sp.block})")
    R = cfg.n_kv_heads * cfg.head_dim
    rows = (cfg.n_sparse, cache.num_blocks, sp.block, R)
    return (jnp.zeros(rows, cfg.compute_dtype),
            jnp.zeros(rows, cfg.compute_dtype),
            jnp.zeros((cfg.n_sparse, cache.num_blocks,
                       sp.keys_per_block * R), cfg.compute_dtype),
            jnp.zeros((cfg.n_lightning, max_batch, cfg.n_heads, cfg.head_dim,
                       cfg.head_dim), jnp.float32))


def _norm(cfg: MiniCPMSALAConfig, p: Params, x: jax.Array,
          dtype: Any = None) -> jax.Array:
    return rmsnorm(p, x, cfg.rms_norm_eps, dtype=dtype or cfg.compute_dtype)


def _matmul(x: jax.Array, p: Params) -> jax.Array:
    """x @ kernel as the kernel lies, summed and returned in fp32."""
    return jnp.matmul(x, p["kernel"], preferred_element_type=jnp.float32)


def _heads(cfg: MiniCPMSALAConfig, lp: Params, h: jax.Array, name: str,
           n_heads: int) -> jax.Array:
    """A projection split into heads, [B, T, n_heads, d] fp32."""
    B, T, _ = h.shape
    return _matmul(h, lp[name]).reshape(B, T, n_heads, cfg.head_dim)


def _mixer_out(cfg: MiniCPMSALAConfig, lp: Params, x: jax.Array,
               h: jax.Array, o: jax.Array) -> jax.Array:
    """``x + rs * W_o (o * sigmoid(W_g h))`` for o [B, T, H * d] fp32."""
    gated = o * jax.nn.sigmoid(_matmul(h, lp["attn_gate"]))
    return x + cfg.residual_scale * _matmul(
        gated.astype(cfg.compute_dtype), lp["attn_out"])


def _mlp(cfg: MiniCPMSALAConfig, lp: Params, x: jax.Array) -> jax.Array:
    with jax.named_scope("mlp"):
        h = _norm(cfg, lp["ln2"], x)
        act = jax.nn.silu(_matmul(h, lp["mlp_gate"])) * _matmul(
            h, lp["mlp_up"])
        return x + cfg.residual_scale * _matmul(
            act.astype(cfg.compute_dtype), lp["mlp_down"])


def _layer(stack: Params, i: jax.Array) -> Params:
    """Layer ``i`` of a stack, read where it lies (as a scan reads its
    scanned inputs)."""
    return jax.tree.map(
        lambda w: jax.lax.dynamic_index_in_dim(w, i, keepdims=False), stack)


def _lightning_layer(cfg: MiniCPMSALAConfig, lp: Params, x: jax.Array,
                     positions: jax.Array, token_mask: jax.Array,
                     states: jax.Array, read_idx: jax.Array,
                     write_idx: jax.Array, fresh: jax.Array):
    """One lightning layer. ``states`` is the layer kind's whole pool as
    ``[Ll * slots, H, d, d]``; ``read_idx``/``write_idx`` [B] this layer's
    entries of it (``write_idx`` past the pool for a row with no real
    token); ``fresh`` [B]: the row starts its sequence, the state is 0."""
    B, T, _ = x.shape
    H, dt = cfg.n_heads, cfg.compute_dtype
    with jax.named_scope("attn"):
        h = _norm(cfg, lp["ln1"], x)
        q = rotary_embedding(
            _norm(cfg, lp["q_norm"], _heads(cfg, lp, h, "attn_q", H)),
            positions, base=cfg.rope_theta)
        k = rotary_embedding(
            _norm(cfg, lp["k_norm"], _heads(cfg, lp, h, "attn_k", H)),
            positions, base=cfg.rope_theta)
        v = _heads(cfg, lp, h, "attn_v", H).astype(dt)
        with jax.named_scope("lightning"):
            with jax.named_scope("kv_cache"):
                state = jnp.where(fresh[:, None, None, None], 0.0,
                                  states[read_idx])
            o, state = lightning_attention(q, k, v, state, lp["decay"],
                                           token_mask)
            with jax.named_scope("kv_cache"):
                states = states.at[write_idx].set(state, mode="drop")
        o = _norm(cfg, lp["out_norm"], o.reshape(B, T, -1), jnp.float32)
        x = _mixer_out(cfg, lp, x, h, o)
    return _mlp(cfg, lp, x), states


def _sparse_layer(cfg: MiniCPMSALAConfig, lp: Params, x: jax.Array,
                  positions: jax.Array, token_mask: jax.Array,
                  k_rows: jax.Array, v_rows: jax.Array, kc_rows: jax.Array,
                  first_block: jax.Array, tables: jax.Array,
                  idx: Dict[str, jax.Array]):
    """One block-sparse layer. ``k_rows``/``v_rows`` are the kind's whole
    pools as rows ``[Ls * N * block, R]``, ``kc_rows`` the index pool as
    ``[Ls * N * keys_per_block, R]``; ``first_block`` this layer's first
    block in them; ``tables`` [B, W] the sequences' blocks within a
    layer; ``idx`` the call's index arithmetic, the same in every layer
    (``_sparse_indices``), -1 where nothing is to be written."""
    B, T, _ = x.shape
    sp, dt = cfg.sparse, cfg.compute_dtype
    H, G = cfg.n_heads, cfg.n_kv_heads
    bs, per = sp.block, sp.keys_per_block
    R = k_rows.shape[-1]
    here = first_block + tables

    def at(i, unit):  # this layer's entries; -1 stays out of every pool
        return jnp.where(i >= 0, first_block * unit + i, k_rows.shape[0])

    with jax.named_scope("attn"):
        h = _norm(cfg, lp["ln1"], x)
        q = _norm(cfg, lp["q_norm"], _heads(cfg, lp, h, "attn_q", H))
        k = _norm(cfg, lp["k_norm"], _heads(cfg, lp, h, "attn_k", G)
                  ).reshape(B, T, R)
        v = _matmul(h, lp["attn_v"]).astype(dt)
        with jax.named_scope("kv_cache"):
            # a slice is whole blocks and is written a block a piece
            piece, unit = ((bs, R), 1) if T > 1 else ((R,), bs)
            where = at(idx["scatter"], unit)
            k_rows = k_rows.reshape(-1, *piece).at[where].set(
                k.reshape(-1, *piece), mode="drop").reshape(-1, R)
            v_rows = v_rows.reshape(-1, *piece).at[where].set(
                v.reshape(-1, *piece), mode="drop").reshape(-1, R)
        with jax.named_scope("sparse_select"):
            # the compressed keys this call completes: those whose last
            # position it writes, from the rows before the slice and its
            # own (a slice), or from the cached rows (a decode step)
            with jax.named_scope("kv_cache"):
                before = k_rows[at(idx["before"], bs)]
            ends = before if T == 1 else jnp.concatenate([before, k], axis=1)
            kc_new = compressed_keys(ends, sp)
            with jax.named_scope("kv_cache"):
                kc_rows = kc_rows.at[at(idx["compressed"], per)].set(
                    kc_new.reshape(-1, R), mode="drop")
                kc = kc_rows.reshape(-1, per * R)[here].reshape(B, -1, R)
            selection = sparse_select(q, kc, positions, token_mask, sp)
        with jax.named_scope("sparse_attn"):
            o = sparse_attend(q, k_rows.reshape(-1, bs, R),
                              v_rows.reshape(-1, bs, R), here, selection,
                              positions, token_mask, sp)
        x = _mixer_out(cfg, lp, x, h, o.reshape(B, T, -1))
    return _mlp(cfg, lp, x), k_rows, v_rows, kc_rows


def _sparse_indices(cfg: MiniCPMSALAConfig, positions: jax.Array,
                    token_mask: jax.Array, tables: jax.Array
                    ) -> Dict[str, jax.Array]:
    """Where a call's rows and compressed keys go, within one layer's
    share of the pools; -1 = nowhere.

    ``scatter``: the new K/V, [B * T / block] blocks (a slice starts on a
    block boundary with its real tokens first, so a block's first token
    says whether it holds any) or, for a decode step, [B] rows.
    ``before`` [B, n] rows read back: the ``kernel - stride`` positions
    before a slice or, for a decode step, the ``kernel`` positions up to
    its own. ``compressed`` [B * n] index rows of the compressed keys the
    call completes (``j`` at row ``table[j // per] * per + j % per``)."""
    B, T = positions.shape
    sp = cfg.sparse
    bs, per, stride = sp.block, sp.keys_per_block, sp.stride
    first = positions[:, :1]
    n_real = jnp.sum(token_mask, axis=1, keepdims=True)

    def rows(pos):  # positions [B, n] -> rows of the pool (clamped at 0)
        pos = jnp.maximum(pos, 0)
        return jnp.take_along_axis(tables, pos // bs, axis=1) * bs + pos % bs

    if T == 1:
        scatter = jnp.where(token_mask, rows(positions), -1).reshape(-1)
        before = rows(first - sp.kernel + 1 + jnp.arange(sp.kernel))
        j = (first + 1 - sp.kernel) // stride
        done = token_mask & (first + 1 >= sp.kernel) \
            & ((first + 1 - sp.kernel) % stride == 0)
    else:
        blk = jnp.take_along_axis(tables, positions[:, ::bs] // bs, axis=1)
        scatter = jnp.where(token_mask[:, ::bs], blk, -1).reshape(-1)
        lead = sp.kernel - stride
        before = rows(first - lead + jnp.arange(lead))
        j = (first - lead) // stride + jnp.arange(T // stride)
        done = (j >= 0) & (j * stride + sp.kernel <= first + n_real)
    j = jnp.maximum(j, 0)
    entry = jnp.take_along_axis(tables, j // per, axis=1) * per + j % per
    return {"scatter": scatter, "before": before,
            "compressed": jnp.where(done, entry, -1).reshape(-1)}


def _paged_backbone(params: Params, cfg: MiniCPMSALAConfig,
                    tokens: jax.Array, positions: jax.Array,
                    token_mask: jax.Array, k_pool: jax.Array,
                    v_pool: jax.Array, index_pool: jax.Array,
                    state_pool: jax.Array, block_tables: jax.Array):
    """Embed -> the interleaved stacks: ``(x [B, T, D] fp32, k_pool,
    v_pool, index_pool, state_pool)``. The pools ride the layer scans as
    rows and are updated in place."""
    B, T = tokens.shape
    Ls, N, bs, R = k_pool.shape
    n_slots = state_pool.shape[1]
    tables, slot = block_tables[:, :-1], block_tables[:, -1]
    idx = _sparse_indices(cfg, positions, token_mask, tables)
    real = jnp.any(token_mask, axis=1)
    fresh = real & (positions[:, 0] == 0)
    nowhere = state_pool.shape[0] * n_slots

    with jax.named_scope("embed"):
        x = cfg.scale_emb * jnp.take(params["embed"]["table"], tokens,
                                     axis=0).astype(jnp.float32)

    def sparse_body(carry, i):
        x, *pools = carry
        out = _sparse_layer(cfg, _layer(params["sparse"], i), x, positions,
                            token_mask, *pools, i * N, tables, idx)
        return out, None

    def lightning_body(carry, i):
        x, states = carry
        at = i * n_slots + slot
        out = _lightning_layer(cfg, _layer(params["lightning"], i), x,
                               positions, token_mask, states, at,
                               jnp.where(real, at, nowhere), fresh)
        return out, None

    sparse = (k_pool.reshape(-1, R), v_pool.reshape(-1, R),
              index_pool.reshape(-1, R))
    states = state_pool.reshape(-1, *state_pool.shape[2:])
    for kind, lo, hi in cfg.runs():
        layers = jnp.arange(lo, hi, dtype=jnp.int32)
        if kind == SPARSE:
            (x, *sparse), _ = jax.lax.scan(sparse_body, (x, *sparse), layers)
        else:
            (x, states), _ = jax.lax.scan(lightning_body, (x, states),
                                          layers)
    return (x, sparse[0].reshape(k_pool.shape),
            sparse[1].reshape(v_pool.shape),
            sparse[2].reshape(index_pool.shape),
            states.reshape(state_pool.shape))


def _paged_logits(params: Params, cfg: MiniCPMSALAConfig, tokens: jax.Array,
                  positions: jax.Array, token_mask: jax.Array,
                  last_index: Any, pools: Tuple[jax.Array, ...],
                  block_tables: jax.Array):
    """Logits at ``last_index`` [B] of each row ([B, V]) or, with None, at
    every position ([B, T, V]); the batch in one pass or, over
    ``PREFILL_TOKENS_PER_PASS`` tokens, a row at a time. A slice is
    padded to whole cache blocks."""
    T = tokens.shape[1]

    def run(tokens, positions, token_mask, tables, last, *pools):
        x, *pools = _paged_backbone(params, cfg, tokens, positions,
                                    token_mask, *pools, tables)
        x = x[:, :T] if last is None else jnp.take_along_axis(
            x, last[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        with jax.named_scope("logits"):
            h = _norm(cfg, params["final_norm"], x, jnp.float32) \
                / (cfg.d_model / cfg.dim_model_base)
            return (_matmul(h.astype(cfg.compute_dtype), params["lm_head"]),
                    *pools)

    return run_rows(run, tokens, positions, token_mask, block_tables,
                    last_index, pools, block=cfg.sparse.block,
                    tokens_per_pass=PREFILL_TOKENS_PER_PASS)


def forward_paged(params: Params, cfg: MiniCPMSALAConfig, tokens: jax.Array,
                  positions: jax.Array, token_mask: jax.Array,
                  last_index: jax.Array, k_pool: jax.Array,
                  v_pool: jax.Array, index_pool: jax.Array,
                  state_pool: jax.Array, block_tables: jax.Array):
    """A prefill slice or a decode step over this family's cache; the
    argument contract of ``models/gpt.py:forward_paged`` with these
    differences. The pools are four (``init_pools``). ``block_tables`` [B,
    W + 1] is a sequence's blocks in order, then its state slot, as
    ``SparseStateLayout.lay_table`` writes a row. A row's real tokens are
    consecutive positions and come first; a slice of more than one token
    starts on a block boundary (the engine's do:
    ``SparseStateLayout.check_prefill``), and the call that holds position
    0 starts the sequence's state from zero.

    Returns ``(logits [B, V] fp32 at each row's last real token, k_pool,
    v_pool, index_pool, state_pool)``.
    """
    return _paged_logits(params, cfg, tokens, positions, token_mask,
                         last_index, (k_pool, v_pool, index_pool,
                                      state_pool), block_tables)


def forward_paged_logits(params: Params, cfg: MiniCPMSALAConfig,
                         tokens: jax.Array, positions: jax.Array,
                         token_mask: jax.Array, k_pool: jax.Array,
                         v_pool: jax.Array, index_pool: jax.Array,
                         state_pool: jax.Array, block_tables: jax.Array):
    """``forward_paged`` returning the logits at every position:
    ``(logits [B, T, V] fp32, the four pools)``. The tests compare it with
    the reference; the engine's speculative verify step is refused for
    this family (a rejected draft cannot be taken out of a state)."""
    return _paged_logits(params, cfg, tokens, positions, token_mask, None,
                         (k_pool, v_pool, index_pool, state_pool),
                         block_tables)


def _cache_layout(cfg: MiniCPMSALAConfig, cache: Any) -> Any:
    # imported here: serving/ imports the models at import time
    from determined_clone_tpu.serving.kv_cache import SparseStateLayout

    return SparseStateLayout(cache, cfg.max_seq_len, topk=cfg.sparse.topk,
                             dense_len=cfg.sparse.dense_len)


PAGED = PagedModel(
    family="minicpm_sala", forward_paged=forward_paged,
    forward_paged_logits=forward_paged_logits, init=init,
    cache_layout=_cache_layout, serving_params=serving_params,
    init_pools=init_pools,
    pool_names=("k_pool", "v_pool", "index_pool", "state_pool"),
    # prefix sharing and spill address the cache as rows a position, and a
    # draft's rejected tokens cannot be taken out of a recurrent state
    # (ROADMAP B-M)
    unsupported=("prefix_cache", "kv_store", "speculative"),
    row_counters=("serving_sparse_kv_rows_total",
                  "serving_sparse_selected_rows_total",
                  "serving_state_slots_total"))
