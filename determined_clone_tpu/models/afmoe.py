"""Arcee's Trinity (``model_type`` ``afmoe``) — sliding-window and full
attention over grouped KV heads in one stack, a sigmoid gate on attention's
output, four norms a layer, sparse experts with a shared expert — on the
serving path, as one member of an expert-parallel group.

From the published configuration
(https://huggingface.co/arcee-ai/Trinity-Large-Preview/blob/main/config.json);
``benchmarks/reference/afmoe.py`` is the same mathematics over a whole
sequence, with no cache, and lists what the configuration does not state.
``x`` is the fp32 residual stream, ``N`` an RMSNorm with a scale. The model
is ``x = E[token] * sqrt(hidden_size)`` (``mup_enabled``), the layers, a
final norm and an untied head. A layer's kind is ``<attention>_<ffn>``:

- **attention**, ``Hq`` query heads of ``D`` over ``Hkv`` KV heads: ``a =
  N_in(x)``; ``q = W_q a``, ``k = W_k a``, ``v = W_v a``; ``g = sigmoid(W_g
  a)``; ``q`` and ``k`` normed a head (one scale the heads share); in a
  ``sliding`` layer rotary over all of ``D`` (halves rotated) and the query
  at ``i`` attends ``max(0, i - sliding_window + 1) .. i``; in a ``full``
  layer **no rotary** and ``0 .. i``; query head ``h`` against KV head ``h
  // (Hq / Hkv)``; ``x += N_post_attn(W_o (g * heads))``.
- **FFN**: ``m = N_pre_mlp(x)``; ``x += N_post_mlp(F(m))``, ``F`` a SwiGLU
  (``dense``) or ``Shared(m) +`` the routed experts (``sparse``:
  ``ops/moe.py:routed_experts``, sigmoid scores over all
  ``published_num_experts``, a selection bias, top-k, normalised over the
  chosen and scaled; **held here: experts** ``[first_expert, first_expert
  + num_experts)``; pairs of absent experts contribute nothing).

**Stacks by layer kind**, as ``models/kimi_linear.py`` keeps them: the
parameters of a kind are one stack, in the order its layers appear, and the
forward is a ``lax.scan`` over each run of one kind.

**The cache** (``serving/kv_cache.py:WindowSlotLayout``; ``init_pools``) is
of two lifetimes, K and V rows ``[Hkv * D]`` of one position either way:

- ``k_pool``, ``v_pool`` ``[L_full, N, block, R]``: the ``full`` layers'
  rows, in blocks that grow with the sequence through its table;
- ``k_window_pool``, ``v_window_pool`` ``[L_sliding, slots, ring, R]``: the
  ``sliding`` layers' rows, a slot a sequence (the last entry of its table
  row), position ``p`` in row ``p % ring``, ``ring = sliding_window +
  prefill_slice_len``: a slice is written first and attended after, so the
  ring still holds the window before the slice's first token, and a
  sequence's cache in these layers stops growing at ``ring`` positions.
  A slot is read as ``ring / block`` blocks through a table made from its
  id, so one attention (``ops/window_attention.py``) reads both kinds.

A decode step reads ``min(length, sliding_window)`` rows a ``sliding``
layer and ``length`` a ``full`` one, never a table; a slice the blocks that
hold what its queries attend.

After the pools every program returns ``[expert_pairs, expert_hits]``
(``PagedModel.step_counters``) and, last, each token's chosen experts in
every ``sparse`` layer ``[B, T, L_sparse * k]`` (``PagedModel.
token_records``), as ``models/glm_moe_dsa.py`` does and for its reason.

**Weights** are held in ``param_dtype`` (bfloat16) and read as they lie;
norm scales, the router and its bias are fp32. There is no training path
(ROADMAP B-M).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from determined_clone_tpu.models.paged import (
    PagedModel,
    cast_leaves,
    run_rows,
)
from determined_clone_tpu.ops import window_attention as wa
from determined_clone_tpu.ops.attention import rotary_embedding
from determined_clone_tpu.ops.layers import rmsnorm
from determined_clone_tpu.ops.moe import routed_experts

Params = Dict[str, Any]

SLIDING, FULL = "sliding", "full"
DENSE, SPARSE = "dense", "sparse"
_ATTENTION = {"sliding_attention": SLIDING, "full_attention": FULL}

# a prefill call over more tokens than this runs a row at a time, so that
# its fp32 temporaries (a pass of attention, the experts' pairs) are one
# row's
PREFILL_TOKENS_PER_PASS = 2048

_PUBLISHED_LAYERS = ("sliding_attention",) * 3 + ("full_attention",)


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    """The source's sizes under the source's key names."""
    vocab_size: int = 200192
    hidden_size: int = 3072
    # the layers held, in order: each one's attention, and how many leading
    # ones have a dense FFN
    num_hidden_layers: int = 60
    layer_types: Tuple[str, ...] = _PUBLISHED_LAYERS * 15
    num_dense_layers: int = 6
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 4096
    rope_theta: float = 10000.0
    intermediate_size: int = 12288
    moe_intermediate_size: int = 3072
    # the routed experts held here, of how many, from which one on
    num_experts: int = 256
    published_num_experts: int = 256
    first_expert: int = 0
    num_experts_per_tok: int = 4
    route_scale: float = 2.448
    mup_enabled: bool = True
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-5
    # the longest prefill slice the sliding layers' ring leaves room for
    # beside its window (a serving size: the engine's chunk_prefill_len)
    prefill_slice_len: int = 2048
    init_std: float = 0.02
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self) -> None:
        if len(self.layer_types) != self.num_hidden_layers \
                or set(self.layer_types) - set(_ATTENTION):
            raise ValueError(
                f"layer_types does not name the attention of each of the "
                f"{self.num_hidden_layers} layers, one of "
                f"{sorted(_ATTENTION)}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are not whole groups of KV heads")
        if not 0 <= self.first_expert <= self.first_expert \
                + self.num_experts <= self.published_num_experts:
            raise ValueError("the experts held are not among the published")

    @property
    def n_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def n_heads(self) -> int:
        return self.num_attention_heads

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def kinds(self) -> Tuple[str, ...]:
        """Each layer's kind, ``<attention>_<ffn>``."""
        return tuple(
            f"{_ATTENTION[kind]}_"
            f"{DENSE if i < self.num_dense_layers else SPARSE}"
            for i, kind in enumerate(self.layer_types))

    @property
    def n_sliding(self) -> int:
        return self.layer_types.count("sliding_attention")

    @property
    def n_full(self) -> int:
        return self.layer_types.count("full_attention")

    @property
    def row_width(self) -> int:
        """Width of a K (or V) row: the KV heads side by side."""
        return self.num_key_value_heads * self.head_dim

    @property
    def ring(self) -> int:
        """Positions a sequence keeps in a sliding layer."""
        return self.sliding_window + self.prefill_slice_len

    def runs(self) -> List[Tuple[str, int, int, int]]:
        """``(kind, lo, hi, first)`` of every run of one kind: layers
        ``[lo, hi)`` of the kind's stack, the first of them the
        ``first``-th layer of its attention (of the sliding layers, or of
        the full ones: its place in their pools)."""
        out: List[Tuple[str, int, int, int]] = []
        seen: Dict[str, int] = {}
        for kind in self.kinds:
            at, attention = seen.get(kind, 0), kind.split("_")[0]
            if out and out[-1][0] == kind:
                out[-1] = out[-1][:2] + (at + 1,) + out[-1][3:]
            else:
                out.append((kind, at, at + 1, seen.get(attention, 0)))
            seen[kind] = at + 1
            seen[attention] = seen.get(attention, 0) + 1
        return out

    @staticmethod
    def tiny() -> "AfmoeConfig":
        """A toy with the published pattern: a dense layer and four expert
        layers, sliding x 2, full, sliding x 2; 4 query heads over 2 KV
        heads of 16, a window of 16 beside slices of 8; 16 experts of which
        8 are held, top-4."""
        return AfmoeConfig(
            vocab_size=128, hidden_size=64, num_hidden_layers=5,
            layer_types=("sliding_attention",) * 2 + ("full_attention",)
            + ("sliding_attention",) * 2, num_dense_layers=1,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            sliding_window=16, intermediate_size=128,
            moe_intermediate_size=32, num_experts=8,
            published_num_experts=16, first_expert=8, num_experts_per_tok=4,
            max_position_embeddings=128, prefill_slice_len=8, init_std=0.1)

    def paged_model(self) -> PagedModel:
        """The family on the serving path (``models/paged.py``)."""
        return PAGED


def layer_shapes(cfg: AfmoeConfig, kind: str) -> Dict[str, Tuple]:
    """``{leaf path: shape}`` of one layer of ``kind``: matrices end in
    ``kernel``, norm scales in ``scale``; the router's bias is named."""
    D, hd = cfg.hidden_size, cfg.head_dim
    Hq, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    shapes: Dict[str, Tuple] = {
        "ln_in/scale": (D,), "ln_post_attn/scale": (D,),
        "ln_pre_mlp/scale": (D,), "ln_post_mlp/scale": (D,),
        "q/kernel": (D, Hq * hd), "k/kernel": (D, Hkv * hd),
        "v/kernel": (D, Hkv * hd), "gate/kernel": (D, Hq * hd),
        "q_norm/scale": (hd,), "k_norm/scale": (hd,),
        "attn_out/kernel": (Hq * hd, D)}
    if kind.endswith(DENSE):
        F = cfg.intermediate_size
        shapes.update({"mlp_gate/kernel": (D, F), "mlp_up/kernel": (D, F),
                       "mlp_down/kernel": (F, D)})
    else:
        F, E = cfg.moe_intermediate_size, cfg.num_experts
        shapes.update({
            "router/kernel": (D, cfg.published_num_experts),
            "router/bias": (cfg.published_num_experts,),
            "shared_gate/kernel": (D, F), "shared_up/kernel": (D, F),
            "shared_down/kernel": (F, D),
            "experts_gate/kernel": (E, D, F), "experts_up/kernel": (E, D, F),
            "experts_down/kernel": (E, F, D)})
    return shapes


def init(key: jax.Array, cfg: AfmoeConfig, *, bias_std: float = 0.01,
         embedding_std: Optional[float] = None) -> Params:
    """Every matrix and the head normal(0, init_std), a layer at a time
    (the fp32 draw of a stack of expert layers is larger than the chip), in
    ``param_dtype`` but the router, fp32; the embedding normal(0,
    ``embedding_std``), by default ``hidden_size ** -0.5``, so that the
    stream the muP multiplier hands the first layer is of unit size; norm
    scales 1; the router's selection bias normal(0, ``bias_std``), so that
    choosing and weighing differ."""
    std, f32 = cfg.init_std, jnp.float32
    keys = iter(jax.random.split(key, 64))

    def leaf(path, shape, n):
        k = next(keys)
        if path.endswith("/scale"):
            return jnp.ones((n, *shape), f32)
        if path == "router/bias":
            return bias_std * jax.random.normal(k, (n, *shape), f32)
        dtype = f32 if path == "router/kernel" else cfg.param_dtype
        return jax.lax.map(
            lambda k: (std * jax.random.normal(k, shape, f32)).astype(dtype),
            jax.random.split(k, n))

    params: Params = {}
    for kind in sorted(set(cfg.kinds)):
        stack: Params = {}
        for path, shape in layer_shapes(cfg, kind).items():
            group, name = path.split("/")
            stack.setdefault(group, {})[name] = leaf(
                path, shape, cfg.kinds.count(kind))
        params[kind] = stack
    V, D = cfg.vocab_size, cfg.hidden_size
    params["embed"] = {"table": (
        (D ** -0.5 if embedding_std is None else embedding_std)
        * jax.random.normal(
            next(keys), (V, D), f32)).astype(cfg.param_dtype)}
    params["final_norm"] = {"scale": jnp.ones((D,), f32)}
    params["lm_head"] = {"kernel": (std * jax.random.normal(
        next(keys), (D, V), f32)).astype(cfg.param_dtype)}
    return params


_MATRIX = re.compile(r"(^|/)(kernel|table)$")


def serving_params(params: Params, cfg: AfmoeConfig) -> Params:
    """Every matrix and the embedding in ``compute_dtype``, which the
    products read them in; everything else and the router fp32."""
    return cast_leaves(
        params, lambda path: cfg.compute_dtype
        if _MATRIX.search(path) and "/router/" not in path else jnp.float32)


def init_pools(cfg: AfmoeConfig, cache: Any, max_batch: int
               ) -> Tuple[jax.Array, ...]:
    """``(k_pool, v_pool, k_window_pool, v_window_pool)``, zeroed (the
    module's doc-string has their shapes), with a slot per batch row."""
    full = (cfg.n_full, cache.num_blocks, cache.block_size, cfg.row_width)
    ring = (cfg.n_sliding, max_batch, cfg.ring, cfg.row_width)
    return tuple(jnp.zeros(shape, cfg.compute_dtype)
                 for shape in (full, full, ring, ring))


def _norm(cfg: AfmoeConfig, p: Params, x: jax.Array,
          dtype: Any = None) -> jax.Array:
    return rmsnorm(p, x, cfg.rms_norm_eps, dtype=dtype or cfg.compute_dtype)


def _matmul(x: jax.Array, p: Params) -> jax.Array:
    """x @ kernel as the kernel lies, summed and returned in fp32."""
    return jnp.matmul(x, p["kernel"], preferred_element_type=jnp.float32)


def _swiglu(cfg: AfmoeConfig, lp: Params, h: jax.Array,
            name: str) -> jax.Array:
    act = jax.nn.silu(_matmul(h, lp[f"{name}_gate"])) \
        * _matmul(h, lp[f"{name}_up"])
    return _matmul(act.astype(cfg.compute_dtype), lp[f"{name}_down"])


def _mlp(cfg: AfmoeConfig, kind: str, lp: Params, i: jax.Array,
         x: jax.Array, token_mask: jax.Array):
    """``(x + N_post_mlp(F(N_pre_mlp(x))), [expert_pairs, expert_hits],
    experts)`` of layer ``i`` of its kind's stack; ``experts`` [B, T, k]
    the experts a ``sparse`` layer chose for each token, None of a
    ``dense`` one."""
    B, T, D = x.shape
    with jax.named_scope("mlp"):
        m32 = _norm(cfg, lp["ln_pre_mlp"], x, jnp.float32)
        m = m32.astype(cfg.compute_dtype)
        if kind.endswith(DENSE):
            f, counts, experts = _swiglu(cfg, lp, m, "mlp"), \
                jnp.zeros((2,), jnp.int32), None
        else:
            routed, counts, experts = routed_experts(
                lp, m32.reshape(B * T, D), first_expert=cfg.first_expert,
                n_held=cfg.num_experts, n_experts=cfg.published_num_experts,
                k=cfg.num_experts_per_tok, scale=cfg.route_scale,
                token_mask=token_mask.reshape(-1),
                first_row=i * cfg.num_experts,
                compute_dtype=cfg.compute_dtype)
            f = _swiglu(cfg, lp, m, "shared") + routed.reshape(B, T, D)
            experts = experts.reshape(B, T, -1)
        return (x + _norm(cfg, lp["ln_post_mlp"], f, jnp.float32), counts,
                experts)


def _layer(stack: Params, i: jax.Array) -> Params:
    """Layer ``i`` of a stack, read where it lies; the routed experts stay
    the kind's whole stack as rows ``[layers * held, ...]``
    (``models/glm_moe_dsa.py:_layer`` says why)."""
    return {name: jax.tree.map(
        (lambda w: w.reshape(-1, *w.shape[2:])) if name.startswith("experts_")
        else (lambda w: jax.lax.dynamic_index_in_dim(w, i, keepdims=False)),
        leaves) for name, leaves in stack.items()}


def _write_indices(positions: jax.Array, token_mask: jax.Array,
                   tables: jax.Array, block: int) -> jax.Array:
    """Where a call's K/V rows go within one layer's share of a pool,
    through ``tables`` [B, W] (a ring where ``W * block`` is short of a
    sequence: entry ``(p // block) % W``); -1 = nowhere. A slice's is [B *
    T / block] blocks (it starts on a block boundary with its real tokens
    first, so a block's first token says whether it holds any); a decode
    step's is [B] rows."""
    W = tables.shape[1]
    if positions.shape[1] == 1:
        row = jnp.take_along_axis(tables, positions // block % W, axis=1) \
            * block + positions % block
        return jnp.where(token_mask, row, -1).reshape(-1)
    blk = jnp.take_along_axis(tables, positions[:, ::block] // block % W,
                              axis=1)
    return jnp.where(token_mask[:, ::block], blk, -1).reshape(-1)


def _attention(cfg: AfmoeConfig, kind: str, lp: Params, x: jax.Array,
               positions: jax.Array, token_mask: jax.Array,
               k_rows: jax.Array, v_rows: jax.Array, first: jax.Array,
               tables: jax.Array, scatter: jax.Array, bs: int):
    """One layer's attention: ``(x + N_post_attn(Attn(N_in(x))), k_rows,
    v_rows)``. ``k_rows``, ``v_rows`` [blocks * block, R] are the whole
    pools of the layer's kind as rows, ``first`` this layer's first block
    in them, ``tables`` [B, W] the sequences' blocks within a layer (of a
    ``sliding`` layer: the ring of the sequence's slot), ``scatter`` where
    the call's rows go within a layer's share (``_write_indices``)."""
    B, T, _ = x.shape
    Hq, Hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    dt, R = cfg.compute_dtype, cfg.row_width
    sliding = kind.startswith(SLIDING)
    window = cfg.sliding_window if sliding else None
    with jax.named_scope("attn"):
        a = _norm(cfg, lp["ln_in"], x)
        q = _norm(cfg, lp["q_norm"], _matmul(a, lp["q"]).reshape(
            B, T, Hq, hd), jnp.float32)
        k = _norm(cfg, lp["k_norm"], _matmul(a, lp["k"]).reshape(
            B, T, Hkv, hd), jnp.float32)
        v = _matmul(a, lp["v"])
        if sliding:
            q = rotary_embedding(q, positions, base=cfg.rope_theta)
            k = rotary_embedding(k, positions, base=cfg.rope_theta)
        with jax.named_scope("kv_cache"):
            # a slice is whole blocks and is written a block a piece
            unit = bs if T > 1 else 1
            piece = (unit, R) if T > 1 else (R,)
            where = jnp.where(scatter >= 0, first * (bs // unit) + scatter,
                              k_rows.shape[0])
            k_rows, v_rows = (
                rows.reshape(-1, *piece).at[where].set(
                    new.astype(dt).reshape(-1, *piece), mode="drop"
                ).reshape(-1, R)
                for rows, new in ((k_rows, k), (v_rows, v)))
        with jax.named_scope("window_attn" if sliding else "full_attn"):
            blocks = (k_rows.reshape(-1, bs, R), v_rows.reshape(-1, bs, R))
            if T == 1:
                hi = jnp.where(token_mask[:, 0], positions[:, 0] + 1, 0)
                lo = jnp.maximum(hi - window, 0) if sliding \
                    else jnp.zeros_like(hi)
                o = wa.decode_rows(
                    q[:, 0].astype(dt), *blocks, first + tables, lo, hi,
                    key_blocks=wa.WINDOW_KEY_BLOCKS if sliding
                    else wa.CONTEXT_KEY_BLOCKS)[:, None]
            else:
                o = wa.slice_rows(q, *blocks, first + tables, positions,
                                  token_mask, window=window)
            gate = jax.nn.sigmoid(_matmul(a, lp["gate"]))
            o = (o.reshape(B, T, -1) * gate).astype(dt)
        x = x + _norm(cfg, lp["ln_post_attn"], _matmul(o, lp["attn_out"]),
                      jnp.float32)
    return x, k_rows, v_rows


def _paged_backbone(params: Params, cfg: AfmoeConfig, tokens: jax.Array,
                    positions: jax.Array, token_mask: jax.Array,
                    k_pool: jax.Array, v_pool: jax.Array,
                    k_window_pool: jax.Array, v_window_pool: jax.Array,
                    block_tables: jax.Array):
    """Embed -> the runs of layers: ``(x [B, T, D] fp32, the four pools,
    counts [2], routing [B, T, L_sparse * k] int32)``; the pools ride the
    layer scans and are updated in place."""
    B, T = tokens.shape
    _, N, bs, R = k_pool.shape
    n_slots = k_window_pool.shape[1]
    ring_blocks = cfg.ring // bs
    tables, slot = block_tables[:, :-1], block_tables[:, -1]
    # a slot as the blocks of its ring, within a sliding layer's share
    rings = slot[:, None] * ring_blocks + jnp.arange(ring_blocks)[None, :]
    by_kind = {
        FULL: (tables, N, _write_indices(positions, token_mask, tables, bs)),
        SLIDING: (rings, n_slots * ring_blocks,
                  _write_indices(positions, token_mask, rings, bs))}

    with jax.named_scope("embed"):
        x = jnp.take(params["embed"]["table"], tokens,
                     axis=0).astype(jnp.float32)
        if cfg.mup_enabled:
            x = x * cfg.hidden_size ** 0.5

    carry = (x, k_pool.reshape(-1, R), v_pool.reshape(-1, R),
             k_window_pool.reshape(-1, R), v_window_pool.reshape(-1, R),
             jnp.zeros((2,), jnp.int32))
    routing = []
    for kind, lo, hi, first in cfg.runs():
        def body(carry, i, kind=kind, lo=lo, first=first):
            x, *rows, counts = carry
            lp = _layer(params[kind], i)
            attention = kind.split("_")[0]
            mine = 0 if attention == FULL else 2
            layer_tables, stride, scatter = by_kind[attention]
            x, rows[mine], rows[mine + 1] = _attention(
                cfg, kind, lp, x, positions, token_mask, rows[mine],
                rows[mine + 1], (first + i - lo) * stride, layer_tables,
                scatter, bs)
            x, hit, experts = _mlp(cfg, kind, lp, i, x, token_mask)
            return (x, *rows, counts + hit), experts

        carry, experts = jax.lax.scan(
            body, carry, jnp.arange(lo, hi, dtype=jnp.int32))
        if experts is not None:                       # [layers, B, T, k]
            routing.extend(experts[j] for j in range(hi - lo))
    x, *rows, counts = carry
    pools = (k_pool, v_pool, k_window_pool, v_window_pool)
    return (x, *(r.reshape(p.shape) for r, p in zip(rows, pools)), counts,
            jnp.concatenate(routing, axis=-1) if routing
            else jnp.zeros((B, T, 0), jnp.int32))


def _paged_logits(params: Params, cfg: AfmoeConfig, tokens: jax.Array,
                  positions: jax.Array, token_mask: jax.Array,
                  last_index: Any, pools: Tuple[jax.Array, ...],
                  block_tables: jax.Array):
    """Logits at ``last_index`` [B] of each row ([B, V]) or, with None, at
    every position ([B, T, V]); the batch in one pass or, over
    ``PREFILL_TOKENS_PER_PASS`` tokens, a row at a time. A slice is padded
    to whole cache blocks. Returns ``(logits, the four pools, counts,
    routing [B, T, L_sparse * k])``."""
    T = tokens.shape[1]

    def run(tokens, positions, token_mask, tables, last, *pools):
        x, *pools, counts, routing = _paged_backbone(
            params, cfg, tokens, positions, token_mask, *pools, tables)
        x = x[:, :T] if last is None else jnp.take_along_axis(
            x, last[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        with jax.named_scope("logits"):
            h = _norm(cfg, params["final_norm"], x)
            return (_matmul(h, params["lm_head"]), *pools, counts,
                    routing[:, :T])

    return run_rows(run, tokens, positions, token_mask, block_tables,
                    last_index, pools, block=pools[0].shape[2],
                    tokens_per_pass=PREFILL_TOKENS_PER_PASS, counters=2)


def forward_paged(params: Params, cfg: AfmoeConfig, tokens: jax.Array,
                  positions: jax.Array, token_mask: jax.Array,
                  last_index: jax.Array, k_pool: jax.Array,
                  v_pool: jax.Array, k_window_pool: jax.Array,
                  v_window_pool: jax.Array, block_tables: jax.Array):
    """A prefill slice or a decode step over this family's cache; the
    argument contract of ``models/gpt.py:forward_paged`` with these
    differences. The pools are four (``init_pools``). ``block_tables`` [B,
    W + 1] is a sequence's blocks in the ``full`` layers in order, then its
    slot in the ``sliding`` layers' pools, as ``WindowSlotLayout.lay_table``
    writes a row. A row's real tokens are consecutive positions and come
    first; a slice of more than one token starts on a block boundary and
    holds at most ``prefill_slice_len`` (the engine's do:
    ``WindowSlotLayout.check_prefill``).

    Returns ``(logits [B, V] fp32 at each row's last real token, the four
    pools, counts [2] int32, routing [B, T, L_sparse * k] int32)``;
    ``counts`` is the call's ``PAGED.step_counters``, ``routing`` its
    ``PAGED.token_records``.
    """
    return _paged_logits(
        params, cfg, tokens, positions, token_mask, last_index,
        (k_pool, v_pool, k_window_pool, v_window_pool), block_tables)


def forward_paged_logits(params: Params, cfg: AfmoeConfig,
                         tokens: jax.Array, positions: jax.Array,
                         token_mask: jax.Array, k_pool: jax.Array,
                         v_pool: jax.Array, k_window_pool: jax.Array,
                         v_window_pool: jax.Array, block_tables: jax.Array):
    """``forward_paged`` returning the logits at every position:
    ``(logits [B, T, V] fp32, the four pools, counts, routing)``. The
    tests compare it with the reference; the engine's speculative verify
    step is refused for this family (a rejected draft's rows would have
    overwritten the ring's oldest)."""
    return _paged_logits(
        params, cfg, tokens, positions, token_mask, None,
        (k_pool, v_pool, k_window_pool, v_window_pool), block_tables)


def _cache_layout(cfg: AfmoeConfig, cache: Any) -> Any:
    # imported here: serving/ imports the models at import time
    from determined_clone_tpu.serving.kv_cache import WindowSlotLayout

    return WindowSlotLayout(cache, cfg.max_seq_len,
                            window=cfg.sliding_window,
                            slice_len=cfg.prefill_slice_len)


def _prefill_counts(cfg: AfmoeConfig, layout: Any, starts: Sequence[int],
                    counts: Sequence[int], length: int) -> Dict[str, int]:
    """The key rows a call's real queries have to attend in one sliding
    layer and in one full layer: the query at position ``i`` ``min(i + 1,
    window)`` and ``i + 1``."""
    w = layout.window
    full = window = 0
    for s, n in zip(starts, counts):
        full += n * s + n * (n + 1) // 2
        inside = min(max(w - s, 0), n)        # queries at positions < w
        window += inside * s + inside * (inside + 1) // 2 + (n - inside) * w
    return {"window_key_rows": window, "full_key_rows": full}


PAGED = PagedModel(
    family="afmoe", forward_paged=forward_paged,
    forward_paged_logits=forward_paged_logits, init=init,
    cache_layout=_cache_layout, serving_params=serving_params,
    init_pools=init_pools,
    pool_names=("k_pool", "v_pool", "k_window_pool", "v_window_pool"),
    # a shared prefix's rows in the sliding layers are gone once a ring has
    # wrapped, and the tiers address two pools of one lifetime; a draft's
    # rejected tokens would have overwritten the ring's oldest rows
    # (ROADMAP B-M, M5)
    unsupported=("prefix_cache", "kv_store", "speculative"),
    row_counters=("serving_full_kv_rows_total",
                  "serving_window_rows_total"),
    step_counters=("expert_pairs", "expert_hits"),
    token_records=True, prefill_counts=_prefill_counts)
