"""GLM-5.2 (``model_type`` ``glm_moe_dsa``) — latent attention, a learned
selection that some layers compute and the next ones reuse, and sparse
experts with a shared expert, on the serving path, as one member of an
expert-parallel group.

From the published configuration
(https://huggingface.co/zai-org/GLM-5.2/blob/main/config.json);
``benchmarks/reference/glm_moe_dsa.py`` is the same mathematics over a
whole sequence, with no cache, in the expanded form, and lists what the
configuration does not state. ``x`` is the fp32 residual stream; every
layer is ``x += Attn(norm(x))`` then ``x += FFN(norm(x))`` (RMSNorm); the
head is untied, ``W_head norm(x)``.

- **Attention** (MLA, ``ops/mla_attention.py``): ``c_Q = norm(W_QA h)``;
  per head ``[q_N | q_R] = W_QB c_Q`` (held as two matrices, the heads'
  ``q_N`` and the heads' ``q_R``), ``q_R`` rotated; ``[c | k_R] = W_KVA
  h``, ``c`` normed, ``k_R`` rotated, one for all heads; **the cache holds
  ``[c | k_R]``**, and the absorbed form reads it as it lies.
- **The indexer** (``ops/dsa_index.py``), in a layer whose
  ``indexer_types`` entry is ``full``: ``q_I = W_IQ c_Q`` (J heads), ``k_I
  = LayerNorm(W_IK h)``, both rotated on their first ``qk_rope_head_dim``
  dimensions and held in ``compute_dtype``, ``w = W_IW h`` (fp32); the
  query attends the ``index_topk``
  positions of largest ``sum_j w_j relu(q_I_j . k_I)``. **The cache holds
  ``k_I``, in ``full`` layers only.** A ``shared`` layer has no indexer: it
  attends the set the nearest ``full`` layer before it chose.
- **FFN**: ``dense`` layers a SwiGLU; ``sparse`` layers ``Shared(h) +``
  the routed experts (``ops/moe.py:routed_experts``: sigmoid scores over
  all ``published_n_routed_experts``, a selection bias, top-k, normalised
  and scaled; **held here: experts** ``[first_expert, first_expert +
  n_routed_experts)``; pairs of absent experts contribute nothing).

**Stacks by layer kind.** A layer's kind is ``<mlp>_<indexer>``
(``dense_full``, ``sparse_shared``, ``sparse_full``, ...); the parameters
of a kind are one stack, in the order its layers appear, and the forward is
a ``lax.scan`` over each run of one kind, with the chosen set carried from
a ``full`` layer into the ``shared`` layers after it (a decode step carries
the chosen positions' rows within a layer's share of the pool ``[B, 1,
K]``, a slice the mask ``[B, T, S]``).

**The cache** (``serving/kv_cache.py:LatentIndexLayout``; ``init_pools``):

- ``latent_pool`` ``[L, N, block, R]``: one row ``[c | k_R | 0]`` a
  position and layer, ``R`` = rank + rope width rounded up to whole
  128-lane tiles (576 -> 640) so that the donated pool is updated in
  place; one gather a layer reads both parts;
- ``index_pool`` ``[L_full, N, block, index_head_dim]``: the ``full``
  layers' indexer keys; the block id that finds a block's latents finds
  its indexer keys, so there is one table.

**Counters from the device** (``PagedModel.step_counters``): after the
pools every program returns ``[expert_pairs, expert_hits]``: the
token-expert pairs that fell to held experts, and the held experts (of
sparse layers x held) that got any, summed over the sparse layers (and
over the rows of a prefill call that runs a row at a time: every pass
reads the experts it hits).

**What every token was routed to** (``PagedModel.token_records``): last,
every program returns ``[B, T, L_sparse * k]`` int32, each token's ``k``
chosen experts (of all the published, held or not) in every sparse layer,
the layers in order. The engine hands a request's back with its result
(``RequestResult.token_records``): whoever recomputes the sequence
elsewhere (a trainer replaying the routing, the benchmark's reference) can
take the same experts, where a near-tie of two scores would else fall the
other way.

**Weights** are held in ``param_dtype`` (bfloat16) and read as they lie;
norm scales, the router and its bias are fp32. There is no training path
and no prediction (MTP) layer (ROADMAP B-M).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from determined_clone_tpu.models.paged import (
    PagedModel,
    cast_leaves,
    run_rows,
)
from determined_clone_tpu.ops import dsa_index, mla_attention as mla
from determined_clone_tpu.ops.layers import layernorm, rmsnorm
from determined_clone_tpu.ops.moe import routed_experts

Params = Dict[str, Any]

DENSE, SPARSE = "dense", "sparse"
FULL, SHARED = "full", "shared"
_LANES = 128

# a prefill call over more tokens than this runs a row at a time, so that
# its fp32 temporaries (the indexer's scores, a pass of attention, the
# experts' pairs) are one row's
PREFILL_TOKENS_PER_PASS = 2048

_PUBLISHED_INDEXERS = (FULL,) * 3 + ((SHARED,) * 3 + (FULL,)) * 18 \
    + (SHARED,) * 3
_PUBLISHED_MLPS = (DENSE,) * 3 + (SPARSE,) * 75


@dataclasses.dataclass(frozen=True)
class GLMMoeDsaConfig:
    """The source's sizes under the source's key names."""
    vocab_size: int = 154880
    hidden_size: int = 6144
    # the layers held, in order
    mlp_layer_types: Tuple[str, ...] = _PUBLISHED_MLPS
    indexer_types: Tuple[str, ...] = _PUBLISHED_INDEXERS
    num_attention_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    # the routed experts held here, of how many, from which one on
    n_routed_experts: int = 256
    published_n_routed_experts: int = 256
    first_expert: int = 0
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    max_position_embeddings: int = 1048576
    rope_theta: float = 8e6
    rms_norm_eps: float = 1e-5
    index_norm_eps: float = 1e-6
    init_std: float = 0.02
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self) -> None:
        if len(self.mlp_layer_types) != len(self.indexer_types):
            raise ValueError("mlp_layer_types and indexer_types name "
                             "different numbers of layers")
        unknown = (set(self.mlp_layer_types) - {DENSE, SPARSE}) \
            | (set(self.indexer_types) - {FULL, SHARED})
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if self.indexer_types[0] != FULL:
            raise ValueError("the first layer held must choose its own "
                             "positions (indexer_types[0] == 'full')")
        if not 0 <= self.first_expert <= self.first_expert \
                + self.n_routed_experts <= self.published_n_routed_experts:
            raise ValueError("the experts held are not among the published")

    @property
    def n_layers(self) -> int:
        return len(self.mlp_layer_types)

    @property
    def n_heads(self) -> int:
        return self.num_attention_heads

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def kinds(self) -> Tuple[str, ...]:
        """Each layer's kind, ``<mlp>_<indexer>``."""
        return tuple(f"{m}_{i}" for m, i in zip(self.mlp_layer_types,
                                                self.indexer_types))

    @property
    def n_full(self) -> int:
        return self.indexer_types.count(FULL)

    @property
    def row_width(self) -> int:
        """Width of a latent row: rank + rope, in whole lane tiles."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // _LANES) \
            * _LANES

    def runs(self) -> List[Tuple[str, int, int, int, int]]:
        """``(kind, lo, hi, first_layer, first_full)`` of every run of one
        kind: layers ``[lo, hi)`` of the kind's stack, which are layers
        ``first_layer...`` of the model, the ``full`` ones among them the
        ``first_full``-th ... ``full`` layers."""
        out: List[Tuple[str, int, int, int, int]] = []
        seen: Dict[str, int] = {}
        n_full = 0
        for layer, kind in enumerate(self.kinds):
            at = seen.get(kind, 0)
            if out and out[-1][0] == kind:
                out[-1] = out[-1][:2] + (at + 1,) + out[-1][3:]
            else:
                out.append((kind, at, at + 1, layer, n_full))
            seen[kind] = at + 1
            n_full += kind.endswith(FULL)
        return out

    @staticmethod
    def tiny() -> "GLMMoeDsaConfig":
        """A toy with the published pattern: one dense layer and four
        expert layers, ``full, shared x 3, full``; 16 experts of which 4
        are held, top-4; a selection of 32 positions."""
        return GLMMoeDsaConfig(
            vocab_size=128, hidden_size=64,
            mlp_layer_types=(DENSE,) + (SPARSE,) * 4,
            indexer_types=(FULL,) + (SHARED,) * 3 + (FULL,),
            num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
            qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
            index_n_heads=2, index_head_dim=8, index_topk=32,
            intermediate_size=128, moe_intermediate_size=32,
            n_routed_experts=4, published_n_routed_experts=16,
            first_expert=4, num_experts_per_tok=4,
            max_position_embeddings=256, init_std=0.1)

    def paged_model(self) -> PagedModel:
        """The family on the serving path (``models/paged.py``)."""
        return PAGED


def layer_shapes(cfg: GLMMoeDsaConfig, kind: str) -> Dict[str, Tuple]:
    """``{leaf path: shape}`` of one layer of ``kind``: matrices end in
    ``kernel``, norm scales in ``scale``, the two biases in ``bias``."""
    D, H = cfg.hidden_size, cfg.num_attention_heads
    nope, rope, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.v_head_dim
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    J, di = cfg.index_n_heads, cfg.index_head_dim
    mlp, indexer = kind.split("_")
    shapes = {
        "ln1/scale": (D,), "q_a/kernel": (D, rq), "q_norm/scale": (rq,),
        "q_b_nope/kernel": (rq, H * nope), "q_b_rope/kernel": (rq, H * rope),
        "kv_a/kernel": (D, rkv + rope), "kv_norm/scale": (rkv,),
        "uk/kernel": (H, nope, rkv), "uv/kernel": (H, rkv, v),
        "attn_out/kernel": (H * v, D), "ln2/scale": (D,),
    }
    if indexer == FULL:
        shapes.update({
            "idx_q/kernel": (rq, J * di), "idx_k/kernel": (D, di),
            "idx_k_norm/scale": (di,), "idx_k_norm/bias": (di,),
            "idx_w/kernel": (D, J)})
    if mlp == DENSE:
        F = cfg.intermediate_size
        shapes.update({"mlp_gate/kernel": (D, F), "mlp_up/kernel": (D, F),
                       "mlp_down/kernel": (F, D)})
    else:
        F, E = cfg.moe_intermediate_size, cfg.n_routed_experts
        shapes.update({
            "router/kernel": (D, cfg.published_n_routed_experts),
            "router/bias": (cfg.published_n_routed_experts,),
            "shared_gate/kernel": (D, F), "shared_up/kernel": (D, F),
            "shared_down/kernel": (F, D),
            "experts_gate/kernel": (E, D, F), "experts_up/kernel": (E, D, F),
            "experts_down/kernel": (E, F, D)})
    return shapes


def init(key: jax.Array, cfg: GLMMoeDsaConfig, *,
         bias_std: float = 0.01) -> Params:
    """Every matrix, the embedding and the head normal(0, init_std), a
    layer at a time (the fp32 draw of a stack of expert layers is larger
    than the chip), in ``param_dtype`` but the router, fp32; norm scales 1,
    the indexer's LayerNorm bias 0; the router's selection bias normal(0,
    ``bias_std``), so that choosing and weighing differ."""
    std, f32 = cfg.init_std, jnp.float32
    keys = iter(jax.random.split(key, 64))

    def leaf(path, shape, n):
        name = path.rsplit("/", 1)[1]
        if name == "scale":
            return jnp.ones((n, *shape), f32)
        if path == "router/bias":
            return bias_std * jax.random.normal(next(keys), (n, *shape), f32)
        if name == "bias":
            return jnp.zeros((n, *shape), f32)
        dtype = f32 if path == "router/kernel" else cfg.param_dtype
        return jax.lax.map(
            lambda k: (std * jax.random.normal(k, shape, f32)).astype(dtype),
            jax.random.split(next(keys), n))

    params: Params = {}
    for kind in sorted(set(cfg.kinds)):
        stack: Params = {}
        for path, shape in layer_shapes(cfg, kind).items():
            group, name = path.split("/")
            stack.setdefault(group, {})[name] = leaf(
                path, shape, cfg.kinds.count(kind))
        params[kind] = stack
    V, D = cfg.vocab_size, cfg.hidden_size
    params["embed"] = {"table": (std * jax.random.normal(
        next(keys), (V, D), f32)).astype(cfg.param_dtype)}
    params["final_norm"] = {"scale": jnp.ones((D,), f32)}
    params["lm_head"] = {"kernel": (std * jax.random.normal(
        next(keys), (D, V), f32)).astype(cfg.param_dtype)}
    return params


_MATRIX = re.compile(r"(^|/)(kernel|table)$")


def serving_params(params: Params, cfg: GLMMoeDsaConfig) -> Params:
    """Every matrix and the embedding in ``compute_dtype``, which the
    products read them in; norm scales, biases and the router fp32."""
    return cast_leaves(
        params, lambda path: cfg.compute_dtype
        if _MATRIX.search(path) and "/router/" not in path else jnp.float32)


def init_pools(cfg: GLMMoeDsaConfig, cache: Any, max_batch: int
               ) -> Tuple[jax.Array, ...]:
    """``(latent_pool, index_pool)``, zeroed (the module's doc-string has
    their shapes)."""
    N, bs = cache.num_blocks, cache.block_size
    return (jnp.zeros((cfg.n_layers, N, bs, cfg.row_width),
                      cfg.compute_dtype),
            jnp.zeros((cfg.n_full, N, bs, cfg.index_head_dim),
                      cfg.compute_dtype))


def _norm(cfg: GLMMoeDsaConfig, p: Params, x: jax.Array,
          dtype: Any = None) -> jax.Array:
    return rmsnorm(p, x, cfg.rms_norm_eps, dtype=dtype or cfg.compute_dtype)


def _matmul(x: jax.Array, p: Params) -> jax.Array:
    """x @ kernel as the kernel lies, summed and returned in fp32."""
    return jnp.matmul(x, p["kernel"], preferred_element_type=jnp.float32)


def _swiglu(cfg: GLMMoeDsaConfig, lp: Params, h: jax.Array,
            name: str) -> jax.Array:
    act = jax.nn.silu(_matmul(h, lp[f"{name}_gate"])) \
        * _matmul(h, lp[f"{name}_up"])
    return _matmul(act.astype(cfg.compute_dtype), lp[f"{name}_down"])


def _mlp(cfg: GLMMoeDsaConfig, kind: str, lp: Params, i: jax.Array,
         x: jax.Array, token_mask: jax.Array):
    """``(x + FFN(norm(x)), [expert_pairs, expert_hits], experts)`` of
    layer ``i`` of its kind's stack; ``experts`` [B, T, k] the experts a
    ``sparse`` layer chose for each token, None of a ``dense`` one."""
    B, T, D = x.shape
    with jax.named_scope("mlp"):
        h32 = _norm(cfg, lp["ln2"], x, jnp.float32)
        h = h32.astype(cfg.compute_dtype)
        if kind.startswith(DENSE):
            return (x + _swiglu(cfg, lp, h, "mlp"),
                    jnp.zeros((2,), jnp.int32), None)
        routed, counts, experts = routed_experts(
            lp, h32.reshape(B * T, D), first_expert=cfg.first_expert,
            n_held=cfg.n_routed_experts,
            n_experts=cfg.published_n_routed_experts,
            k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
            token_mask=token_mask.reshape(-1),
            first_row=i * cfg.n_routed_experts,
            compute_dtype=cfg.compute_dtype)
        return (x + _swiglu(cfg, lp, h, "shared") + routed.reshape(B, T, D),
                counts, experts.reshape(B, T, -1))


def _layer(stack: Params, i: jax.Array) -> Params:
    """Layer ``i`` of a stack, read where it lies. The routed experts stay
    the kind's whole stack, as rows ``[layers * held, ...]``: a tile of
    pairs reads its one expert from it (``routed_experts(first_row=)``); a
    layer's share cut out ahead of that loop would be copied, 1.2 GB a
    layer."""
    return {name: jax.tree.map(
        (lambda w: w.reshape(-1, *w.shape[2:])) if name.startswith("experts_")
        else (lambda w: jax.lax.dynamic_index_in_dim(w, i, keepdims=False)),
        leaves) for name, leaves in stack.items()}


def _attention(cfg: GLMMoeDsaConfig, kind: str, lp: Params, x: jax.Array,
               positions: jax.Array, token_mask: jax.Array,
               latent_rows: jax.Array, index_rows: jax.Array,
               latent_first: jax.Array, index_first: jax.Array,
               tables: jax.Array, idx: Dict[str, jax.Array],
               selection: jax.Array):
    """One layer's attention: ``(x + Attn(norm(x)), latent_rows,
    index_rows, selection)``. ``latent_rows`` [L * N * block, R] and
    ``index_rows`` [L_full * N * block, d_I] are the whole pools as rows;
    ``latent_first`` / ``index_first`` this layer's first block in each;
    ``tables`` [B, W] the sequences' blocks within a layer; ``idx`` the
    call's write addresses (``_write_indices``); ``selection`` what the
    last ``full`` layer chose, replaced if this one is ``full``."""
    B, T, D = x.shape
    H, dt = cfg.num_attention_heads, cfg.compute_dtype
    nope, rope, rank = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.kv_lora_rank
    R, bs = cfg.row_width, idx["block"]
    J, di = cfg.index_n_heads, cfg.index_head_dim
    # a slice is whole blocks and is written a block a piece
    unit = bs if T > 1 else 1

    def write(rows, first, new):
        width = rows.shape[-1]
        piece = (unit, width) if T > 1 else (width,)
        where = jnp.where(idx["scatter"] >= 0,
                          first * (bs // unit) + idx["scatter"],
                          rows.shape[0])
        return rows.reshape(-1, *piece).at[where].set(
            new.reshape(-1, *piece), mode="drop").reshape(-1, width)

    with jax.named_scope("attn"):
        h = _norm(cfg, lp["ln1"], x)
        c_q = _norm(cfg, lp["q_norm"], _matmul(h, lp["q_a"]))
        kv = _matmul(h, lp["kv_a"])
        row = jnp.concatenate([
            _norm(cfg, lp["kv_norm"], kv[..., :rank], jnp.float32),
            mla.rope_interleaved(kv[..., rank:], positions,
                                 base=cfg.rope_theta),
            jnp.zeros((B, T, R - rank - rope), jnp.float32)], axis=-1)
        with jax.named_scope("kv_cache"):
            latent_rows = write(latent_rows, latent_first, row.astype(dt))
        if kind.endswith(FULL):
            with jax.named_scope("dsa_index"):
                # the indexer reads the query latent attention reads, and
                # its weights as they lie; its scores are fp32 sums
                k_i = layernorm(lp["idx_k_norm"], _matmul(h, lp["idx_k"]),
                                cfg.index_norm_eps)
                k_i = mla.rope_interleaved(k_i, positions, rotary=rope,
                                           base=cfg.rope_theta)
                with jax.named_scope("kv_cache"):
                    index_rows = write(index_rows, index_first,
                                       k_i.astype(dt))
                q_i = mla.rope_interleaved(
                    _matmul(c_q, lp["idx_q"]).reshape(B, T, J, di),
                    positions, rotary=rope, base=cfg.rope_theta)
                scores = dsa_index.index_scores(
                    q_i.astype(dt), _matmul(h, lp["idx_w"]),
                    index_rows.reshape(-1, bs, di), index_first + tables,
                    positions, token_mask)
                if T == 1:
                    # carried as rows of a layer's share of the pool: the
                    # shared layers after this one look nothing up
                    at = dsa_index.select(scores, cfg.index_topk)
                    selection = jnp.where(at >= 0, jnp.take_along_axis(
                        tables, jnp.maximum(at[:, 0], 0) // bs,
                        axis=1)[:, None] * bs + at % bs, -1)
                else:
                    selection = dsa_index.select_mask(scores, cfg.index_topk)
        with jax.named_scope("mla_attn"):
            q = mla.absorbed_query(
                _matmul(c_q, lp["q_b_nope"]).reshape(B, T, H, nope),
                mla.rope_interleaved(
                    _matmul(c_q, lp["q_b_rope"]).reshape(B, T, H, rope),
                    positions, base=cfg.rope_theta),
                lp["uk"]["kernel"], R, dt)
            scale = (nope + rope) ** -0.5
            here = latent_first + tables
            if T == 1:
                o = mla.mla_decode(
                    q, latent_rows,
                    latent_first * bs + jnp.maximum(selection[:, 0], 0),
                    selection[:, 0] >= 0, scale=scale)
            else:
                o = mla.mla_slice(q, latent_rows.reshape(-1, bs, R), here,
                                  selection, positions, token_mask,
                                  scale=scale, rank=rank)
            o = mla.expand_values(o, lp["uv"]["kernel"])
        x = x + _matmul(o.reshape(B, T, -1).astype(dt), lp["attn_out"])
    return x, latent_rows, index_rows, selection


def _write_indices(positions: jax.Array, token_mask: jax.Array,
                   tables: jax.Array, block: int) -> Dict[str, Any]:
    """Where a call's rows go within one layer's share of a pool; -1 =
    nowhere. A slice's ``scatter`` is [B * T / block] blocks (it starts on
    a block boundary with its real tokens first, so a block's first token
    says whether it holds any); a decode step's is [B] rows."""
    if positions.shape[1] == 1:
        row = jnp.take_along_axis(tables, positions // block, axis=1) \
            * block + positions % block
        scatter = jnp.where(token_mask, row, -1).reshape(-1)
    else:
        blk = jnp.take_along_axis(tables, positions[:, ::block] // block,
                                  axis=1)
        scatter = jnp.where(token_mask[:, ::block], blk, -1).reshape(-1)
    return {"scatter": scatter, "block": block}


def _paged_backbone(params: Params, cfg: GLMMoeDsaConfig, tokens: jax.Array,
                    positions: jax.Array, token_mask: jax.Array,
                    latent_pool: jax.Array, index_pool: jax.Array,
                    block_tables: jax.Array, *, collect: bool = False):
    """Embed -> the runs of layers: ``(x [B, T, D] fp32, latent_pool,
    index_pool, counts [2], routing [B, T, L_sparse * k] int32,
    selections)``; the pools ride the layer scans as rows and are updated
    in place. ``routing`` is every ``sparse`` layer's choice of experts
    for each token, the layers in order. With ``collect``, ``selections``
    is every ``full`` layer's choice, stacked (else None)."""
    B, T = tokens.shape
    L, N, bs, R = latent_pool.shape
    di = index_pool.shape[-1]
    idx = _write_indices(positions, token_mask, block_tables, bs)

    with jax.named_scope("embed"):
        x = jnp.take(params["embed"]["table"], tokens,
                     axis=0).astype(jnp.float32)

    S = block_tables.shape[1] * bs
    empty = jnp.full((B, 1, min(cfg.index_topk, S)), -1, jnp.int32) \
        if T == 1 else jnp.zeros((B, T, S), bool)
    state = (x, latent_pool.reshape(-1, R), index_pool.reshape(-1, di),
             empty, jnp.zeros((2,), jnp.int32))
    chosen, routing = [], []
    for kind, lo, hi, first_layer, first_full in cfg.runs():
        def body(carry, i, kind=kind, lo=lo, first_layer=first_layer,
                 first_full=first_full):
            x, latent_rows, index_rows, selection, counts = carry
            lp = _layer(params[kind], i)
            x, latent_rows, index_rows, selection = _attention(
                cfg, kind, lp, x, positions, token_mask, latent_rows,
                index_rows, (first_layer + i - lo) * N,
                (first_full + i - lo) * N, block_tables, idx, selection)
            x, hit, experts = _mlp(cfg, kind, lp, i, x, token_mask)
            return (x, latent_rows, index_rows, selection, counts + hit), \
                (selection if collect and kind.endswith(FULL) else None,
                 experts)

        state, (picked, experts) = jax.lax.scan(
            body, state, jnp.arange(lo, hi, dtype=jnp.int32))
        if picked is not None:
            chosen.append(picked)
        if experts is not None:                       # [layers, B, T, k]
            routing.extend(experts[j] for j in range(hi - lo))
    x, latent_rows, index_rows, _, counts = state
    return (x, latent_rows.reshape(latent_pool.shape),
            index_rows.reshape(index_pool.shape), counts,
            jnp.concatenate(routing, axis=-1) if routing
            else jnp.zeros((B, T, 0), jnp.int32),
            jnp.concatenate(chosen) if chosen else None)


def _paged_logits(params: Params, cfg: GLMMoeDsaConfig, tokens: jax.Array,
                  positions: jax.Array, token_mask: jax.Array,
                  last_index: Any, pools: Tuple[jax.Array, ...],
                  block_tables: jax.Array):
    """Logits at ``last_index`` [B] of each row ([B, V]) or, with None, at
    every position ([B, T, V]); the batch in one pass or, over
    ``PREFILL_TOKENS_PER_PASS`` tokens, a row at a time. A slice is padded
    to whole cache blocks. Returns ``(logits, latent_pool, index_pool,
    counts, routing [B, T, L_sparse * k])``."""
    T = tokens.shape[1]

    def run(tokens, positions, token_mask, tables, last, *pools):
        x, latent_pool, index_pool, counts, routing, _ = _paged_backbone(
            params, cfg, tokens, positions, token_mask, *pools, tables)
        x = x[:, :T] if last is None else jnp.take_along_axis(
            x, last[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        with jax.named_scope("logits"):
            h = _norm(cfg, params["final_norm"], x)
            return (_matmul(h, params["lm_head"]), latent_pool, index_pool,
                    counts, routing[:, :T])

    return run_rows(run, tokens, positions, token_mask, block_tables,
                    last_index, pools, block=pools[0].shape[2],
                    tokens_per_pass=PREFILL_TOKENS_PER_PASS, counters=2)


def forward_paged(params: Params, cfg: GLMMoeDsaConfig, tokens: jax.Array,
                  positions: jax.Array, token_mask: jax.Array,
                  last_index: jax.Array, latent_pool: jax.Array,
                  index_pool: jax.Array, block_tables: jax.Array):
    """A prefill slice or a decode step over this family's cache; the
    argument contract of ``models/gpt.py:forward_paged`` with these
    differences. The pools are two (``init_pools``). A row's real tokens
    are consecutive positions and come first; a slice of more than one
    token starts on a block boundary (the engine's do:
    ``LatentIndexLayout.check_prefill``).

    Returns ``(logits [B, V] fp32 at each row's last real token,
    latent_pool, index_pool, counts [2] int32, routing [B, T, L_sparse *
    k] int32)``; ``counts`` is the call's ``PAGED.step_counters``,
    ``routing`` its ``PAGED.token_records``.
    """
    return _paged_logits(params, cfg, tokens, positions, token_mask,
                         last_index, (latent_pool, index_pool), block_tables)


def forward_paged_logits(params: Params, cfg: GLMMoeDsaConfig,
                         tokens: jax.Array, positions: jax.Array,
                         token_mask: jax.Array, latent_pool: jax.Array,
                         index_pool: jax.Array, block_tables: jax.Array):
    """``forward_paged`` returning the logits at every position:
    ``(logits [B, T, V] fp32, the two pools, counts, routing)``. The tests compare
    it with the reference; the engine's speculative verify step is refused
    for this family (the prediction layer that would draft is not held:
    ROADMAP B-M)."""
    return _paged_logits(params, cfg, tokens, positions, token_mask, None,
                         (latent_pool, index_pool), block_tables)


def _cache_layout(cfg: GLMMoeDsaConfig, cache: Any) -> Any:
    # imported here: serving/ imports the models at import time
    from determined_clone_tpu.serving.kv_cache import LatentIndexLayout

    return LatentIndexLayout(cache, cfg.max_seq_len, topk=cfg.index_topk)


def _prefill_counts(cfg: GLMMoeDsaConfig, layout: Any,
                    starts: Sequence[int], counts: Sequence[int],
                    length: int) -> Dict[str, int]:
    """The key tiles a call's slices multiply in every layer's latent
    attention, beside those whole tables would cost."""
    return mla.key_tiles(starts, counts, length, cfg.num_attention_heads,
                         layout.table_width, layout.cache.block_size,
                         layers=len(cfg.kinds))


PAGED = PagedModel(
    family="glm_moe_dsa", forward_paged=forward_paged,
    forward_paged_logits=forward_paged_logits, init=init,
    cache_layout=_cache_layout, serving_params=serving_params,
    init_pools=init_pools, pool_names=("latent_pool", "index_pool"),
    # no prediction layer is held to draft from; the tiers address K and V
    # pools by name; sharing a prefix would need a slice that starts
    # inside a block (a tail hit) and is not tried (ROADMAP B-M)
    unsupported=("prefix_cache", "kv_store", "speculative"),
    row_counters=("serving_dsa_scored_rows_total",
                  "serving_dsa_selected_rows_total"),
    step_counters=("expert_pairs", "expert_hits"),
    token_records=True, prefill_counts=_prefill_counts)
