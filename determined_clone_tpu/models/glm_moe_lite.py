"""GLM-4.7-Flash (``model_type`` ``glm4_moe_lite``) on the training path, as
one member of an expert-parallel group: latent attention through the flash
kernels, a dropless expert layer with a reverse mode and an
auxiliary-loss-free selection bias, and a multi-token prediction module in
the loss.

From the published configuration
(https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json); its
layers are the DeepSeek-V2 / V3 block (arXiv:2405.04434 section 2.1,
arXiv:2412.19437 sections 2.1.2 and 2.2). ``benchmarks/reference/
glm4_moe_lite.py`` is the same mathematics in plain ``jax.numpy`` and lists
what the configuration does not state. ``x`` is the fp32 residual stream;
every layer is ``x += Attn(norm(x))`` then ``x += FFN(norm(x))`` (RMSNorm);
the head is untied.

- **Attention** (scopes ``attn`` / ``mla_attn``), un-absorbed, H heads:
  ``q = W_QB norm(W_QA h)``, a head ``[q_N | q_R]``; ``[c | k_R] = W_KVA h``
  with ``c`` normed and ``k_R`` one for all heads; rotary (interleaved
  pairs, ``ops/mla_attention.py:rope_interleaved``) on ``q_R`` and ``k_R``;
  ``[k_N | v] = W_KVB c`` a head. Keys ``[k_N | k_R]`` and values are
  expanded a head and go, with the queries, through
  ``ops/flash_attention.py`` as ``[B, T, H, d]`` ``compute_dtype`` tensors
  (compiled on the chip, interpreted off it; under a mesh of several
  devices inside ``shard_map``, as ``models/gpt.py`` calls it). The kernels
  take one head size for queries, keys and values: ``qk_nope_head_dim +
  qk_rope_head_dim == v_head_dim`` (192 + 64 = 256 as published).
- **FFN** (scope ``mlp``): the first ``first_k_dense_replace`` layers a
  SwiGLU; the others ``Shared(h)`` (``moe_shared``) plus the routed experts
  (``ops/moe.py:routed_experts_trained``, scopes ``moe_route`` and
  ``moe_experts``: sigmoid scores over all ``published_n_routed_experts``,
  a selection bias, top-k, normalised and scaled; **held here: experts**
  ``[first_expert, first_expert + n_routed_experts)``; dropless; pairs of
  absent experts contribute nothing and nothing stands in for the exchange).
- **The prediction module** (scope ``mtp``, one): ``h' = W_EH [norm_e(Emb(
  t_{i+1})) ; norm_h(x_i)]`` with ``x_i`` the stack's output before the
  final norm, one expert-kind layer on ``h'``, a final norm of its own and
  the model's head. ``L = CE(t_{i+1} | x_i) + mtp_loss_weight * CE(t_{i+2}
  | h'_i)``: the first over every position, the second over those that
  have a second target.
- **The selection bias** rides in ``params`` (``.../router/bias``), so
  that checkpoints, sharding rules and whoever compares states see it, and
  no gradient trains it: ``loss_fn`` returns every expert layer's load
  (tokens that chose each of all the experts, this chip's rows) as
  ``statistics`` beside the loss, and :func:`update_selection_bias` moves
  ``b_e += bias_update_rate * sign(mean load - load_e)`` after the
  optimizer's step (``JaxTrial.apply_statistics``). Its optimizer must
  leave it alone: :func:`trained_mask` says which leaves weight decay may
  touch (its gradient is exactly zero, so Adam's moments stay zero and
  the global-norm clip does not see it).

**Stacks by FFN kind**: ``params["dense"]`` and ``params["sparse"]`` hold
their layers stacked, in order; the forward is one ``lax.scan`` a run, each
layer under ``jax.checkpoint`` (``remat``: the backward pass makes all of a
layer again but the flash kernel's output and log-sum-exp, which are kept,
so the kernel's forward runs once). The two heads' fp32 logits are
made one after the other, each under ``jax.checkpoint``, so that one
``[B T, V]`` block and its gradient are alive at a time.

Parameters fp32; products ``compute_dtype`` operands summed in fp32; norm
statistics, softmax, router, gates, logits and losses fp32. There is no
serving path (``ROADMAP.md`` B-M: the prediction module as a drafter).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from determined_clone_tpu.ops.layers import rmsnorm, softmax_cross_entropy
from determined_clone_tpu.ops.mla_attention import rope_interleaved
from determined_clone_tpu.ops.moe import routed_experts_trained
from determined_clone_tpu.parallel.sharding import ShardingRules

Params = Dict[str, Any]

DENSE, SPARSE, MTP = "dense", "sparse", "mtp"


@dataclasses.dataclass(frozen=True)
class GLMMoeLiteConfig:
    """The source's sizes under the source's key names."""
    vocab_size: int = 154880
    hidden_size: int = 2048
    num_hidden_layers: int = 47
    first_k_dense_replace: int = 1
    num_nextn_predict_layers: int = 1
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    # the routed experts held here, of how many, from which one on
    n_routed_experts: int = 64
    published_n_routed_experts: int = 64
    first_expert: int = 0
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    max_position_embeddings: int = 202752
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    init_std: float = 0.02
    bias_update_rate: float = 1e-3
    mtp_loss_weight: float = 0.3
    remat: bool = True
    compute_dtype: Any = jnp.bfloat16

    def __post_init__(self) -> None:
        if self.qk_nope_head_dim + self.qk_rope_head_dim != self.v_head_dim:
            raise ValueError(
                "the flash kernels take one head size: qk_nope_head_dim + "
                f"qk_rope_head_dim ({self.qk_head_dim}) must equal "
                f"v_head_dim ({self.v_head_dim})")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace outside the stack")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("one prediction module at most")
        if not 0 <= self.first_expert <= self.first_expert \
                + self.n_routed_experts <= self.published_n_routed_experts:
            raise ValueError("the held experts are not among the published")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def n_sparse(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @staticmethod
    def tiny() -> "GLMMoeLiteConfig":
        return GLMMoeLiteConfig(
            vocab_size=256, hidden_size=64, num_hidden_layers=3,
            num_attention_heads=4, q_lora_rank=32, kv_lora_rank=32,
            qk_nope_head_dim=48, qk_rope_head_dim=16, v_head_dim=64,
            intermediate_size=128, moe_intermediate_size=32,
            n_routed_experts=4, published_n_routed_experts=16,
            first_expert=4, num_experts_per_tok=3,
            max_position_embeddings=128)


def layer_shapes(cfg: GLMMoeLiteConfig, kind: str) -> Dict[str, Tuple]:
    """``{leaf path: shape}`` of one layer of ``kind``: matrices end in
    ``kernel``, norm scales in ``scale``, the selection bias in ``bias``."""
    D, H = cfg.hidden_size, cfg.num_attention_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    shapes = {
        "ln1/scale": (D,), "q_a/kernel": (D, rq), "q_norm/scale": (rq,),
        "q_b/kernel": (rq, H * cfg.qk_head_dim),
        "kv_a/kernel": (D, rkv + cfg.qk_rope_head_dim),
        "kv_norm/scale": (rkv,),
        "kv_b/kernel": (rkv, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "attn_out/kernel": (H * cfg.v_head_dim, D), "ln2/scale": (D,),
    }
    if kind == DENSE:
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


def param_shapes(cfg: GLMMoeLiteConfig) -> Dict[str, Tuple]:
    """``{leaf path: shape}`` of the whole model, stacks with their leading
    layer dimension (a kind with no layer is left out)."""
    D, V = cfg.hidden_size, cfg.vocab_size
    shapes: Dict[str, Tuple] = {"embed/table": (V, D)}
    for kind, n in ((DENSE, cfg.first_k_dense_replace),
                    (SPARSE, cfg.n_sparse)):
        if n:
            shapes.update({f"{kind}/{path}": (n, *shape) for path, shape
                           in layer_shapes(cfg, kind).items()})
    if cfg.num_nextn_predict_layers:
        shapes.update({
            f"{MTP}/enorm/scale": (D,), f"{MTP}/hnorm/scale": (D,),
            f"{MTP}/eh_proj/kernel": (2 * D, D),
            f"{MTP}/final_norm/scale": (D,)})
        shapes.update({f"{MTP}/layer/{path}": (1, *shape) for path, shape
                       in layer_shapes(cfg, SPARSE).items()})
    shapes.update({"final_norm/scale": (D,), "lm_head/kernel": (D, V)})
    return shapes


def init(key: jax.Array, cfg: GLMMoeLiteConfig, *, bias_std: float = 0.01,
         embedding_std: Optional[float] = None) -> Params:
    """fp32: every matrix normal(0, ``init_std``) (the embedding normal(0,
    ``embedding_std``) where that is given), norm scales 1, the selection
    bias normal(0, ``bias_std``) so that choosing and weighing differ from
    the first step."""
    shapes = param_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    params: Params = {}
    for k, (path, shape) in zip(keys, sorted(shapes.items())):
        name = path.rsplit("/", 1)[1]
        if name == "scale":
            leaf = jnp.ones(shape, jnp.float32)
        else:
            std = bias_std if name == "bias" else cfg.init_std
            if path == "embed/table" and embedding_std is not None:
                std = embedding_std
            leaf = std * jax.random.normal(k, shape, jnp.float32)
        node = params
        *groups, last = path.split("/")
        for group in groups:
            node = node.setdefault(group, {})
        node[last] = leaf
    return params


def _is_selection_bias(path: Tuple[Any, ...]) -> bool:
    return [getattr(k, "key", None) for k in path[-2:]] == ["router", "bias"]


def trained_mask(params: Params) -> Params:
    """True for the leaves a gradient trains, False for the selection
    biases (an ``optax`` mask: ``optax.adamw(..., mask=trained_mask)``)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: not _is_selection_bias(path), params)


# Megatron-style rules as GPT's, with the experts' leading dimension over
# ``ep``. Stacked leaves lead with their layer dimension. (Rules only: the
# layer runs without its exchange, so a mesh with ep > 1 is not trained yet.)
GLM_MOE_LITE_SHARDING_RULES = ShardingRules(rules=[
    (r"embed/table$",                 P("tp", "fsdp")),          # [V, D]
    (r"lm_head/kernel$",              P("fsdp", "tp")),          # [D, V]
    (r"experts_(gate|up)/kernel$",    P(None, "ep", "fsdp", "tp")),
    (r"experts_down/kernel$",         P(None, "ep", "tp", "fsdp")),
    (r"router/",                      P()),
    (r"(q_a|kv_a|eh_proj)/kernel$",   P(None, "fsdp", None)),
    (r"(q_b|kv_b|mlp_gate|mlp_up|shared_gate|shared_up)/kernel$",
     P(None, "fsdp", "tp")),
    (r"(attn_out|mlp_down|shared_down)/kernel$", P(None, "tp", "fsdp")),
    (r"/scale$",                      P()),
])
# q, k, v as the flash kernel sees them: rows and heads are independent
FLASH_QKV_SPEC = P(("dp", "fsdp"), None, "tp", None)


def _norm(cfg: GLMMoeLiteConfig, p: Params, x: jax.Array,
          dtype: Any = None) -> jax.Array:
    return rmsnorm(p, x, cfg.rms_norm_eps, dtype=dtype or cfg.compute_dtype)


def _matmul(cfg: GLMMoeLiteConfig, x: jax.Array, p: Params) -> jax.Array:
    """x @ kernel, ``compute_dtype`` operands, summed and returned in
    fp32."""
    return jnp.matmul(x.astype(cfg.compute_dtype),
                      p["kernel"].astype(cfg.compute_dtype),
                      preferred_element_type=jnp.float32)


def _swiglu(cfg: GLMMoeLiteConfig, lp: Params, h: jax.Array,
            name: str) -> jax.Array:
    act = jax.nn.silu(_matmul(cfg, h, lp[f"{name}_gate"])) \
        * _matmul(cfg, h, lp[f"{name}_up"])
    return _matmul(cfg, act, lp[f"{name}_down"])


def _flash(q: jax.Array, k: jax.Array, v: jax.Array,
           mesh: Optional[Any]) -> jax.Array:
    """Causal flash attention over q, k, v [B, T, H, d], each device over
    its own rows and heads when ``mesh`` spans devices; T is padded to what
    the kernels tile (causal: padding is seen by no real query)."""
    from determined_clone_tpu.ops.flash_attention import (
        flash_attention_per_shard,
        seq_multiple,
    )

    T = q.shape[1]
    pad = -T % seq_multiple(T)
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for t in (q, k, v))
    return flash_attention_per_shard(q, k, v, mesh, FLASH_QKV_SPEC)[:, :T]


def _attention(cfg: GLMMoeLiteConfig, lp: Params, x: jax.Array,
               positions: jax.Array, mesh: Optional[Any]) -> jax.Array:
    B, T, _ = x.shape
    H, dt = cfg.num_attention_heads, cfg.compute_dtype
    nope, rank = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    with jax.named_scope("attn"):
        h = _norm(cfg, lp["ln1"], x)
        with jax.named_scope("mla_attn"):
            c_q = _norm(cfg, lp["q_norm"], _matmul(cfg, h, lp["q_a"]))
            q = _matmul(cfg, c_q, lp["q_b"]).reshape(B, T, H, -1)
            q = jnp.concatenate([
                q[..., :nope], rope_interleaved(
                    q[..., nope:], positions, base=cfg.rope_theta)], axis=-1)
            kv = _matmul(cfg, h, lp["kv_a"])
            c = _norm(cfg, lp["kv_norm"], kv[..., :rank])
            k_rope = rope_interleaved(kv[..., rank:], positions,
                                      base=cfg.rope_theta)
            kv = _matmul(cfg, c, lp["kv_b"]).reshape(B, T, H, -1)
            k = jnp.concatenate([
                kv[..., :nope], jnp.broadcast_to(
                    k_rope[:, :, None, :], (B, T, H, k_rope.shape[-1]))],
                axis=-1)
            o = _flash(q.astype(dt), k.astype(dt), kv[..., nope:].astype(dt),
                       mesh)
            return x + _matmul(cfg, o.reshape(B, T, -1), lp["attn_out"])


def _ffn(cfg: GLMMoeLiteConfig, kind: str, lp: Params, x: jax.Array
         ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """``(x + FFN(norm(x)), the expert layer's statistics or None)``."""
    B, T, D = x.shape
    with jax.named_scope("mlp"):
        h32 = _norm(cfg, lp["ln2"], x, jnp.float32)
        if kind == DENSE:
            return x + _swiglu(cfg, lp, h32, "mlp"), None
        with jax.named_scope("moe_shared"):
            shared = _swiglu(cfg, lp, h32, "shared")
        routed, stats = routed_experts_trained(
            lp, h32.reshape(B * T, D), first_expert=cfg.first_expert,
            n_experts=cfg.published_n_routed_experts,
            k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
            compute_dtype=cfg.compute_dtype)
        return x + shared + routed.reshape(B, T, D), stats


def _run(cfg: GLMMoeLiteConfig, kind: str, stack: Params, x: jax.Array,
         positions: jax.Array, mesh: Optional[Any]
         ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """A run of layers of one kind, scanned; statistics stacked a layer."""
    def layer(x, lp):
        x = _attention(cfg, lp, x, positions, mesh)
        return _ffn(cfg, kind, lp, x)

    if cfg.remat:
        from determined_clone_tpu.ops.flash_attention import (
            save_flash_residuals,
        )

        layer = jax.checkpoint(layer, policy=save_flash_residuals)
    return jax.lax.scan(layer, x, stack)


def _embed(cfg: GLMMoeLiteConfig, params: Params, tokens: jax.Array
           ) -> jax.Array:
    with jax.named_scope("embed"):
        return jnp.take(params["embed"]["table"], tokens,
                        axis=0).astype(jnp.float32)


def backbone(params: Params, cfg: GLMMoeLiteConfig, tokens: jax.Array, *,
             mesh: Optional[Any] = None
             ) -> Tuple[jax.Array, Dict[str, Any]]:
    """tokens int32 [B, T] -> ``(x [B, T, D] fp32, the stack's output
    before the final norm; {"sparse": the expert layers' statistics,
    stacked})``."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    x = _embed(cfg, params, tokens)
    stats: Dict[str, Any] = {}
    if cfg.first_k_dense_replace:
        x, _ = _run(cfg, DENSE, params[DENSE], x, positions, mesh)
    if cfg.n_sparse:
        x, stats[SPARSE] = _run(cfg, SPARSE, params[SPARSE], x, positions,
                                mesh)
    return x, stats


def _predict(params: Params, cfg: GLMMoeLiteConfig, x: jax.Array,
             next_tokens: jax.Array, mesh: Optional[Any]
             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The prediction module's hidden state before its final norm: ``x``
    [B, T, D] the stack's output, ``next_tokens`` [B, T] the token after
    each position."""
    B, T = next_tokens.shape
    mp = params[MTP]
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    with jax.named_scope("embed"):
        e = _norm(cfg, mp["enorm"], _embed(cfg, params, next_tokens))
        h = _matmul(cfg, jnp.concatenate(
            [e, _norm(cfg, mp["hnorm"], x)], axis=-1), mp["eh_proj"])
    return _run(cfg, SPARSE, mp["layer"], h, positions, mesh)


def _head_loss(cfg: GLMMoeLiteConfig, norm: Params, head: Params,
               x: jax.Array, targets: jax.Array, weights: jax.Array
               ) -> jax.Array:
    """Weighted mean cross-entropy of ``W_head norm(x)``, fp32 logits;
    under ``remat`` the logits are made again in the backward pass and
    kept by nobody."""
    def loss(norm, head, x):
        with jax.named_scope("logits"):
            logits = jnp.matmul(
                _norm(cfg, norm, x), head["kernel"].astype(cfg.compute_dtype),
                preferred_element_type=jnp.float32)
            per_token = softmax_cross_entropy(logits, targets)
            return jnp.sum(per_token * weights) / jnp.sum(weights)

    return (jax.checkpoint(loss) if cfg.remat else loss)(norm, head, x)


def loss_fn(params: Params, cfg: GLMMoeLiteConfig, tokens: jax.Array,
            targets: jax.Array, *, mesh: Optional[Any] = None
            ) -> Tuple[jax.Array, Dict[str, jax.Array], Dict[str, Any]]:
    """``(L, metrics, statistics)`` of tokens [B, T] and targets [B, T]
    (the token after each position): ``L = loss_next + mtp_loss_weight *
    loss_mtp``; ``metrics``: the two losses and, from the expert layers
    (the prediction module's among them), ``moe_pairs_held`` and
    ``moe_experts_hit`` summed over the layers and
    ``moe_load_max_over_mean`` of the worst; ``statistics``: what
    :func:`update_selection_bias` takes."""
    x, stats = backbone(params, cfg, tokens, mesh=mesh)
    ones = jnp.ones(targets.shape, jnp.float32)
    loss_next = _head_loss(cfg, params["final_norm"], params["lm_head"], x,
                           targets, ones)
    loss, metrics = loss_next, {"loss_next": loss_next}
    if cfg.num_nextn_predict_layers:
        with jax.named_scope("mtp"):
            h, stats[MTP] = _predict(params, cfg, x, targets, mesh)
            # position i predicts t_{i+2} = targets[i + 1]; the last has none
            second = jnp.roll(targets, -1, axis=1)
            loss_mtp = _head_loss(
                cfg, params[MTP]["final_norm"], params["lm_head"], h, second,
                ones.at[:, -1].set(0.0))
        loss = loss + cfg.mtp_loss_weight * loss_mtp
        metrics["loss_mtp"] = loss_mtp
    if stats:
        load = jnp.concatenate([s["load"] for s in stats.values()])
        metrics.update(
            moe_pairs_held=sum(jnp.sum(s["pairs_held"])
                               for s in stats.values()),
            moe_experts_hit=sum(jnp.sum(s["experts_hit"])
                                for s in stats.values()).astype(jnp.float32),
            moe_load_max_over_mean=jnp.max(
                jnp.max(load, axis=-1) / jnp.mean(load, axis=-1)))
    return loss, metrics, {name: s["load"] for name, s in stats.items()}


def chosen_experts(params: Params, cfg: GLMMoeLiteConfig, tokens: jax.Array,
                   targets: jax.Array, *, mesh: Optional[Any] = None
                   ) -> Dict[str, jax.Array]:
    """Forward only: the experts every token chose, ``{"sparse": [L, B T,
    k], "mtp": [1, B T, k]}`` int32, for whoever recomputes the step
    elsewhere and wants the same choices where two scores nearly tie."""
    x, stats = backbone(params, cfg, tokens, mesh=mesh)
    if cfg.num_nextn_predict_layers:
        stats[MTP] = _predict(params, cfg, x, targets, mesh)[1]
    return {name: s["experts"] for name, s in stats.items()}


def update_selection_bias(params: Params, cfg: GLMMoeLiteConfig,
                          loads: Dict[str, jax.Array]) -> Params:
    """``b_e += bias_update_rate * sign(mean load - load_e)`` in every
    expert layer (DeepSeek-V3, 2.1.2), from ``loss_fn``'s statistics."""
    def moved(router: Params, load: jax.Array) -> Params:
        error = jnp.mean(load, axis=-1, keepdims=True) - load
        return {**router, "bias": router["bias"]
                + cfg.bias_update_rate * jnp.sign(error)}

    with jax.named_scope("optimizer"), jax.named_scope("bias_update"):
        out = dict(params)
        if SPARSE in loads:
            out[SPARSE] = {**params[SPARSE], "router": moved(
                params[SPARSE]["router"], loads[SPARSE])}
        if MTP in loads:
            layer = params[MTP]["layer"]
            out[MTP] = {**params[MTP], "layer": {
                **layer, "router": moved(layer["router"], loads[MTP])}}
        return out
