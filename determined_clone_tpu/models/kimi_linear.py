"""Kimi-Linear (``model_type`` ``kimi_linear``) — delta-rule linear
attention beside latent attention read whole, over sparse experts with a
shared expert, on the serving path, as one member of an expert-parallel
group.

From the published configuration
(https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json)
and the Kimi Linear report (arXiv:2510.26692, "Kimi Delta Attention");
``benchmarks/reference/kimi_linear.py`` is the same mathematics over a
whole sequence, with no cache, and lists what the configuration does not
state. ``x`` is the fp32 residual stream; every layer is ``x +=
Attn(norm(x))`` then ``x += FFN(norm(x))`` (RMSNorm); the head is untied,
``W_head norm(x)``. A layer's kind is ``<attention>_<ffn>``:

- **``kda``** (``ops/kda.py``), H heads of d: ``[q | k | v] =
  SiLU(conv(W_qkv h))``, the causal depthwise convolution of
  ``short_conv_kernel_size`` taps over positions, no bias; ``q, k``
  L2-normalised a head, ``q`` scaled by ``d ** -0.5``; the log decay ``g =
  -exp(A_log) * softplus(W_fb W_fa h + dt_bias)`` a channel and the write
  strength ``b = sigmoid(W_b h)`` a head, both fp32; the gated delta rule
  over a state ``[d, d]`` fp32 a head; ``y = W_o (norm_head(o) *
  sigmoid(W_gb W_ga h))``, the norm over one head's output, its scale
  shared by the heads.
- **``mla``** (``ops/mla_attention.py``), no rotary on any part
  (``mla_use_nope``) and no query latent (``q_lora_rank`` null): ``[q_N |
  q_R] = W_q h`` a head, ``[c | k_R] = W_kva h`` with ``c`` normed; head
  ``i``'s key is ``[W_UK_i c | k_R]`` and its value ``W_UV_i c``, neither
  ever formed (the absorbed form); softmax scale ``(nope + rope) ** -0.5``,
  causal over **every** cached position.
- **FFN**: ``dense`` a SwiGLU; ``sparse`` ``Shared(h) +`` the routed
  experts (``ops/moe.py:routed_experts``: sigmoid scores over all
  ``published_num_experts``, a selection bias, top-k, normalised over the
  chosen and scaled; **held here: experts** ``[first_expert, first_expert +
  num_experts)``; pairs of absent experts contribute nothing).

**Stacks by layer kind**, as ``models/glm_moe_dsa.py`` keeps them: the
parameters of a kind are one stack, in the order its layers appear, and the
forward is a ``lax.scan`` over each run of one kind.

**The cache** (``serving/kv_cache.py:StateSlotLayout``; ``init_pools``)
holds three things of two lifetimes:

- ``latent_pool`` ``[L_mla, N, block, R]``: one row ``[c | k_R | 0]`` a
  position in the ``mla`` layers, ``R`` = rank + rope width rounded up to
  whole 128-lane tiles (576 -> 640); it grows a position at a time. A
  decode step reads a row's blocks through its table up to its real length
  (``mla_decode_dense``), a slice through the same table in one Pallas
  kernel under the causal mask (``mla_slice``);
- ``state_pool`` ``[L_kda, slots, H, d, d]`` fp32: a ``kda`` layer's state
  of each sequence;
- ``tail_pool`` ``[L_kda, slots, K - 1, 3 H d]``: the last ``K - 1`` rows
  of ``W_qkv h`` before the convolution, in ``compute_dtype``.

Neither of the last two grows; one slot, the last entry of the sequence's
table row, names both. They are read as zero by the call that holds the
sequence's position 0, carried from one prefill slice to the next, advanced
over real tokens only (a padded bucket neither decays the state nor shifts
the tail), and written back by every call.

After the pools every program returns ``[expert_pairs, expert_hits]``
(``PagedModel.step_counters``) and, last, each token's chosen experts in
every ``sparse`` layer ``[B, T, L_sparse * k]`` (``PagedModel.
token_records``), as ``models/glm_moe_dsa.py`` does and for its reason.

**Weights** are held in ``param_dtype`` (bfloat16) and read as they lie;
norm scales, the convolution's taps, ``A_log``, ``dt_bias``, the router and
its bias are fp32. There is no training path (ROADMAP B-M).
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
from determined_clone_tpu.ops import mla_attention as mla
from determined_clone_tpu.ops.kda import kda, short_conv
from determined_clone_tpu.ops.layers import rmsnorm
from determined_clone_tpu.ops.moe import routed_experts

Params = Dict[str, Any]

KDA, MLA = "kda", "mla"
DENSE, SPARSE = "dense", "sparse"
_LANES = 128
L2_EPS = 1e-6

# a prefill call over more tokens than this runs a row at a time, so that
# its fp32 temporaries (a chunk's decays, a pass of attention, the experts'
# pairs) are one row's
PREFILL_TOKENS_PER_PASS = 2048

_PUBLISHED_FULL = (4, 8, 12, 16, 20, 24, 27)
_PUBLISHED_KDA = tuple(i for i in range(1, 28) if i not in _PUBLISHED_FULL)


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """The source's sizes under the source's key names
    (``linear_attn_config``'s ``kda_layers``, ``full_attn_layers``,
    ``num_heads``, ``head_dim`` and ``short_conv_kernel_size`` with a
    ``kda_`` where they would clash)."""
    vocab_size: int = 163840
    hidden_size: int = 2304
    # the layers held, numbered from 1 in order: which are KDA, which MLA,
    # and how many leading ones have a dense FFN
    num_hidden_layers: int = 27
    kda_layers: Tuple[int, ...] = _PUBLISHED_KDA
    full_attn_layers: Tuple[int, ...] = _PUBLISHED_FULL
    first_k_dense_replace: int = 1
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    # the routed experts held here, of how many, from which one on
    num_experts: int = 256
    published_num_experts: int = 256
    first_expert: int = 0
    num_experts_per_token: int = 8
    routed_scaling_factor: float = 2.446
    model_max_length: int = 1048576
    rms_norm_eps: float = 1e-5
    init_std: float = 0.02
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self) -> None:
        layers = sorted(self.kda_layers + self.full_attn_layers)
        if layers != list(range(1, self.num_hidden_layers + 1)):
            raise ValueError(
                "kda_layers and full_attn_layers together do not name "
                f"layers 1..{self.num_hidden_layers} once each")
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
        return self.model_max_length

    @property
    def kinds(self) -> Tuple[str, ...]:
        """Each layer's kind, ``<attention>_<ffn>``."""
        return tuple(
            f"{KDA if i in self.kda_layers else MLA}_"
            f"{DENSE if i <= self.first_k_dense_replace else SPARSE}"
            for i in range(1, self.num_hidden_layers + 1))

    @property
    def n_kda(self) -> int:
        return len(self.kda_layers)

    @property
    def n_mla(self) -> int:
        return len(self.full_attn_layers)

    @property
    def n_sparse(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def conv_width(self) -> int:
        """Columns of ``W_qkv h``: the heads' q, then k, then v."""
        return 3 * self.kda_num_heads * self.kda_head_dim

    @property
    def row_width(self) -> int:
        """Width of a latent row: rank + rope, in whole lane tiles."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // _LANES) \
            * _LANES

    def runs(self) -> List[Tuple[str, int, int, int]]:
        """``(kind, lo, hi, first)`` of every run of one kind: layers
        ``[lo, hi)`` of the kind's stack, the first of them the
        ``first``-th layer of its attention (of the KDA layers, or of the
        MLA layers: its place in their pools)."""
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
    def tiny() -> "KimiLinearConfig":
        """A toy with the published pattern: a dense layer and four expert
        layers, KDA x 3, MLA, KDA; 16 experts of which 8 are held, top-4."""
        return KimiLinearConfig(
            vocab_size=128, hidden_size=64, num_hidden_layers=5,
            kda_layers=(1, 2, 3, 5), full_attn_layers=(4,),
            kda_num_heads=4, kda_head_dim=16, num_attention_heads=4,
            kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4,
            v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
            num_experts=8, published_num_experts=16, first_expert=8,
            num_experts_per_token=4, model_max_length=512, init_std=0.1)

    def paged_model(self) -> PagedModel:
        """The family on the serving path (``models/paged.py``)."""
        return PAGED


def layer_shapes(cfg: KimiLinearConfig, kind: str) -> Dict[str, Tuple]:
    """``{leaf path: shape}`` of one layer of ``kind``: matrices end in
    ``kernel``, norm scales in ``scale``; the others are named below."""
    D = cfg.hidden_size
    attention, ffn = kind.split("_")
    shapes: Dict[str, Tuple] = {"ln1/scale": (D,), "ln2/scale": (D,)}
    if attention == KDA:
        H, d, W = cfg.kda_num_heads, cfg.kda_head_dim, cfg.conv_width
        shapes.update({
            "kda_qkv/kernel": (D, W),
            "kda_conv/taps": (cfg.short_conv_kernel_size, W),
            "kda_fa/kernel": (D, d), "kda_fb/kernel": (d, H * d),
            "kda_decay/log_a": (H,), "kda_decay/dt_bias": (H * d,),
            "kda_b/kernel": (D, H),
            "kda_ga/kernel": (D, d), "kda_gb/kernel": (d, H * d),
            "kda_norm/scale": (d,), "attn_out/kernel": (H * d, D)})
    else:
        H = cfg.num_attention_heads
        nope, rope, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
            cfg.v_head_dim
        rank = cfg.kv_lora_rank
        shapes.update({
            "q_nope/kernel": (D, H * nope), "q_rope/kernel": (D, H * rope),
            "kv_a/kernel": (D, rank + rope), "kv_norm/scale": (rank,),
            "uk/kernel": (H, nope, rank), "uv/kernel": (H, rank, v),
            "attn_out/kernel": (H * v, D)})
    if ffn == DENSE:
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


def init(key: jax.Array, cfg: KimiLinearConfig, *, bias_std: float = 0.01,
         embedding_std: float = 1.0) -> Params:
    """Every matrix and the head normal(0, init_std), a layer at a time
    (the fp32 draw of a stack of expert layers is larger than the chip), in
    ``param_dtype`` but the router, fp32; the embedding normal(0,
    ``embedding_std``): it stands for a residual stream of the size the
    layers' outputs have; norm scales 1; the convolution's taps
    uniform(+-K ** -0.5) (a Conv1d's default), fp32; the router's selection
    bias normal(0, ``bias_std``), so that choosing and weighing differ.
    ``A_log = log(uniform(1, 16))`` a head and ``dt_bias`` the inverse
    softplus of ``exp(uniform(log 1e-3, log 1))`` a channel (the released
    module's form, its upper end raised from 0.1), so that a channel's
    decay ``exp(-A softplus(. + dt_bias))`` lies anywhere in (0, 1)."""
    std, f32 = cfg.init_std, jnp.float32
    keys = iter(jax.random.split(key, 64))
    K = cfg.short_conv_kernel_size

    def uniform(k, shape, lo, hi):
        return jax.random.uniform(k, shape, f32, lo, hi)

    def leaf(path, shape, n):
        name = path.rsplit("/", 1)[1]
        k = next(keys)
        if name == "scale":
            return jnp.ones((n, *shape), f32)
        if name == "taps":
            return uniform(k, (n, *shape), -K ** -0.5, K ** -0.5)
        if name == "log_a":
            return jnp.log(uniform(k, (n, *shape), 1.0, 16.0))
        if name == "dt_bias":
            dt = jnp.exp(uniform(k, (n, *shape), jnp.log(1e-3), 0.0))
            return dt + jnp.log(-jnp.expm1(-dt))      # softplus^-1(dt)
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
    params["embed"] = {"table": (embedding_std * jax.random.normal(
        next(keys), (V, D), f32)).astype(cfg.param_dtype)}
    params["final_norm"] = {"scale": jnp.ones((D,), f32)}
    params["lm_head"] = {"kernel": (std * jax.random.normal(
        next(keys), (D, V), f32)).astype(cfg.param_dtype)}
    return params


_MATRIX = re.compile(r"(^|/)(kernel|table)$")


def serving_params(params: Params, cfg: KimiLinearConfig) -> Params:
    """Every matrix and the embedding in ``compute_dtype``, which the
    products read them in; everything else and the router fp32."""
    return cast_leaves(
        params, lambda path: cfg.compute_dtype
        if _MATRIX.search(path) and "/router/" not in path else jnp.float32)


def init_pools(cfg: KimiLinearConfig, cache: Any, max_batch: int
               ) -> Tuple[jax.Array, ...]:
    """``(latent_pool, state_pool, tail_pool)``, zeroed (the module's
    doc-string has their shapes), with a slot per batch row."""
    H, d = cfg.kda_num_heads, cfg.kda_head_dim
    return (jnp.zeros((cfg.n_mla, cache.num_blocks, cache.block_size,
                       cfg.row_width), cfg.compute_dtype),
            jnp.zeros((cfg.n_kda, max_batch, H, d, d), jnp.float32),
            jnp.zeros((cfg.n_kda, max_batch, cfg.short_conv_kernel_size - 1,
                       cfg.conv_width), cfg.compute_dtype))


def _norm(cfg: KimiLinearConfig, p: Params, x: jax.Array,
          dtype: Any = None) -> jax.Array:
    return rmsnorm(p, x, cfg.rms_norm_eps, dtype=dtype or cfg.compute_dtype)


def _matmul(x: jax.Array, p: Params) -> jax.Array:
    """x @ kernel as the kernel lies, summed and returned in fp32."""
    return jnp.matmul(x, p["kernel"], preferred_element_type=jnp.float32)


def _low_rank(cfg: KimiLinearConfig, lp: Params, h: jax.Array,
              name: str) -> jax.Array:
    """``W_b W_a h`` through the head's width, fp32."""
    return _matmul(_matmul(h, lp[f"{name}a"]).astype(cfg.compute_dtype),
                   lp[f"{name}b"])


def _unit(x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _swiglu(cfg: KimiLinearConfig, lp: Params, h: jax.Array,
            name: str) -> jax.Array:
    act = jax.nn.silu(_matmul(h, lp[f"{name}_gate"])) \
        * _matmul(h, lp[f"{name}_up"])
    return _matmul(act.astype(cfg.compute_dtype), lp[f"{name}_down"])


def _mlp(cfg: KimiLinearConfig, kind: str, lp: Params, i: jax.Array,
         x: jax.Array, token_mask: jax.Array):
    """``(x + FFN(norm(x)), [expert_pairs, expert_hits], experts)`` of
    layer ``i`` of its kind's stack; ``experts`` [B, T, k] the experts a
    ``sparse`` layer chose for each token, None of a ``dense`` one."""
    B, T, D = x.shape
    with jax.named_scope("mlp"):
        h32 = _norm(cfg, lp["ln2"], x, jnp.float32)
        h = h32.astype(cfg.compute_dtype)
        if kind.endswith(DENSE):
            return (x + _swiglu(cfg, lp, h, "mlp"),
                    jnp.zeros((2,), jnp.int32), None)
        routed, counts, experts = routed_experts(
            lp, h32.reshape(B * T, D), first_expert=cfg.first_expert,
            n_held=cfg.num_experts, n_experts=cfg.published_num_experts,
            k=cfg.num_experts_per_token, scale=cfg.routed_scaling_factor,
            token_mask=token_mask.reshape(-1),
            first_row=i * cfg.num_experts, compute_dtype=cfg.compute_dtype)
        return (x + _swiglu(cfg, lp, h, "shared") + routed.reshape(B, T, D),
                counts, experts.reshape(B, T, -1))


def _layer(stack: Params, i: jax.Array) -> Params:
    """Layer ``i`` of a stack, read where it lies; the routed experts stay
    the kind's whole stack as rows ``[layers * held, ...]``
    (``models/glm_moe_dsa.py:_layer`` says why)."""
    return {name: jax.tree.map(
        (lambda w: w.reshape(-1, *w.shape[2:])) if name.startswith("experts_")
        else (lambda w: jax.lax.dynamic_index_in_dim(w, i, keepdims=False)),
        leaves) for name, leaves in stack.items()}


def _kda_attention(cfg: KimiLinearConfig, lp: Params, x: jax.Array,
                   token_mask: jax.Array, states: jax.Array,
                   tails: jax.Array, read_idx: jax.Array,
                   write_idx: jax.Array, fresh: jax.Array):
    """One KDA layer's attention: ``(x + Attn(norm(x)), states, tails)``.
    ``states`` [L_kda * slots, H, d, d] and ``tails`` [L_kda * slots, K -
    1, 3 H d] are the whole pools; ``read_idx`` / ``write_idx`` [B] this
    layer's entries of them (``write_idx`` past the pools for a row with no
    real token); ``fresh`` [B]: the row starts its sequence, both are 0."""
    B, T, _ = x.shape
    H, d, dt = cfg.kda_num_heads, cfg.kda_head_dim, cfg.compute_dtype
    with jax.named_scope("attn"):
        h = _norm(cfg, lp["ln1"], x)
        # the rows the convolution reads are the rows the tail keeps
        rows = _matmul(h, lp["kda_qkv"]).astype(dt)
        with jax.named_scope("kda_conv"):
            tail = jnp.where(fresh[:, None, None], 0, tails[read_idx])
            mixed, tail = short_conv(rows, lp["kda_conv"]["taps"], tail,
                                     jnp.sum(token_mask, axis=1))
            tails = tails.at[write_idx].set(tail, mode="drop")
            q, k, v = jnp.split(jax.nn.silu(mixed).reshape(B, T, 3 * H, d),
                                3, axis=2)
        g = -jnp.exp(lp["kda_decay"]["log_a"])[:, None] * jax.nn.softplus(
            (_low_rank(cfg, lp, h, "kda_f") + lp["kda_decay"]["dt_bias"]
             ).reshape(B, T, H, d))
        b = jax.nn.sigmoid(_matmul(h, lp["kda_b"]))
        with jax.named_scope("kda"):
            state = jnp.where(fresh[:, None, None, None], 0.0,
                              states[read_idx])
            o, state = kda(_unit(q) * d ** -0.5, _unit(k), v, g, b, state,
                           token_mask)
            states = states.at[write_idx].set(state, mode="drop")
        gate = jax.nn.sigmoid(_low_rank(cfg, lp, h, "kda_g"))
        o = _norm(cfg, lp["kda_norm"], o, jnp.float32).reshape(B, T, -1)
        x = x + _matmul((o * gate).astype(dt), lp["attn_out"])
    return x, states, tails


def _mla_attention(cfg: KimiLinearConfig, lp: Params, x: jax.Array,
                   positions: jax.Array, token_mask: jax.Array,
                   latent_rows: jax.Array, first: jax.Array,
                   tables: jax.Array, scatter: jax.Array, bs: int):
    """One MLA layer's attention: ``(x + Attn(norm(x)), latent_rows)``.
    ``latent_rows`` [L_mla * N * block, R] is the whole pool as rows,
    ``first`` this layer's first block in it, ``tables`` [B, W] the
    sequences' blocks within a layer, ``scatter`` where the call's rows go
    within a layer's share (``_write_indices``)."""
    B, T, _ = x.shape
    H, dt, R = cfg.num_attention_heads, cfg.compute_dtype, cfg.row_width
    nope, rope, rank = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.kv_lora_rank
    with jax.named_scope("attn"):
        h = _norm(cfg, lp["ln1"], x)
        kv = _matmul(h, lp["kv_a"])
        row = jnp.concatenate([
            _norm(cfg, lp["kv_norm"], kv[..., :rank], jnp.float32),
            kv[..., rank:],
            jnp.zeros((B, T, R - rank - rope), jnp.float32)], axis=-1)
        with jax.named_scope("kv_cache"):
            # a slice is whole blocks and is written a block a piece
            unit = bs if T > 1 else 1
            piece = (unit, R) if T > 1 else (R,)
            where = jnp.where(scatter >= 0, first * (bs // unit) + scatter,
                              latent_rows.shape[0])
            latent_rows = latent_rows.reshape(-1, *piece).at[where].set(
                row.astype(dt).reshape(-1, *piece), mode="drop"
            ).reshape(-1, R)
        with jax.named_scope("mla_attn"):
            q = mla.absorbed_query(
                _matmul(h, lp["q_nope"]).reshape(B, T, H, nope),
                _matmul(h, lp["q_rope"]).reshape(B, T, H, rope),
                lp["uk"]["kernel"], R, dt)
            scale = (nope + rope) ** -0.5
            blocks = latent_rows.reshape(-1, bs, R)
            if T == 1:
                lengths = jnp.where(token_mask[:, 0], positions[:, 0] + 1, 0)
                o = mla.mla_decode_dense(q, blocks, first + tables, lengths,
                                         scale=scale)
            else:
                o = mla.mla_slice(q, blocks, first + tables, None, positions,
                                  token_mask, scale=scale, rank=rank)
            o = mla.expand_values(o, lp["uv"]["kernel"])
        x = x + _matmul(o.reshape(B, T, -1).astype(dt), lp["attn_out"])
    return x, latent_rows


def _write_indices(positions: jax.Array, token_mask: jax.Array,
                   tables: jax.Array, block: int) -> jax.Array:
    """Where a call's latent rows go within one layer's share of the pool;
    -1 = nowhere. A slice's is [B * T / block] blocks (it starts on a block
    boundary with its real tokens first, so a block's first token says
    whether it holds any); a decode step's is [B] rows."""
    if positions.shape[1] == 1:
        row = jnp.take_along_axis(tables, positions // block, axis=1) \
            * block + positions % block
        return jnp.where(token_mask, row, -1).reshape(-1)
    blk = jnp.take_along_axis(tables, positions[:, ::block] // block, axis=1)
    return jnp.where(token_mask[:, ::block], blk, -1).reshape(-1)


def _paged_backbone(params: Params, cfg: KimiLinearConfig, tokens: jax.Array,
                    positions: jax.Array, token_mask: jax.Array,
                    latent_pool: jax.Array, state_pool: jax.Array,
                    tail_pool: jax.Array, block_tables: jax.Array):
    """Embed -> the runs of layers: ``(x [B, T, D] fp32, latent_pool,
    state_pool, tail_pool, counts [2], routing [B, T, L_sparse * k]
    int32)``; the pools ride the layer scans and are updated in place."""
    B, T = tokens.shape
    _, N, bs, R = latent_pool.shape
    n_slots = state_pool.shape[1]
    tables, slot = block_tables[:, :-1], block_tables[:, -1]
    scatter = _write_indices(positions, token_mask, tables, bs)
    real = jnp.any(token_mask, axis=1)
    fresh = real & (positions[:, 0] == 0)
    nowhere = state_pool.shape[0] * n_slots

    with jax.named_scope("embed"):
        x = jnp.take(params["embed"]["table"], tokens,
                     axis=0).astype(jnp.float32)

    carry = (x, latent_pool.reshape(-1, R),
             state_pool.reshape(-1, *state_pool.shape[2:]),
             tail_pool.reshape(-1, *tail_pool.shape[2:]),
             jnp.zeros((2,), jnp.int32))
    routing = []
    for kind, lo, hi, first in cfg.runs():
        def body(carry, i, kind=kind, lo=lo, first=first):
            x, latent_rows, states, tails, counts = carry
            lp = _layer(params[kind], i)
            at = first + i - lo                 # of this attention's layers
            if kind.startswith(KDA):
                here = at * n_slots + slot
                x, states, tails = _kda_attention(
                    cfg, lp, x, token_mask, states, tails, here,
                    jnp.where(real, here, nowhere), fresh)
            else:
                x, latent_rows = _mla_attention(
                    cfg, lp, x, positions, token_mask, latent_rows, at * N,
                    tables, scatter, bs)
            x, hit, experts = _mlp(cfg, kind, lp, i, x, token_mask)
            return (x, latent_rows, states, tails, counts + hit), experts

        carry, experts = jax.lax.scan(
            body, carry, jnp.arange(lo, hi, dtype=jnp.int32))
        if experts is not None:                       # [layers, B, T, k]
            routing.extend(experts[j] for j in range(hi - lo))
    x, latent_rows, states, tails, counts = carry
    return (x, latent_rows.reshape(latent_pool.shape),
            states.reshape(state_pool.shape), tails.reshape(tail_pool.shape),
            counts, jnp.concatenate(routing, axis=-1) if routing
            else jnp.zeros((B, T, 0), jnp.int32))


def _paged_logits(params: Params, cfg: KimiLinearConfig, tokens: jax.Array,
                  positions: jax.Array, token_mask: jax.Array,
                  last_index: Any, pools: Tuple[jax.Array, ...],
                  block_tables: jax.Array):
    """Logits at ``last_index`` [B] of each row ([B, V]) or, with None, at
    every position ([B, T, V]); the batch in one pass or, over
    ``PREFILL_TOKENS_PER_PASS`` tokens, a row at a time. A slice is padded
    to whole cache blocks. Returns ``(logits, latent_pool, state_pool,
    tail_pool, counts, routing [B, T, L_sparse * k])``."""
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


def forward_paged(params: Params, cfg: KimiLinearConfig, tokens: jax.Array,
                  positions: jax.Array, token_mask: jax.Array,
                  last_index: jax.Array, latent_pool: jax.Array,
                  state_pool: jax.Array, tail_pool: jax.Array,
                  block_tables: jax.Array):
    """A prefill slice or a decode step over this family's cache; the
    argument contract of ``models/gpt.py:forward_paged`` with these
    differences. The pools are three (``init_pools``). ``block_tables`` [B,
    W + 1] is a sequence's blocks in order, then its slot, as
    ``StateSlotLayout.lay_table`` writes a row. A row's real tokens are
    consecutive positions and come first; a slice of more than one token
    starts on a block boundary (the engine's do:
    ``StateSlotLayout.check_prefill``), and the call that holds position 0
    starts the sequence's states and tails from zero.

    Returns ``(logits [B, V] fp32 at each row's last real token,
    latent_pool, state_pool, tail_pool, counts [2] int32, routing [B, T,
    L_sparse * k] int32)``; ``counts`` is the call's
    ``PAGED.step_counters``, ``routing`` its ``PAGED.token_records``.
    """
    return _paged_logits(params, cfg, tokens, positions, token_mask,
                         last_index, (latent_pool, state_pool, tail_pool),
                         block_tables)


def forward_paged_logits(params: Params, cfg: KimiLinearConfig,
                         tokens: jax.Array, positions: jax.Array,
                         token_mask: jax.Array, latent_pool: jax.Array,
                         state_pool: jax.Array, tail_pool: jax.Array,
                         block_tables: jax.Array):
    """``forward_paged`` returning the logits at every position:
    ``(logits [B, T, V] fp32, the three pools, counts, routing)``. The
    tests compare it with the reference; the engine's speculative verify
    step is refused for this family (a rejected draft cannot be taken out
    of a state)."""
    return _paged_logits(params, cfg, tokens, positions, token_mask, None,
                         (latent_pool, state_pool, tail_pool), block_tables)


def _cache_layout(cfg: KimiLinearConfig, cache: Any) -> Any:
    # imported here: serving/ imports the models at import time
    from determined_clone_tpu.serving.kv_cache import StateSlotLayout

    return StateSlotLayout(cache, cfg.max_seq_len)


def _prefill_counts(cfg: KimiLinearConfig, layout: Any,
                    starts: Sequence[int], counts: Sequence[int],
                    length: int) -> Dict[str, int]:
    """The key tiles a call's slices multiply in the MLA layers' latent
    attention, beside those whole tables would cost (the table's last
    entry is the slot)."""
    return mla.key_tiles(starts, counts, length, cfg.num_attention_heads,
                         layout.table_width - 1, layout.cache.block_size,
                         layers=len(cfg.full_attn_layers))


PAGED = PagedModel(
    family="kimi_linear", forward_paged=forward_paged,
    forward_paged_logits=forward_paged_logits, init=init,
    cache_layout=_cache_layout, serving_params=serving_params,
    init_pools=init_pools,
    pool_names=("latent_pool", "state_pool", "tail_pool"),
    # sharing a prefix would need the state and the tail at the shared
    # length, which no later sequence left behind; the tiers address K and
    # V pools by name; a draft's rejected tokens cannot be taken out of a
    # state (ROADMAP B-M)
    unsupported=("prefix_cache", "kv_store", "speculative"),
    row_counters=("serving_latent_rows_cached_total",
                  "serving_latent_rows_read_total",
                  "serving_state_slots_total"),
    step_counters=("expert_pairs", "expert_hits"),
    token_records=True, prefill_counts=_prefill_counts)
