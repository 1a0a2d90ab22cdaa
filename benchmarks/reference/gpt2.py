"""Plain reference for the repo's GPT family: forward pass, loss, gradients
and the AdamW step, in straightforward ``jax.numpy``.

Written from the published description of GPT-2 (Radford et al. 2019:
pre-LayerNorm decoder blocks, fused QKV projection, GELU in its tanh form
(``gelu_new``), output head tied to the token embedding) with the two
departures this repository's family makes from it, both noted because the
reference has to compute what the system claims to compute:

- positions enter through rotary embeddings on q and k (half-split layout,
  base 10000) instead of GPT-2's learned position table ``wpe``;
- LayerNorm epsilon is 1e-5.

No kernel, no KV cache, no buckets, no sharding rule. It imports nothing of
``determined_clone_tpu`` and receives its weights from the benchmark's own
seeded generator (``benchmarks/adapters``), as a tree with the leaves

    embed/table [V, D]; final_norm/{scale,bias} [D];
    blocks/{ln1,ln2}/{scale,bias} [L, D];
    blocks/attn_qkv/{kernel [L, D, 3D], bias [L, 3D]};
    blocks/attn_out/{kernel [L, D, D], bias [L, D]};
    blocks/mlp_up/{kernel [L, D, F], bias [L, F]};
    blocks/mlp_down/{kernel [L, F, D], bias [L, D]}.

``precision`` selects how matrix products are computed:

- ``"f32"``: float32 operands at ``jax.lax.Precision.HIGHEST`` — the
  reference proper;
- ``"bf16"``: operands rounded to bfloat16, float32 accumulation — what the
  configurations state for the system (bf16 compute over fp32 parameters);
- ``"fp8"``: operands rounded to float8 e4m3 with one scale per tensor
  (amax / 448), float32 accumulation, and in the backward pass every
  product's incoming gradient rounded to float8 e5m2 (amax / 57344), as
  fp8 training recipes do; gradients pass straight through the roundings.
  The nearest precision below the one the configurations state, used only
  by the control (``benchmarks/tools/readings.py``, ``benchmarks/tests``).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]

PRECISIONS = ("f32", "bf16", "fp8")
LN_EPS = 1e-5
ROPE_BASE = 10000.0
E4M3_MAX = 448.0
E5M2_MAX = 57344.0


def _round_fp8(x: jax.Array) -> jax.Array:
    """x rounded to e4m3 under one per-tensor scale; straight-through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(rounded - x)


@jax.custom_vjp
def _round_gradient_fp8(x: jax.Array) -> jax.Array:
    """Identity whose incoming gradient is rounded to e5m2 under one
    per-tensor scale."""
    return x


def _round_gradient_fwd(x):
    return x, None


def _round_gradient_bwd(_, g):
    scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-30) / E5M2_MAX
    return ((g / scale).astype(jnp.float8_e5m2).astype(g.dtype) * scale,)


_round_gradient_fp8.defvjp(_round_gradient_fwd, _round_gradient_bwd)


def matmul(a: jax.Array, b: jax.Array, precision: str) -> jax.Array:
    """``a @ b`` (batched like ``jnp.matmul``) at the chosen precision,
    float32 result."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if precision == "f32":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "fp8":
        a, b = _round_fp8(a), _round_fp8(b)
    elif precision != "bf16":
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    out = jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return _round_gradient_fp8(out) if precision == "fp8" else out


def layernorm(p: Params, x: jax.Array) -> jax.Array:
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def rotary(x: jax.Array, positions: jax.Array) -> jax.Array:
    """x: [B, T, H, hd]; positions: [T]. Half-split rotary embedding."""
    half = x.shape[-1] // 2
    freqs = ROPE_BASE ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def gelu_new(x: jax.Array) -> jax.Array:
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def block(lp: Params, x: jax.Array, n_heads: int, precision: str
          ) -> jax.Array:
    """One decoder block on x [B, T, D] (float32), full causal attention."""
    B, T, D = x.shape
    hd = D // n_heads
    h = layernorm(lp["ln1"], x)
    qkv = matmul(h, lp["attn_qkv"]["kernel"], precision) \
        + lp["attn_qkv"]["bias"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    pos = jnp.arange(T)
    q = rotary(q.reshape(B, T, n_heads, hd), pos).transpose(0, 2, 1, 3)
    k = rotary(k.reshape(B, T, n_heads, hd), pos).transpose(0, 2, 1, 3)
    v = v.reshape(B, T, n_heads, hd).transpose(0, 2, 1, 3)
    scores = matmul(q, k.transpose(0, 1, 3, 2), precision) / jnp.sqrt(
        jnp.float32(hd))                                    # [B, H, T, T]
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = matmul(probs, v, precision)                      # [B, H, T, hd]
    attn = attn.transpose(0, 2, 1, 3).reshape(B, T, D)
    x = x + matmul(attn, lp["attn_out"]["kernel"], precision) \
        + lp["attn_out"]["bias"]
    h = layernorm(lp["ln2"], x)
    h = gelu_new(matmul(h, lp["mlp_up"]["kernel"], precision)
                 + lp["mlp_up"]["bias"])
    return x + matmul(h, lp["mlp_down"]["kernel"], precision) \
        + lp["mlp_down"]["bias"]


def forward(params: Params, tokens: jax.Array, *, n_heads: int,
            precision: str = "f32", remat: bool = False) -> jax.Array:
    """tokens int32 [B, T] -> logits float32 [B, T, V]. ``remat`` recomputes
    each block in the backward pass (memory only; same numbers)."""
    x = params["embed"]["table"][tokens].astype(jnp.float32)

    def body(x, lp):
        return block(lp, x, n_heads, precision), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = layernorm(params["final_norm"], x)
    return matmul(x, params["embed"]["table"].T, precision)


def summed_loss(params: Params, batch: jax.Array, *, n_heads: int,
                precision: str = "f32") -> jax.Array:
    """Sum over every position of the next-token cross-entropy.
    batch: int32 [B, T+1]; inputs are batch[:, :-1], targets batch[:, 1:]."""
    logits = forward(params, batch[:, :-1], n_heads=n_heads,
                     precision=precision, remat=True)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, batch[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(logz - picked)


def loss_and_grads(params: Params, batch: jax.Array, *, n_heads: int,
                   precision: str = "f32", rows_per_block: int = 2
                   ) -> Tuple[jax.Array, Params]:
    """Mean cross-entropy over the whole batch and its gradient, computed
    ``rows_per_block`` sequences at a time so that the reference fits
    beside nothing but itself."""
    n_rows, width = batch.shape
    if n_rows % rows_per_block:
        raise ValueError(f"{n_rows} rows not divisible by {rows_per_block}")
    blocks = batch.reshape(n_rows // rows_per_block, rows_per_block, width)
    grad_fn = jax.value_and_grad(
        lambda p, b: summed_loss(p, b, n_heads=n_heads, precision=precision))

    def body(carry, rows):
        total, acc = carry
        value, g = grad_fn(params, rows)
        return (total + value, jax.tree.map(jnp.add, acc, g)), None

    zero = jax.tree.map(jnp.zeros_like, params)
    (total, grads), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), zero), blocks)
    n = n_rows * (width - 1)
    return total / n, jax.tree.map(lambda g: g / n, grads)


def clip_by_global_norm(grads: Params, max_norm: float) -> Params:
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(grads)))
    scale = jnp.where(norm < max_norm, 1.0, max_norm / norm)
    return jax.tree.map(lambda g: g * scale, grads)


def adamw_step(params: Params, grads: Params, mu: Params, nu: Params,
               count: int, *, lr: float, b1: float, b2: float, eps: float,
               weight_decay: float) -> Tuple[Params, Params, Params]:
    """AdamW (Loshchilov & Hutter 2019): decoupled decay on every leaf,
    bias-corrected moments. ``count`` is the step being taken, from 1."""
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count

    def new(p, m, v):
        return p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                         + weight_decay * p)

    return jax.tree.map(new, params, mu, nu), mu, nu


def leaf_norms(tree: Params) -> jax.Array:
    """L2 norm of every leaf, in ``jax.tree.leaves`` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def train_three_steps(params: Params, batches: Sequence[jax.Array], *,
                      n_heads: int, optimizer: Dict[str, float],
                      precision: str = "f32", rows_per_block: int = 2,
                      probe: Any = None, probe_arg: Any = None
                      ) -> Dict[str, Any]:
    """Follow the first steps of training on ``batches`` (one per step).

    Returns what the benchmark compares: each step's loss, the per-leaf norm
    of the first gradient as the optimizer receives it (after the clip),
    and the per-leaf norm of the parameters' change over all the steps.
    ``probe`` ((gradient tree, probe_arg) -> array), where given, is
    evaluated on that first gradient and returned as ``first_grad_probe``;
    ``probe_arg`` travels as an argument of the compiled step, so that a
    value that changes with the seed does not make a new program."""
    opt = dict(optimizer)
    clip = opt.pop("clip_global_norm")

    def step(p, mu, nu, batch, count, arg, probing):
        loss, g = loss_and_grads(p, batch, n_heads=n_heads,
                                 precision=precision,
                                 rows_per_block=rows_per_block)
        g = clip_by_global_norm(g, clip)
        probed = probe(g, arg) if probing else jnp.zeros(())
        p, mu, nu = adamw_step(p, g, mu, nu, count, **opt)
        return p, mu, nu, loss, leaf_norms(g), probed

    # the first step keeps the initial parameters (compared at the end);
    # later steps reuse their inputs' memory
    first_step = jax.jit(
        functools.partial(step, probing=probe is not None),
        donate_argnums=(1, 2))
    later_step = jax.jit(functools.partial(step, probing=False),
                         donate_argnums=(0, 1, 2))

    @jax.jit
    def change(p_new, p_old):
        return leaf_norms(jax.tree.map(jnp.subtract, p_new, p_old))

    p = params
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses: List[float] = []
    first_grad = first_probe = None
    for i, batch in enumerate(batches, start=1):
        p, mu, nu, loss, gnorms, probed = (
            first_step if i == 1 else later_step)(
            p, mu, nu, jnp.asarray(batch), jnp.float32(i), probe_arg)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = jax.device_get(gnorms)
            first_probe = jax.device_get(probed)
    return {"losses": losses, "first_grad_leaf_norms": first_grad,
            "first_grad_probe": first_probe,
            "param_change_leaf_norms": jax.device_get(change(p, params))}


def teacher_forced_logits(params: Params, tokens: Sequence[int], *,
                          n_heads: int, precision: str = "f32",
                          pad_to: int) -> jax.Array:
    """Logits [len(tokens), V] of one sequence. Padding on the right cannot
    reach a causal position on its left."""
    padded = list(tokens) + [0] * (pad_to - len(tokens))
    logits = _sequence_logits(params, jnp.asarray([padded], jnp.int32),
                              n_heads, precision)
    return logits[0, :len(tokens)]


@functools.partial(jax.jit, static_argnums=(2, 3))
def _sequence_logits(params, tokens, n_heads, precision):
    return forward(params, tokens, n_heads=n_heads, precision=precision)
