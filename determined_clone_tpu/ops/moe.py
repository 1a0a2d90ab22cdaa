"""Mixture-of-Experts FFN with expert parallelism over the mesh's ``ep`` axis.

Absent in the reference (SURVEY.md §2.7: "Expert parallel (EP / MoE) — ❌
absent"); first-class here. The design is the TPU-idiomatic einsum-dispatch
form (Switch-Transformer style): routing is expressed as dense one-hot
dispatch/combine tensors so every op is a static-shaped einsum the MXU can
tile — no gather/scatter, no dynamic shapes. When expert weights carry an
``ep`` PartitionSpec, XLA lowers the dispatch einsum to an all-to-all over the
ep axis automatically.

Capacity semantics: each expert processes at most C = ceil(tokens/E ·
capacity_factor) tokens; overflow tokens fall through the residual connection
(standard drop-token behavior). The router adds the load-balancing auxiliary
loss E · Σ_e f_e·P_e from the Switch paper.

Beside it, :func:`routed_experts`: a dropless expert layer that is told
which experts it holds — one member of an expert-parallel group, without
the exchange (the serving path of ``models/glm_moe_dsa.py``).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from determined_clone_tpu.ops.layers import trunc_normal

Params = Dict[str, Any]


def moe_init(key: jax.Array, n_experts: int, d_model: int, d_ff: int,
             dtype=jnp.float32, out_stddev: float = 0.02) -> Params:
    """Expert-stacked FFN params: leading [E] expert dim (sharded over ep)."""
    k_r, k_up, k_dn = jax.random.split(key, 3)
    return {
        "router": {"kernel": trunc_normal(k_r, (d_model, n_experts),
                                          stddev=0.02, dtype=dtype)},
        "up": {"kernel": trunc_normal(k_up, (n_experts, d_model, d_ff),
                                      stddev=0.02, dtype=dtype),
               "bias": jnp.zeros((n_experts, d_ff), dtype)},
        "down": {"kernel": trunc_normal(k_dn, (n_experts, d_ff, d_model),
                                        stddev=out_stddev, dtype=dtype),
                 "bias": jnp.zeros((n_experts, d_model), dtype)},
    }


def expert_capacity(n_tokens: int, n_experts: int,
                    capacity_factor: float) -> int:
    return max(1, math.ceil(n_tokens / n_experts * capacity_factor))


def moe_ffn(
    params: Params,
    x: jax.Array,
    *,
    k: int = 2,
    capacity_factor: float = 1.25,
    compute_dtype=jnp.bfloat16,
) -> Tuple[jax.Array, jax.Array]:
    """Top-k routed expert FFN. x: [B, T, D] → ([B, T, D], aux_loss scalar).

    All shapes static: dispatch/combine are [N, E, C] one-hot tensors, expert
    compute is batched einsum over the [E] dim (ep-shardable).
    """
    B, T, D = x.shape
    N = B * T
    E = params["router"]["kernel"].shape[-1]
    C = expert_capacity(N, E, capacity_factor)
    k = min(k, E)

    tokens = x.reshape(N, D)
    # Router in fp32 for a stable softmax.
    logits = tokens.astype(jnp.float32) @ params["router"]["kernel"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                       # [N, E]

    # Top-k choices, processed in priority order so earlier choices claim
    # capacity first (running per-expert token counts carry between choices).
    top_probs, top_idx = jax.lax.top_k(probs, k)                  # [N, k]
    # Renormalize the chosen gates so combine weights sum to 1 per token.
    top_probs = top_probs / jnp.maximum(
        jnp.sum(top_probs, axis=-1, keepdims=True), 1e-9)

    dispatch = jnp.zeros((N, E, C), jnp.bool_)
    combine = jnp.zeros((N, E, C), jnp.float32)
    counts = jnp.zeros((E,), jnp.int32)                           # claimed slots
    for i in range(k):
        mask_i = jax.nn.one_hot(top_idx[:, i], E, dtype=jnp.int32)   # [N, E]
        pos_i = jnp.cumsum(mask_i, axis=0) - mask_i + counts[None, :]
        pos = jnp.sum(pos_i * mask_i, axis=-1)                    # [N] slot per token
        keep = pos < C
        counts = counts + jnp.sum(mask_i, axis=0)
        onehot_pos = jax.nn.one_hot(pos, C, dtype=jnp.float32)    # [N, C]
        d_i = (mask_i.astype(jnp.float32)[:, :, None] * onehot_pos[:, None, :]
               * keep.astype(jnp.float32)[:, None, None])
        dispatch = dispatch | (d_i > 0)
        combine = combine + d_i * top_probs[:, i][:, None, None]

    # Dispatch → expert compute → combine. XLA turns the E-dim contractions
    # into an all-to-all when up/down kernels are sharded over ep.
    xe = jnp.einsum("nec,nd->ecd", dispatch.astype(compute_dtype),
                    tokens.astype(compute_dtype))                 # [E, C, D]
    h = jnp.einsum("ecd,edf->ecf", xe,
                   params["up"]["kernel"].astype(compute_dtype))
    h = h + params["up"]["bias"].astype(compute_dtype)[:, None, :]
    h = jax.nn.gelu(h, approximate=True)
    ye = jnp.einsum("ecf,efd->ecd", h,
                    params["down"]["kernel"].astype(compute_dtype))
    ye = ye + params["down"]["bias"].astype(compute_dtype)[:, None, :]
    y = jnp.einsum("nec,ecd->nd", combine.astype(compute_dtype), ye)

    # Switch load-balancing loss: E · Σ_e (dispatch fraction · router prob).
    # First-choice assignment fractions, as in the paper.
    first = jax.nn.one_hot(top_idx[:, 0], E, dtype=jnp.float32)
    f = jnp.mean(first, axis=0)
    p = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f * p)

    return y.reshape(B, T, D).astype(x.dtype), aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# A dropless expert layer that holds some of the experts
# ---------------------------------------------------------------------------

PAIR_TILE = 128  # token-expert pairs multiplied through one expert at a time


def route(router: Params, h: jax.Array, *, k: int, scale: float
          ) -> Tuple[jax.Array, jax.Array]:
    """Sigmoid routing with a selection bias, for h [N, D] fp32: ``s =
    sigmoid(h W)`` over all the router's experts (fp32, full precision);
    the ``k`` experts of largest ``s + b`` are chosen (``b`` =
    ``router["bias"]`` chooses and does not weigh); ``g = scale * s / sum
    of the chosen s``. Returns ``(experts [N, k] int32, gates [N, k]
    fp32)``."""
    scores = jax.nn.sigmoid(jnp.matmul(
        h.astype(jnp.float32), router["kernel"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, experts = jax.lax.top_k(scores + router["bias"].astype(jnp.float32),
                               k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    return experts.astype(jnp.int32), scale * chosen / jnp.sum(
        chosen, axis=-1, keepdims=True)


def routed_experts(params: Params, h: jax.Array, *, first_expert: int,
                   n_held: int, n_experts: int, k: int, scale: float,
                   token_mask: Optional[jax.Array] = None,
                   first_row: Any = 0, compute_dtype=jnp.bfloat16
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The routed part of an expert layer, as the member of an
    expert-parallel group that holds experts ``[first_expert, first_expert +
    n_held)`` of ``n_experts`` computes it: h [N, D] fp32 -> ``(y [N, D]
    fp32, counts [2] int32, experts [N, k] int32)``.

    Every token is routed over all ``n_experts`` (:func:`route`:
    ``params["router"]`` = kernel [D, n_experts] and bias [n_experts]); the
    token-expert pairs whose expert is held are sorted by expert and
    multiplied, ``PAIR_TILE`` pairs of one expert at a time, through that
    expert's SwiGLU (``params["experts_gate" | "experts_up"]`` kernels
    [n_held, D, F], ``params["experts_down"]`` [n_held, F, D], read as they
    lie; in stacks of several layers' experts, this layer's start at row
    ``first_row``); ``y[n] = sum over n's chosen and held experts of g *
    Expert(h[n])``.
    Pairs of absent experts contribute nothing (their gates still took part
    in the normaliser); nothing stands in for the exchange. The shared
    expert is the caller's.

    **No pair is dropped, whatever the routing.** The tiles are a loop
    whose length is the number of tiles the step's pairs fill (an expert's
    pairs start on a tile boundary; at most ``ceil(N k / PAIR_TILE) + n_held``
    tiles, all pairs to held experts), so the work is in proportion to the
    pairs that fell here, and an expert with no pair is not read.

    ``token_mask`` [N]: tokens that are padding route nowhere. ``counts`` =
    (pairs that fell to held experts, held experts that got at least one);
    ``experts`` = every token's ``k`` chosen experts of all ``n_experts``,
    held or not, in the order of their biased scores.
    """
    N, D = h.shape
    tile = PAIR_TILE
    if params["router"]["kernel"].shape[-1] != n_experts \
            or not 0 <= first_expert <= first_expert + n_held <= n_experts:
        raise ValueError(
            f"experts [{first_expert}, {first_expert + n_held}) are not "
            f"among the router's {params['router']['kernel'].shape[-1]} "
            f"(n_experts {n_experts})")
    with jax.named_scope("moe_route"):
        experts, gates = route(params["router"], h, k=k, scale=scale)
        local = experts - first_expert
        held = (local >= 0) & (local < n_held)
        if token_mask is not None:
            held &= token_mask[:, None]
        group = jnp.where(held, local, n_held).reshape(-1)        # [N k]
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        sizes = jnp.sum(group[:, None] == jnp.arange(n_held)[None, :],
                        axis=0, dtype=jnp.int32)                  # [n_held]
        tiles = -(-sizes // tile)
        tile_end = jnp.cumsum(tiles)
        pair_start = jnp.cumsum(sizes) - sizes
        counts = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0)]
                           ).astype(jnp.int32)
        tokens = order // k
        weights = gates.reshape(-1)[order]

    x = h.astype(compute_dtype)
    gate_w, up_w, down_w = (params[n]["kernel"] for n in (
        "experts_gate", "experts_up", "experts_down"))

    def one_tile(t, y):
        e = jnp.minimum(jnp.searchsorted(tile_end, t, side="right"),
                        n_held - 1)
        rank = (t - (tile_end[e] - tiles[e])) * tile + jnp.arange(tile)
        real = rank < sizes[e]
        at = jnp.where(real, pair_start[e] + rank, 0)
        rows = x[tokens[at]]                                      # [tile, D]

        def w(stack):
            return jax.lax.dynamic_index_in_dim(stack, first_row + e,
                                                keepdims=False)

        act = jax.nn.silu(jnp.matmul(
            rows, w(gate_w), preferred_element_type=jnp.float32)) \
            * jnp.matmul(rows, w(up_w), preferred_element_type=jnp.float32)
        out = jnp.matmul(act.astype(compute_dtype), w(down_w),
                         preferred_element_type=jnp.float32)
        out = out * jnp.where(real, weights[at], 0.0)[:, None]
        return y.at[jnp.where(real, tokens[at], N)].add(out, mode="drop")

    with jax.named_scope("moe_experts"):
        y = jax.lax.fori_loop(0, tile_end[-1], one_tile,
                              jnp.zeros((N, D), jnp.float32))
    return y, counts, experts
