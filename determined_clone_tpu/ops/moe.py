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
the exchange (the serving path of ``models/glm_moe_dsa.py``) — and
:func:`routed_experts_trained`, the same layer with a reverse mode (the
training path of ``models/glm_moe_lite.py``).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

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


class _PairPlan(NamedTuple):
    """The token-expert pairs that fell to held experts, sorted by expert,
    an expert's pairs starting on a tile boundary."""
    order: jax.Array       # [N k] pairs by held expert, the absent last
    sizes: jax.Array       # [n_held] pairs of each held expert
    tiles: jax.Array       # [n_held] tiles they fill
    tile_end: jax.Array    # [n_held] cumulative
    pair_start: jax.Array  # [n_held] an expert's first pair in ``order``
    counts: jax.Array      # [2] (pairs held, held experts that got any)
    tokens: jax.Array      # [N k] the token of each sorted pair
    weights: jax.Array     # [N k] its gate
    tile_rows: int

    def tile(self, t: Any) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Tile ``t``: ``(its expert, which of its rows hold a pair, each
        row's place in the sorted pairs; 0 where it holds none)``."""
        e = jnp.minimum(jnp.searchsorted(self.tile_end, t, side="right"),
                        self.sizes.shape[0] - 1)
        rank = (t - (self.tile_end[e] - self.tiles[e])) * self.tile_rows \
            + jnp.arange(self.tile_rows)
        real = rank < self.sizes[e]
        return e, real, jnp.where(real, self.pair_start[e] + rank, 0)


def _pair_plan(experts: jax.Array, gates: jax.Array, *, first_expert: int,
               n_held: int, tile: int,
               token_mask: Optional[jax.Array] = None) -> _PairPlan:
    k = experts.shape[-1]
    local = experts - first_expert
    held = (local >= 0) & (local < n_held)
    if token_mask is not None:
        held &= token_mask[:, None]
    group = jnp.where(held, local, n_held).reshape(-1)            # [N k]
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    sizes = jnp.sum(group[:, None] == jnp.arange(n_held)[None, :],
                    axis=0, dtype=jnp.int32)                      # [n_held]
    tiles = -(-sizes // tile)
    tile_end = jnp.cumsum(tiles)
    pair_start = jnp.cumsum(sizes) - sizes
    counts = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0)]
                       ).astype(jnp.int32)
    tokens = order // k
    weights = gates.reshape(-1)[order]
    return _PairPlan(order, sizes, tiles, tile_end, pair_start, counts,
                     tokens, weights, tile)


def _experts_forward(plan: _PairPlan, x: jax.Array, stacks: Tuple[jax.Array,
                     jax.Array, jax.Array], first_row: Any = 0) -> jax.Array:
    """The tile loop: ``y[n] = sum over n's chosen and held experts of g *
    Expert(x[n])`` [N, D] fp32, ``plan.tile_rows`` pairs of one expert at a
    time, as many tiles as the pairs fill; x in the products' dtype, the
    stacks ``(gate, up, down)`` read as they lie, a layer's experts from
    row ``first_row`` on."""
    N, D = x.shape
    tokens, weights = plan.tokens, plan.weights
    gate_w, up_w, down_w = stacks

    def one_tile(t, y):
        e, real, at = plan.tile(t)
        rows = x[tokens[at]]                                      # [tile, D]

        def w(stack):
            return jax.lax.dynamic_index_in_dim(stack, first_row + e,
                                                keepdims=False)

        act = jax.nn.silu(jnp.matmul(
            rows, w(gate_w), preferred_element_type=jnp.float32)) \
            * jnp.matmul(rows, w(up_w), preferred_element_type=jnp.float32)
        out = jnp.matmul(act.astype(x.dtype), w(down_w),
                         preferred_element_type=jnp.float32)
        out = out * jnp.where(real, weights[at], 0.0)[:, None]
        return y.at[jnp.where(real, tokens[at], N)].add(out, mode="drop")

    return jax.lax.fori_loop(0, plan.tile_end[-1], one_tile,
                             jnp.zeros((N, D), jnp.float32))


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
        plan = _pair_plan(experts, gates, first_expert=first_expert,
                          n_held=n_held, tile=tile, token_mask=token_mask)
    with jax.named_scope("moe_experts"):
        y = _experts_forward(
            plan, h.astype(compute_dtype),
            tuple(params[n]["kernel"] for n in (
                "experts_gate", "experts_up", "experts_down")), first_row)
    return y, plan.counts, experts


# ---------------------------------------------------------------------------
# The same layer with a reverse mode
# ---------------------------------------------------------------------------

# Sorted pairs a loop turn, and the most (rows, contraction, columns) a grid
# step of the grouped products takes, for the rows' products and for the
# weights' gradients: a v5e's sweep of one layer at 8192 tokens and 8638 pairs
# held (tools/moe_train_sweep.py; PERF.md, PR 43), forward / backward ms:
# 2048 pairs a turn with 256 rows a step 3.42 / 8.13, with 512 3.62 / 8.39;
# 1024 a turn 3.45 / 8.07 and 3.66 / 8.32; 4096 a turn 3.81 / 8.75 and 3.99 /
# 8.94 (what is not a product works on a turn's whole buffer, so the last
# turn's empty rows cost); the weights' gradients take 0.79-0.82 ms a product
# over 8192 rows whatever their tiles. A turn of 256-pair tiles of one expert,
# the form before: 4.12 / 11.28.
TRAIN_PAIR_ROWS = 2048
TRAIN_TILES = (256, 2048, 1536)
TRAIN_OUTER_TILES = (512, 2048, 768)


def _in_tiles(a: jax.Array) -> jax.Array:
    """[n, D] -> [n, D / 128, 128]: a row in tiles of its own, so that a
    copy can address it (``grouped_matmul.add_rows``)."""
    n, d = a.shape
    return a.reshape((n, d // 128, 128) if d % 128 == 0 else (n, 1, d))


def _chunk(plan: _PairPlan, c: Any, rows: int):
    """Turn ``c``'s ``rows`` of the sorted held pairs: ``(which of them hold
    a pair, each one's place in the sorted pairs; 0 where it holds none, the
    rows of each held expert among them)``."""
    first = c * rows
    at = first + jnp.arange(rows)
    real = at < plan.counts[0]
    end = plan.pair_start + plan.sizes
    sizes = jnp.clip(end - first, 0, rows) \
        - jnp.clip(plan.pair_start - first, 0, rows)
    return real, jnp.where(real, at, 0), sizes.astype(jnp.int32)


def _chunks(plan: _PairPlan, rows: int) -> jax.Array:
    return -(-plan.counts[0] // rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _held_experts(h, gates, experts, gate_w, up_w, down_w, first_expert,
                  rows, compute_dtype):
    return _held_experts_fwd(h, gates, experts, gate_w, up_w, down_w,
                             first_expert, rows, compute_dtype)[0]


def _held_experts_fwd(h, gates, experts, gate_w, up_w, down_w, first_expert,
                      rows, compute_dtype):
    """``rows`` of the sorted held pairs a turn, as many turns as the pairs
    fill: the tokens' rows gathered, the three products grouped by expert
    (``ops/grouped_matmul.py``; a row that holds no pair is left unwritten
    by them and masked here), the gated outputs added to their tokens'
    rows."""
    # imported where a trained layer is traced: the served cells import this
    # module too, and importing Pallas costs a process a second
    from determined_clone_tpu.ops.grouped_matmul import (
        add_rows,
        grouped_matmul,
    )

    N, D = h.shape
    plan = _pair_plan(experts, gates, first_expert=first_expert,
                      n_held=gate_w.shape[0], tile=rows)
    x = h.astype(compute_dtype)
    wg, wu, wd = (w.astype(compute_dtype) for w in (gate_w, up_w, down_w))

    def one_chunk(c, y):
        real, at, sizes = _chunk(plan, c, rows)
        keep = real[:, None]
        token = plan.tokens[at]
        xs = x[token]
        g = jnp.where(keep, grouped_matmul(xs, wg, sizes, tiles=TRAIN_TILES),
                      0.0)
        u = jnp.where(keep, grouped_matmul(xs, wu, sizes, tiles=TRAIN_TILES),
                      0.0)
        out = grouped_matmul((jax.nn.silu(g) * u).astype(compute_dtype), wd,
                             sizes, tiles=TRAIN_TILES)
        out = jnp.where(keep, out * plan.weights[at][:, None], 0.0)
        return add_rows(y, token, _in_tiles(out), sizes)

    y = jax.lax.fori_loop(0, _chunks(plan, rows), one_chunk,
                          _in_tiles(jnp.zeros((N, D), jnp.float32)))
    return y.reshape(N, D), (h, plan, gate_w, up_w, down_w)


def _held_experts_bwd(first_expert, rows, compute_dtype, residuals, dy):
    """A turn at a time, as the forward: the turn's two up-products are made
    again (nothing of a pair is kept from the forward), then with ``a =
    silu(g) u`` and ``z = dy W_down^T`` (one product): the gate's gradient
    ``a . z``, ``da = w z``, the three weight gradients summed into their
    expert's fp32 slab (``grouped_outer``: an expert with no pair in the
    turn is not touched), and the rows' gradient ``dg W_gate^T + du
    W_up^T`` added to its token's. Eight products a pair to the forward's
    three."""
    from determined_clone_tpu.ops.grouped_matmul import (
        add_rows,
        grouped_matmul,
        grouped_outer,
    )

    h, plan, gate_w, up_w, down_w = residuals
    N, D = h.shape
    dt = compute_dtype
    x, dy16 = h.astype(dt), dy.astype(dt)
    wg, wu, wd = (w.astype(dt) for w in (gate_w, up_w, down_w))
    n_pairs = plan.order.shape[0]
    f32 = jnp.float32

    def product(a, stack, sizes, transpose=False):
        return grouped_matmul(a, stack, sizes, transpose=transpose,
                              tiles=TRAIN_TILES)

    def one_chunk(c, carry):
        dx, d_sorted, d_wg, d_wu, d_wd = carry
        real, at, sizes = _chunk(plan, c, rows)
        keep = real[:, None]
        token = plan.tokens[at]
        xs = x[token]
        w = jnp.where(real, plan.weights[at], 0.0)[:, None]
        g = jnp.where(keep, product(xs, wg, sizes), 0.0)
        u = jnp.where(keep, product(xs, wu, sizes), 0.0)
        sig = jax.nn.sigmoid(g)
        silu = g * sig
        act = silu * u
        d_out = jnp.where(keep, dy16[token], 0)
        z = jnp.where(keep, product(d_out, wd, sizes, transpose=True), 0.0)
        d_gate = jnp.sum(act * z, axis=-1)
        d_act = z * w
        d_g = (d_act * u * (sig + silu * (1.0 - sig))).astype(dt)
        d_u = (d_act * silu).astype(dt)
        # every operand of the sums is finite in the rows that hold no pair
        # too (zeros, or pair 0's token): grouped_outer asks for that
        d_wd = grouped_outer((act * w).astype(dt).T, d_out, sizes, d_wd,
                             tiles=TRAIN_OUTER_TILES)
        d_wg = grouped_outer(xs.T, d_g, sizes, d_wg, tiles=TRAIN_OUTER_TILES)
        d_wu = grouped_outer(xs.T, d_u, sizes, d_wu, tiles=TRAIN_OUTER_TILES)
        d_rows = product(d_g, wg, sizes, transpose=True) \
            + product(d_u, wu, sizes, transpose=True)
        dx = add_rows(dx, token, _in_tiles(d_rows), sizes)
        d_sorted = d_sorted.at[jnp.where(real, at, n_pairs)].set(
            d_gate, mode="drop")
        return dx, d_sorted, d_wg, d_wu, d_wd

    dx, d_sorted, d_wg, d_wu, d_wd = jax.lax.fori_loop(
        0, _chunks(plan, rows), one_chunk,
        (_in_tiles(jnp.zeros((N, D), f32)), jnp.zeros((n_pairs,), f32))
        + tuple(jnp.zeros(w.shape, f32) for w in (gate_w, up_w, down_w)))
    d_gates = jnp.zeros((n_pairs,), f32).at[plan.order].set(d_sorted)
    return (dx.reshape(N, D).astype(h.dtype), d_gates.reshape(N, -1), None,
            d_wg.astype(gate_w.dtype), d_wu.astype(up_w.dtype),
            d_wd.astype(down_w.dtype))


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def routed_experts_trained(params: Params, h: jax.Array, *, first_expert: int,
                           n_experts: int, k: int, scale: float,
                           rows: int = TRAIN_PAIR_ROWS,
                           compute_dtype=jnp.bfloat16
                           ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """:func:`routed_experts` with a reverse mode, over one layer's own
    parameters (``experts_*`` kernels [n_held, ...] in any dtype, cast to
    ``compute_dtype`` for the products): h [N, D] fp32 -> ``(y [N, D]
    fp32, statistics)``.

    Same routing (:func:`route` over all ``n_experts``) and as dropless:
    the pairs that fell to held experts, sorted by expert, go ``rows`` a
    loop turn through products grouped by expert (Pallas:
    ``ops/grouped_matmul.py``), as many turns as the pairs fill, so that the
    work of both passes is in proportion to the pairs that fell here (the
    served loop multiplies a tile of one expert's pairs at a time; a trained
    step holds ten times the pairs and a backward pass, whose weight
    gradients a tile at a time would read and write whole). Gradients
    reach ``h`` (through the experts and through the router), the three
    expert stacks and, through the gates ``scale * s / sum s``, the
    router's kernel; nothing flows through the selection or its bias.

    ``statistics``: ``load`` [n_experts] fp32, the tokens that chose each
    expert of all ``n_experts`` (what the selection bias is moved by);
    ``pairs_held`` and ``experts_hit`` as :func:`routed_experts` counts
    them; ``experts`` [N, k] int32, every token's chosen experts.
    """
    n_held = params["experts_gate"]["kernel"].shape[0]
    if params["router"]["kernel"].shape[-1] != n_experts \
            or not 0 <= first_expert <= first_expert + n_held <= n_experts:
        raise ValueError(
            f"experts [{first_expert}, {first_expert + n_held}) are not "
            f"among the router's {params['router']['kernel'].shape[-1]} "
            f"(n_experts {n_experts})")
    with jax.named_scope("moe_route"):
        experts, gates = route(
            {"kernel": params["router"]["kernel"],
             "bias": jax.lax.stop_gradient(params["router"]["bias"])},
            h, k=k, scale=scale)
        load = jnp.zeros((n_experts,), jnp.float32).at[
            experts.reshape(-1)].add(1.0)
        held = jax.lax.dynamic_slice_in_dim(load, first_expert, n_held)
    with jax.named_scope("moe_experts"):
        y = _held_experts(
            h, gates, experts, *(params[n]["kernel"] for n in (
                "experts_gate", "experts_up", "experts_down")),
            first_expert, rows, compute_dtype)
    return y, {"load": load, "pairs_held": jnp.sum(held),
               "experts_hit": jnp.sum(held > 0), "experts": experts}
