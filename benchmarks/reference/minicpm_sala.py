"""Plain reference for MiniCPM-SALA: the forward pass over a whole sequence,
in straightforward ``jax.numpy``. No cache, no kernel, no batching, no
chunked recurrence; it imports nothing of ``determined_clone_tpu`` and
receives its weights from the benchmark's seeded generator
(``benchmarks/adapters/minicpm_sala.py``), as a tree with the leaves

    embed/table [V, D]; final_norm/scale [D]; lm_head/kernel [D, V];
    sparse/...  [Ls, ...] and lightning/... [Ll, ...], each with
      {ln1,ln2}/scale [., D]; {q_norm,k_norm}/scale [., d];
      attn_q/kernel [., D, H d]; {attn_k,attn_v}/kernel [., D, G d]
      (lightning: [., D, H d]); attn_gate/kernel [., D, H d];
      attn_out/kernel [., H d, D];
      {mlp_gate,mlp_up}/kernel [., D, F]; mlp_down/kernel [., F, D];
    lightning/out_norm/scale [Ll, H d]; lightning/decay [Ll, H].

Written from the published configuration
(https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json: hidden
4096, 32 query heads of 128 over 2 key/value heads, intermediate 16384,
``mixer_types``, ``qk_norm``, ``attn_use_rope`` false, ``lightning_use_rope``
true, ``rope_theta`` 10000, ``rms_norm_eps`` 1e-6, ``scale_emb`` 12,
``scale_depth`` 1.4, ``dim_model_base`` 256, output gates and the lightning
output norm, untied head, no biases) and the published descriptions of its
two mixers. ``x`` [T, D] is the float32 residual stream, ``rs = scale_depth
/ sqrt(32)`` (the published depth, whatever part of it is held):

1. ``x = scale_emb * E[tokens]``; ``norm(x) = x / sqrt(mean(x^2) + eps) *
   w``.
2. Every layer: ``x += rs * Mixer(h)``, ``h = norm(x)``; then ``x += rs *
   W_down(silu(W_gate h') * W_up h')``, ``h' = norm(x)``.
3. ``lightning-attn``: ``q = rope(norm_d(W_q h))``, ``k = rope(norm_d(W_k
   h))``, ``v = W_v h``, H heads of d. Per head, decay ``lam``: ``S_t = lam
   S_{t-1} + k_t^T v_t``, ``S_0 = 0``; ``o_t = d^-1/2 q_t S_t``, a literal
   scan over the tokens. ``y = W_o (norm(o) * sigmoid(W_g h))``, that norm
   over all heads' outputs side by side.
4. ``minicpm4`` (InfLLM-V2; MiniCPM4 report, arXiv:2506.07900): ``q =
   norm_d(W_q h)`` of H heads, ``k = norm_d(W_k h)`` and ``v = W_v h`` of G,
   no positions; head h reads key/value head ``h // (H / G)``. A query at
   position t with ``t + 1 <= dense_len`` attends every key ``<= t``. Past
   it: ``kc_j = mean(k[stride j : stride j + kernel])``; ``p_h =
   softmax_j(d^-1/2 q_h . kc_j)`` over the j with ``stride j + kernel - 1 <=
   t``; ``r_g = sum_{h in g} p_h``; block b (positions ``[block b, block (b
   + 1))``) scores ``max_j r_g[j]`` over the ``kc_j`` that overlap it; the
   first ``init_blocks`` blocks and those holding positions ``(t - window,
   t]`` are taken, the best-scored others fill up to ``topk`` blocks in all
   (``lax.top_k``: ties to the lower block); causal softmax attention over
   the rows of the chosen blocks. ``y = W_o (o * sigmoid(W_g h))``.
5. ``logits = W_head (norm(x) / (D / dim_model_base))``.

What the configuration does not state (the benchmark's configuration lists
these under ``assumed``): the decay table (Lightning Attention's, as
MiniMax-Text-01 has it: ``slope_h = 2^(-8 (h + 1) / H)``, ``lam = exp(-
slope_h (1 - l / 31 + 1e-5))``, l the published layer); the sparse
constants (MiniCPM4's published ``sparse_config``: kernel 32, stride 16,
block 64, topk 64, init_blocks 1, window 2048, dense_len 8192); that forced
blocks count toward the 64; the tie order; the exact softmax in step 4's
scoring (the released kernels approximate its normaliser); one scale [d]
per layer for each of ``norm_d``; the output norm's width; the rotary
convention (half-split, absolute positions).

``precision`` selects how matrix products are computed, as in
``reference/gpt2.py``: ``"f32"`` float32 at ``Precision.HIGHEST``, the
reference proper; ``"bf16"`` operands rounded to bfloat16, float32 sums,
what the configuration states for the system; ``"fp8"`` operands rounded to
float8 e4m3 under one scale per tensor, the control. The selection is made
from the scores of the same precision.

A weight is raised to float32 by the product that reads it, attention runs
a block of queries at a time and the MLP a block of rows at a time, so that
34816 positions fit one chip beside 5.6 GB of bfloat16 weights.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.gpt2 import matmul

Params = Dict[str, Any]

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
# the layers the benchmark's configuration holds: published layers 9..16
MIXERS = (SPARSE,) + (LIGHTNING,) * 6 + (SPARSE,)
PUBLISHED_LAYERS = 32
SCALE_EMB, SCALE_DEPTH, DIM_MODEL_BASE = 12.0, 1.4, 256
ROPE_BASE, RMS_EPS = 10000.0, 1e-6
SPARSE_CONSTANTS = dict(kernel=32, stride=16, block=64, topk=64,
                        init_blocks=1, window=2048, dense_len=8192)
ROWS = 2048      # rows of the MLP and of the head computed at a time
Q_BLOCK = 128    # queries of a sparse layer's attention at a time


def decay_table(layers: Sequence[int], n_heads: int,
                published_layers: int = PUBLISHED_LAYERS) -> np.ndarray:
    """``lam`` [len(layers), H] of the published layers ``layers``."""
    slope = 2.0 ** (-8.0 * np.arange(1, n_heads + 1) / n_heads)
    depth = 1.0 - np.asarray(layers, np.float64)[:, None] / (
        published_layers - 1) + 1e-5
    return np.exp(-slope[None, :] * depth).astype(np.float32)


def rmsnorm(w: jax.Array, x: jax.Array) -> jax.Array:
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + RMS_EPS) * w.astype(jnp.float32)


def rotary(x: jax.Array, positions: jax.Array) -> jax.Array:
    """x: [T, H, d]; positions: [T]. Half-split (rotate-half) layout."""
    half = x.shape[-1] // 2
    freqs = ROPE_BASE ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def lightning_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        decay: jax.Array, precision: str) -> jax.Array:
    """q, k, v: [T, H, d]; decay [H] -> o [T, H, d]: the recurrence, a
    token a step."""
    H, d = q.shape[1:]

    def step(S, qkv):
        q_t, k_t, v_t = qkv                                      # [H, d]
        S = decay[:, None, None] * S + k_t[:, :, None] * v_t[:, None, :]
        return S, matmul(q_t[:, None, :], S, precision)[:, 0] * d ** -0.5

    _, o = jax.lax.scan(step, jnp.zeros((H, d, d), jnp.float32), (q, k, v))
    return o


def sparse_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     kernel: int, stride: int, block: int, topk: int,
                     init_blocks: int, window: int, dense_len: int,
                     precision: str, q_block: int = Q_BLOCK,
                     choice: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """q: [T, H, d]; k, v: [T, G, d], T whole blocks. Returns ``(o [T, H,
    d], chosen [G, T, T / block] bool)``: the blocks each query's group
    attended (all its blocks while ``t + 1 <= dense_len``). With ``choice``
    (the same shape) the selection is not made and ``choice`` is attended
    instead (the tests hand over the program's)."""
    T, H, d = q.shape
    G = k.shape[1]
    nb, qb = T // block, math.gcd(q_block, T)
    if T % block:
        raise ValueError(f"{T} positions are not whole blocks of {block}")
    scale = d ** -0.5

    def heads(x):  # [., G, d] -> [., H, d]: head h reads group h // (H / G)
        return jnp.repeat(x, H // G, axis=1)

    # compressed keys, and which of them overlap which block
    J = (T - kernel) // stride + 1
    starts = stride * jnp.arange(J)
    kc = jnp.mean(k[starts[:, None] + jnp.arange(kernel)[None]], axis=1)
    overlap = (starts[None, :] < block * (jnp.arange(nb) + 1)[:, None]) \
        & (starts[None, :] + kernel > block * jnp.arange(nb)[:, None])
    # at most this many overlap one block; their indices, per block
    width = (block + kernel) // stride
    first = jnp.argmax(overlap, axis=1)
    near = jnp.minimum(first[:, None] + jnp.arange(width)[None], J - 1)
    near_ok = jnp.take_along_axis(overlap, near, axis=1)         # [nb, w]
    kc_h = heads(kc).transpose(1, 2, 0)                          # [H, d, J]
    k_h = heads(k).transpose(1, 2, 0)                            # [H, d, T]
    v_h = heads(v).transpose(1, 0, 2)                            # [H, T, d]
    b = jnp.arange(nb)

    def select(q_h, t):
        s = matmul(q_h, kc_h, precision) * scale                 # [H, qb, J]
        complete = (starts + kernel - 1)[None, :] <= t[:, None]  # [qb, J]
        p = jax.nn.softmax(jnp.where(complete[None], s, -jnp.inf), axis=-1)
        p = jnp.where(complete[None], p, 0.0)      # none complete: NaN -> 0
        r = jnp.sum(p.reshape(G, H // G, qb, J), axis=1)         # [G, qb, J]
        score = jnp.max(jnp.where(near_ok, r[:, :, near], -jnp.inf), axis=-1)
        exists = b[None, :] <= (t // block)[:, None]             # [qb, nb]
        forced = exists & ((b[None, :] < init_blocks) | (
            b[None, :] >= (jnp.maximum(t - window + 1, 0) // block)[:, None]))
        prio = jnp.where(forced, jnp.inf, jnp.where(exists, score, -jnp.inf))
        vals, idx = jax.lax.top_k(prio, min(topk, nb))       # [G, qb, topk]
        took = jnp.zeros((G, qb, nb), bool).at[
            jnp.arange(G)[:, None, None], jnp.arange(qb)[None, :, None],
            idx].set(vals > -jnp.inf)
        return jnp.where((t + 1 <= dense_len)[None, :, None], exists[None],
                         took)

    def one_block(lo):
        q_h = jax.lax.dynamic_slice_in_dim(q, lo, qb).transpose(1, 0, 2)
        t = lo + jnp.arange(qb)
        chosen = select(q_h, t) if choice is None else \
            jax.lax.dynamic_slice_in_dim(choice, lo, qb, axis=1)
        seen = jnp.repeat(chosen, block, axis=-1) \
            & (jnp.arange(T)[None, :] <= t[:, None])[None]       # [G, qb, T]
        s = matmul(q_h, k_h, precision) * scale                  # [H, qb, T]
        probs = jax.nn.softmax(
            jnp.where(jnp.repeat(seen, H // G, axis=0), s, -jnp.inf), -1)
        return matmul(probs, v_h, precision).transpose(1, 0, 2), chosen

    out, chosen = jax.lax.map(one_block, jnp.arange(0, T, qb))
    return (out.reshape(T, H, d),
            chosen.transpose(1, 0, 2, 3).reshape(G, T, nb))


def _by_rows(fn, x: jax.Array) -> jax.Array:
    """``fn`` over x [T, ...] a block of ``ROWS`` rows at a time."""
    T = x.shape[0]
    rows = math.gcd(ROWS, T)
    out = jax.lax.map(fn, x.reshape(T // rows, rows, *x.shape[1:]))
    return out.reshape(T, *out.shape[2:])


def _mlp(lp: Params, x: jax.Array, rs: float, precision: str) -> jax.Array:
    def rows(x):
        h = rmsnorm(lp["ln2"]["scale"], x)
        act = jax.nn.silu(matmul(h, lp["mlp_gate"]["kernel"], precision)) \
            * matmul(h, lp["mlp_up"]["kernel"], precision)
        return x + rs * matmul(act, lp["mlp_down"]["kernel"], precision)

    return _by_rows(rows, x)


def _heads(lp: Params, h: jax.Array, name: str, precision: str,
           d: int) -> jax.Array:
    out = matmul(h, lp[name]["kernel"], precision)
    return out.reshape(h.shape[0], -1, d)


def lightning_layer(lp: Params, x: jax.Array, n_heads: int, rs: float,
                    precision: str) -> jax.Array:
    T, D = x.shape
    d = lp["attn_q"]["kernel"].shape[-1] // n_heads
    pos = jnp.arange(T)
    h = rmsnorm(lp["ln1"]["scale"], x)
    q = rotary(rmsnorm(lp["q_norm"]["scale"],
                       _heads(lp, h, "attn_q", precision, d)), pos)
    k = rotary(rmsnorm(lp["k_norm"]["scale"],
                       _heads(lp, h, "attn_k", precision, d)), pos)
    v = _heads(lp, h, "attn_v", precision, d)
    o = lightning_attention(q, k, v, lp["decay"].astype(jnp.float32),
                            precision)
    o = rmsnorm(lp["out_norm"]["scale"], o.reshape(T, -1))
    gate = jax.nn.sigmoid(matmul(h, lp["attn_gate"]["kernel"], precision))
    x = x + rs * matmul(o * gate, lp["attn_out"]["kernel"], precision)
    return _mlp(lp, x, rs, precision)


def sparse_layer(lp: Params, x: jax.Array, n_heads: int, rs: float,
                 precision: str, sparse: Dict[str, int],
                 choice: Optional[jax.Array]
                 ) -> Tuple[jax.Array, jax.Array]:
    T, D = x.shape
    d = lp["attn_q"]["kernel"].shape[-1] // n_heads
    h = rmsnorm(lp["ln1"]["scale"], x)
    q = rmsnorm(lp["q_norm"]["scale"], _heads(lp, h, "attn_q", precision, d))
    k = rmsnorm(lp["k_norm"]["scale"], _heads(lp, h, "attn_k", precision, d))
    v = _heads(lp, h, "attn_v", precision, d)
    o, chosen = sparse_attention(q, k, v, precision=precision,
                                 choice=choice, **sparse)
    gate = jax.nn.sigmoid(matmul(h, lp["attn_gate"]["kernel"], precision))
    x = x + rs * matmul(o.reshape(T, -1) * gate, lp["attn_out"]["kernel"],
                        precision)
    return _mlp(lp, x, rs, precision), chosen


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
def _hidden(params, tokens, n_heads, precision, mixers, sparse,
            published_layers, dim_model_base, choices):
    """The final norm's output over ``dim_model_base`` scaling, [T, D],
    and every sparse layer's chosen blocks [Ls, G, T, T / block]."""
    rs = SCALE_DEPTH / published_layers ** 0.5
    x = SCALE_EMB * params["embed"]["table"][tokens].astype(jnp.float32)
    seen = {SPARSE: 0, LIGHTNING: 0}
    chosen = []
    for kind in mixers:                  # a layer at a time, as published
        stack = params["sparse" if kind == SPARSE else "lightning"]
        lp = jax.tree.map(lambda w: w[seen[kind]], stack)
        if kind == SPARSE:
            x, took = sparse_layer(
                lp, x, n_heads, rs, precision, dict(sparse),
                None if choices is None else choices[seen[kind]])
            chosen.append(took)
        else:
            x = lightning_layer(lp, x, n_heads, rs, precision)
        seen[kind] += 1
    D = x.shape[1]
    return (rmsnorm(params["final_norm"]["scale"], x) / (D / dim_model_base),
            jnp.stack(chosen))


@functools.partial(jax.jit, static_argnums=(2,))
def _head(x, kernel, precision):
    return matmul(x, kernel, precision)


def forward(params: Params, tokens: Sequence[int], *, n_heads: int,
            precision: str = "f32", mixers: Sequence[str] = MIXERS,
            published_layers: int = PUBLISHED_LAYERS,
            dim_model_base: int = DIM_MODEL_BASE,
            choices: Optional[Any] = None, n_rows: Optional[int] = None,
            **sparse: int) -> Tuple[np.ndarray, np.ndarray]:
    """``(logits [n_rows, V], chosen [Ls, G, T, T / block])`` of one
    sequence of T tokens, whole blocks; logits of its first ``n_rows``
    positions (all of them by default). ``sparse`` overrides the published
    selection constants (the tests' toy sizes); ``choices`` (the shape of
    ``chosen``) replaces every sparse layer's own selection: the tests'
    entry point for comparing attention given a choice."""
    constants = tuple(sorted({**SPARSE_CONSTANTS, **sparse}.items()))
    x, chosen = _hidden(params, jnp.asarray(tokens, jnp.int32), n_heads,
                        precision, tuple(mixers), constants,
                        published_layers, dim_model_base,
                        None if choices is None else jnp.asarray(choices))
    T = x.shape[0]
    n_rows = T if n_rows is None else n_rows
    rows = math.gcd(ROWS, T)
    head = params["lm_head"]["kernel"]
    logits = np.empty((n_rows, head.shape[1]), np.float32)
    for lo in range(0, n_rows, rows):    # a block of rows at a time, to the
        logits[lo:lo + rows] = np.asarray(  # host: [33792, 73448] is 10 GB
            _head(x[lo:lo + rows], head, precision))[:n_rows - lo]
    return logits, np.asarray(chosen)


def teacher_forced_logits(params: Params, tokens: Sequence[int], *,
                          n_heads: int, precision: str = "f32",
                          pad_to: int, **constants: Any) -> np.ndarray:
    """Logits [len(tokens), V] of one sequence at the published constants
    and the benchmark's eight layers (``harness/serve.py`` gives none
    other; the tests' toy cell binds its own ``constants``, ``forward``'s
    keywords). Padding on the right reaches no position on its left: the
    recurrence runs forward, attention is causal, and a compressed key that
    holds padding is complete only for queries past the padding."""
    padded = list(tokens) + [0] * (pad_to - len(tokens))
    return forward(params, padded, n_heads=n_heads, precision=precision,
                   n_rows=len(tokens), **constants)[0]
