"""Plain reference for EvaByte: the forward pass over a whole sequence, in
straightforward ``jax.numpy``. No cache, no kernel, no batching; it imports
nothing of ``determined_clone_tpu`` and receives its weights from the
benchmark's seeded generator (``benchmarks/adapters/evabyte.py``), as a tree
with the leaves

    embed/table [V, D]; final_norm/scale [D]; lm_head/kernel [D, P * V];
    blocks/{ln1,ln2}/scale [L, D];
    blocks/{attn_q,attn_k,attn_v,attn_out}/kernel [L, D, D];
    blocks/eva/{phi,mu} [L, H, hd];
    blocks/{mlp_gate,mlp_up}/kernel [L, D, F]; blocks/mlp_down/kernel [L, F, D].

Written from the published configuration
(https://huggingface.co/EvaByte/EvaByte/blob/main/config.json: hidden 4096,
32 heads of 128, intermediate 11008, ``window_size`` 2048, ``chunk_size``
16, ``rope_theta`` 1e5, ``rms_norm_eps`` 1e-5, ``norm_add_unit_offset``,
``fp32_skip_add``, ``fp32_logits``, ``num_pred_heads`` 8, vocabulary 320,
untied, no biases) and the published description of EVA attention. For a
pre-norm decoder with residual stream x:

1. ``h = x / sqrt(mean(x^2) + eps) * (1 + w)``.
2. ``q, k, v = h Wq, h Wk, h Wv``, H heads of hd; rotary on q and k;
   ``s = hd ** -0.5``.
3. Token t lies in window ``t // window`` and chunk ``t // chunk``. Per head
   two learned vectors phi, mu. Summary of a whole chunk c:
   ``a_j = softmax over the chunk's tokens of (s * k_j . phi)``,
   ``K_c = sum_j a_j k_j + mu``, ``V_c = sum_j a_j v_j``.
4. Query t attends exactly to the tokens ``j <= t`` of its own window and
   to the summaries of every chunk of every earlier window, under one
   softmax. Windows are blocks, not sliding.
5. ``x = x + o Wo``; ``x = x + Wd (silu(Wg h') * Wu h')``, ``h' = norm(x)``.
6. After the last layer the norm, then ``Linear(D -> P x V)``: head i
   predicts byte t + 1 + i.

Departures that may exist, because the configuration file does not state
them (the benchmark's configuration lists them under ``assumed``): the scale
``s`` inside the chunk softmax; the rotary convention (half-split layout,
absolute positions); that summaries are pooled from *rotated* keys.

``precision`` selects how matrix products (the projections, q.k, the
probabilities times v, k.phi, the head) are computed, as in
``reference/gpt2.py``: ``"f32"`` float32 at ``Precision.HIGHEST``, the
reference proper; ``"bf16"`` operands rounded to bfloat16, float32 sums,
what the configuration states for the system; ``"fp8"`` operands rounded to
float8 e4m3 under one scale per tensor, the control.

A layer's weights are raised to float32 one layer at a time and attention
runs a window at a time, so that a 14336-token sequence fits one chip
beside 6.5 GB of bfloat16 weights.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

from benchmarks.reference.gpt2 import matmul

Params = Dict[str, Any]

WINDOW = 2048
CHUNK = 16
ROPE_BASE = 1e5
RMS_EPS = 1e-5
PRED_HEADS = 8


def rmsnorm(w: jax.Array, x: jax.Array) -> jax.Array:
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + RMS_EPS) * (1.0 + w.astype(jnp.float32))


def rotary(x: jax.Array, positions: jax.Array) -> jax.Array:
    """x: [T, H, hd]; positions: [T]. Half-split (rotate-half) layout."""
    half = x.shape[-1] // 2
    freqs = ROPE_BASE ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def chunk_summaries(k: jax.Array, v: jax.Array, phi: jax.Array,
                    mu: jax.Array, chunk: int, precision: str):
    """k, v: [T, H, hd], T whole chunks -> (K_c, V_c) each [T / chunk, H, hd]."""
    T, H, hd = k.shape
    kc = k.reshape(T // chunk, chunk, H, hd)
    vc = v.reshape(T // chunk, chunk, H, hd)
    # k_j . phi per head: [chunks, H, chunk, hd] @ [H, hd, 1]
    scores = matmul(kc.transpose(0, 2, 1, 3), phi[None, :, :, None],
                    precision)[..., 0] * hd ** -0.5            # [chunks, H, C]
    a = jax.nn.softmax(scores, axis=-1).transpose(0, 2, 1)[..., None]
    return jnp.sum(a * kc, axis=1) + mu[None], jnp.sum(a * vc, axis=1)


def eva_attention(q: jax.Array, k: jax.Array, v: jax.Array, phi: jax.Array,
                  mu: jax.Array, *, window: int, chunk: int, precision: str,
                  summaries: bool = True) -> jax.Array:
    """q, k, v: [T, H, hd] of one sequence (k rotated), T whole windows or
    fewer than one -> [T, H, hd]. ``summaries=False`` leaves the chunk
    summaries out (what a window-only model would compute; tests)."""
    T, H, hd = q.shape
    sum_k, sum_v = chunk_summaries(k, v, phi, mu, chunk, precision)
    out = []
    for lo in range(0, T, window):
        hi = min(lo + window, T)
        n_sum = lo // chunk if summaries else 0  # chunks of earlier windows
        keys = jnp.concatenate([k[lo:hi], sum_k[:n_sum]]).transpose(1, 2, 0)
        vals = jnp.concatenate([v[lo:hi], sum_v[:n_sum]]).transpose(1, 0, 2)
        scores = matmul(q[lo:hi].transpose(1, 0, 2), keys,
                        precision) * hd ** -0.5        # [H, hi - lo, S]
        causal = jnp.tril(jnp.ones((hi - lo, hi - lo), bool))
        seen = jnp.concatenate(
            [causal, jnp.ones((hi - lo, n_sum), bool)], axis=1)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        out.append(matmul(probs, vals, precision).transpose(1, 0, 2))
    return jnp.concatenate(out)


def block(lp: Params, x: jax.Array, n_heads: int, *, window: int,
          chunk: int, precision: str, summaries: bool) -> jax.Array:
    """One decoder block on x [T, D] (float32)."""
    T, D = x.shape
    hd = D // n_heads
    lp = jax.tree.map(lambda w: w.astype(jnp.float32), lp)
    pos = jnp.arange(T)
    h = rmsnorm(lp["ln1"]["scale"], x)
    q = rotary(matmul(h, lp["attn_q"]["kernel"], precision
                      ).reshape(T, n_heads, hd), pos)
    k = rotary(matmul(h, lp["attn_k"]["kernel"], precision
                      ).reshape(T, n_heads, hd), pos)
    v = matmul(h, lp["attn_v"]["kernel"], precision).reshape(T, n_heads, hd)
    o = eva_attention(q, k, v, lp["eva"]["phi"], lp["eva"]["mu"],
                      window=window, chunk=chunk, precision=precision,
                      summaries=summaries)
    x = x + matmul(o.reshape(T, D), lp["attn_out"]["kernel"], precision)
    h = rmsnorm(lp["ln2"]["scale"], x)
    act = jax.nn.silu(matmul(h, lp["mlp_gate"]["kernel"], precision)) \
        * matmul(h, lp["mlp_up"]["kernel"], precision)
    return x + matmul(act, lp["mlp_down"]["kernel"], precision)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
def _all_head_logits(params, tokens, n_heads, precision, window, chunk,
                     summaries, heads):
    x = params["embed"]["table"][tokens].astype(jnp.float32)

    def body(x, lp):
        return block(lp, x, n_heads, window=window, chunk=chunk,
                     precision=precision, summaries=summaries), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = rmsnorm(params["final_norm"]["scale"], x)
    head = params["lm_head"]["kernel"].astype(jnp.float32)
    vocab = head.shape[1] // PRED_HEADS
    logits = matmul(x, head[:, :heads * vocab], precision)
    return logits.reshape(len(tokens), heads, vocab)


def all_head_logits(params: Params, tokens: Sequence[int], *, n_heads: int,
                    precision: str = "f32", window: int = WINDOW,
                    chunk: int = CHUNK, summaries: bool = True,
                    heads: int = PRED_HEADS) -> jax.Array:
    """Logits [len(tokens), heads, V] of one sequence whose length is whole
    chunks: head i at position t scores byte t + 1 + i."""
    if len(tokens) % chunk:
        raise ValueError(f"{len(tokens)} tokens are not whole chunks of "
                         f"{chunk}")
    return _all_head_logits(params, jnp.asarray(tokens, jnp.int32), n_heads,
                            precision, window, chunk, summaries, heads)


def teacher_forced_logits(params: Params, tokens: Sequence[int], *,
                          n_heads: int, precision: str = "f32",
                          pad_to: int) -> jax.Array:
    """Head 0's logits [len(tokens), V] of one sequence, at the published
    window and chunk. Padding on the right reaches no position on its left:
    attention is causal inside a window, and a chunk's summary is seen only
    from later windows, so a chunk that holds padding is seen by padding
    alone."""
    padded = list(tokens) + [0] * (pad_to - len(tokens))
    return all_head_logits(params, padded, n_heads=n_heads,
                           precision=precision, heads=1)[:len(tokens), 0]
