"""Kimi Delta Attention (KDA): a gated delta rule with a decay per channel
and per token, and the short causal convolution that feeds it.

Per head, with a state ``S`` of ``[d_k, d_v]`` fp32 (``S_0 = 0``), a decay
``a_t = exp(g_t)`` in (0, 1] **per key channel and per token** and a write
strength ``b_t`` in [0, 1] per token (Kimi Linear, arXiv:2510.26692, "Kimi
Delta Attention"):

    S'  = Diag(a_t) S_{t-1}
    S_t = S' + b_t k_t (v_t - S'^T k_t)^T        o_t = S_t^T q_t

so a sequence's memory is one state that does not grow; the delta term takes
out what the decayed state already answers to ``k_t`` before it writes.
Two forms of the same recurrence, both taking the state in and giving it
back, both over *real* tokens only: a masked position has ``g = 0`` and
``b = 0``, so it neither decays the state nor writes to it, and a padded
bucket leaves the state where its last real token left it.

- ``T == 1``, a decode step: the recurrence itself, elementwise in fp32.
- ``T > 1``, a prefill slice: chunks of ``chunk`` tokens (the WY / UT
  transform). With ``G_i`` the sum of ``g`` over the chunk up to and
  including position ``i`` and ``u_i = b_i (v_i - S'_i^T k_i)`` the value
  position ``i`` really writes,

      A_ij = b_i sum_c k_ic k_jc exp(G_ic - G_jc)          (j <  i)
      B_ij =     sum_c q_ic k_jc exp(G_ic - G_jc)          (j <= i)
      (I + A) U = b (V - (K exp(G)) S_0)
      O   = (Q exp(G)) S_0 + B U
      S_C = Diag(exp(G_C)) S_0 + (K exp(G_C - G))^T U

  ``X = (I + A)^-1`` is a forward substitution over the chunk's rows, made
  for all chunks at once (it does not depend on the state); the chunks are
  then walked in order with three products a chunk. The decay between two
  positions is formed per channel from the difference ``G_i - G_j`` (the
  ``[C, C, d]`` factor, one chunk at a time): **every exponent is a
  non-positive difference**, so the smallest decays underflow to 0 and
  nothing overflows. The lightning chunk form (``lam ** (a_i - a_j)``, one
  scalar a head) does not carry over: the decay is a vector and the delta
  rule couples a chunk's writes.

Everything here is fp32: the state, the decays, the solve, and the
products (``Precision.HIGHEST``: a read of the state feeds the next write,
so a rounded read is a rounded state).

:func:`short_conv` is the causal depthwise convolution before it, with the
last ``K - 1`` rows carried between calls beside the state.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

CHUNK = 64
_HI = jax.lax.Precision.HIGHEST


def short_conv(x: jax.Array, weight: jax.Array, tail: jax.Array,
               n_real: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Causal depthwise convolution over positions, no bias: ``y_t = sum_j
    weight[j] x_{t - K + 1 + j}`` (tap ``K - 1`` multiplies the current
    row). x [B, T, W] the rows of this call; weight [K, W] fp32; ``tail``
    [B, K - 1, W] the last ``K - 1`` rows before the call (zeros at a
    sequence's start); ``n_real`` [B] how many of the call's rows are real
    (they come first). Returns ``(y [B, T, W] fp32, tail' [B, K - 1, W])``:
    the rows before the first row that follows the real ones, so a row of
    padding does not shift the tail."""
    K, T = weight.shape[0], x.shape[1]
    rows = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    w = weight.astype(jnp.float32)
    y = sum(w[j] * rows[:, j:j + T].astype(jnp.float32) for j in range(K))
    new_tail = jax.vmap(lambda r, n: jax.lax.dynamic_slice_in_dim(
        r, n, K - 1, axis=0))(rows, n_real.astype(jnp.int32))
    return y, new_tail.astype(tail.dtype)


def _step(q, k, v, g, b, state, token_mask):
    f32 = jnp.float32
    q, k, v, g = (x[:, 0].astype(f32) for x in (q, k, v, g))     # [B, H, d]
    decayed = jnp.exp(g)[..., None] * state                # Diag(a) S
    answered = jnp.sum(k[..., None] * decayed, axis=-2)    # S'^T k
    u = b[:, 0].astype(f32)[..., None] * (v - answered)
    new = decayed + k[..., None] * u[..., None, :]
    state = jnp.where(token_mask[:, 0, None, None, None], new, state)
    out = jnp.sum(q[..., None] * state, axis=-2)
    return out[:, None], state


def _matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.matmul(a, b, precision=_HI,
                      preferred_element_type=jnp.float32)


def _inverse_unit_lower(a: jax.Array) -> jax.Array:
    """``(I + a)^-1`` for ``a`` [..., C, C] strictly lower triangular, by
    forward substitution over the rows: row ``i`` of the inverse is ``e_i -
    sum_{j < i} a_ij row_j``."""
    C = a.shape[-1]
    eye = jnp.eye(C, dtype=a.dtype)

    def row(i, x):
        a_i = jax.lax.dynamic_index_in_dim(a, i, axis=-2, keepdims=False)
        new = eye[i] - jnp.sum(a_i[..., :, None] * x, axis=-2)
        return jax.lax.dynamic_update_index_in_dim(x, new, i, axis=-2)

    return jax.lax.fori_loop(0, C, row, jnp.zeros_like(a))


def kda(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
        b: jax.Array, state: jax.Array, token_mask: jax.Array, *,
        chunk: int = CHUNK) -> Tuple[jax.Array, jax.Array]:
    """q, k [B, T, H, d_k] (``k`` of unit length a head, ``q`` scaled), v
    [B, T, H, d_v]; g [B, T, H, d_k] fp32 the log decay (<= 0); b [B, T, H]
    fp32 the write strength; state [B, H, d_k, d_v] fp32, the state before
    the first token; token_mask [B, T] bool. Returns ``(o [B, T, H, d_v]
    fp32, state' [B, H, d_k, d_v] fp32)``: the outputs at every position
    (those of masked positions mean nothing) and the state after the last
    real token."""
    B, T, H, dk = q.shape
    f32 = jnp.float32
    state = state.astype(f32)
    if T == 1:
        return _step(q, k, v, g, b, state, token_mask)
    C = chunk
    pad = -T % C
    if pad:
        q, k, v, g = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for x in (q, k, v, g))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        token_mask = jnp.pad(token_mask, ((0, 0), (0, pad)))
    N = (T + pad) // C
    real = token_mask[:, :, None]
    g = jnp.where(real[..., None], g.astype(f32), 0.0)
    b = jnp.where(real, b.astype(f32), 0.0)

    def chunks(x):  # [B, N * C, H, ...] -> [N, B, H, C, ...]
        x = x.astype(f32).reshape(B, N, C, H, *x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, g, b = map(chunks, (q, k, v, g, b))
    G = jnp.cumsum(g, axis=-2)                             # [N, B, H, C, d]
    lower = jnp.tril(jnp.ones((C, C), bool))
    strict = jnp.tril(jnp.ones((C, C), bool), -1)

    def pairs(xs):
        """A, B of one chunk: the [C, C, d] factor is this chunk's."""
        q_c, k_c, G_c, b_c = xs
        between = jnp.exp(jnp.where(
            lower[..., None], G_c[..., :, None, :] - G_c[..., None, :, :],
            -jnp.inf)) * k_c[..., None, :, :]              # [B, H, C, C, d]
        a = jnp.sum(k_c[..., :, None, :] * between, axis=-1)
        return (jnp.where(strict, a * b_c[..., None], 0.0),
                jnp.sum(q_c[..., :, None, :] * between, axis=-1))

    A, Bm = jax.lax.map(pairs, (q, k, G, b))               # [N, B, H, C, C]
    X = _inverse_unit_lower(A)
    decay = jnp.exp(G)
    W = _matmul(X, b[..., None] * k * decay)               # [N, B, H, C, dk]
    U0 = _matmul(X, b[..., None] * v)                      # [N, B, H, C, dv]
    to_end = jnp.exp(G[..., -1:, :] - G)                   # exp(G_C - G_j)

    def one_chunk(S, xs):
        q_d, k_e, W_c, U0_c, B_c, last = xs
        U = U0_c - _matmul(W_c, S)
        out = _matmul(q_d, S) + _matmul(B_c, U)
        S = last[..., None] * S + _matmul(jnp.swapaxes(k_e, -1, -2), U)
        return S, out

    state, out = jax.lax.scan(
        one_chunk, state,
        (q * decay, k * to_end, W, U0, Bm, decay[..., -1, :]))
    out = jnp.moveaxis(jnp.moveaxis(out, 0, 1), 2, 3)      # [B, N, C, H, dv]
    return out.reshape(B, N * C, H, -1)[:, :T], state
