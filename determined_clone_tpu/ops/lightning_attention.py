"""Lightning (linear) attention with a constant decay per head.

Per head, with a decay ``lam`` in (0, 1] and a state ``S`` of ``[d, d]``
(``S_0 = 0``):

    S_t = lam * S_{t-1} + k_t^T v_t          o_t = d ** -0.5 * q_t S_t

so a sequence's memory is one state that does not grow. Two forms of the
same recurrence, both taking the state in and giving it back, both over
*real* tokens only (``token_mask``): a masked position neither decays the
state nor adds to it, so a padded bucket leaves the state where its last
real token left it.

- ``T == 1``, a decode step: the recurrence itself, elementwise in fp32.
- ``T > 1``, a prefill slice: chunks of ``chunk`` tokens. With ``a_i`` the
  number of real tokens up to and including position ``i`` of the chunk
  and ``m_j`` the mask,

      O  = d ** -0.5 * ((Q K^T * D) V + (Q * lam ** a_i) S)
      D_ij = lam ** (a_i - a_j) * m_j   for j <= i, else 0
      S' = lam ** a_C * S + sum_j lam ** (a_C - a_j) * m_j * k_j^T v_j

  Every power is of a non-negative exponent, formed as a difference in the
  exponent and not as a quotient of powers, so the smallest decays
  underflow to 0 and nothing overflows.

The state, the decay weights and every sum are fp32; the products' operands
are the inputs' dtype (bf16 on the serving path), the state rounded to it
for the read-out product of a slice.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp


def lightning_decay(n_heads: int, layer: int, n_layers: int) -> jax.Array:
    """``lam`` [H] fp32 of published layer ``layer`` of ``n_layers``, by
    the Lightning Attention convention (MiniMax-Text-01's): ``slope_h =
    2 ** (-8 (h + 1) / H)``, ``lam = exp(-slope_h * (1 - layer / (n_layers
    - 1) + 1e-5))``. Any table in (0, 1] serves :func:`lightning_attention`.
    """
    slope = 2.0 ** (-8.0 * jnp.arange(1, n_heads + 1, dtype=jnp.float32)
                    / n_heads)
    return jnp.exp(-slope * (1.0 - layer / max(n_layers - 1, 1) + 1e-5))


def _step(q, k, v, state, log_lam, token_mask):
    f32 = jnp.float32
    q, k, v = (x[:, 0].astype(f32) for x in (q, k, v))           # [B, H, d]
    lam = jnp.exp(log_lam)[None, :, None, None]
    new = lam * state + k[..., :, None] * v[..., None, :]
    state = jnp.where(token_mask[:, 0, None, None, None], new, state)
    out = jnp.sum(q[..., :, None] * state, axis=-2) * q.shape[-1] ** -0.5
    return out[:, None], state


def lightning_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        state: jax.Array, decay: jax.Array,
                        token_mask: jax.Array, *, chunk: int = 256
                        ) -> Tuple[jax.Array, jax.Array]:
    """q, k, v: [B, T, H, d]; state: [B, H, d, d] fp32, the state before
    the first token; decay: [H] fp32 in (0, 1]; token_mask: [B, T] bool.
    Returns ``(o [B, T, H, d] fp32, state' [B, H, d, d] fp32)``: the
    outputs at every position (those of masked positions mean nothing) and
    the state after the last real token."""
    B, T, H, d = q.shape
    log_lam = jnp.log(decay.astype(jnp.float32))
    if T == 1:
        return _step(q, k, v, state, log_lam, token_mask)
    C = math.gcd(T, chunk)
    scale = d ** -0.5
    dt = q.dtype

    def chunks(x):  # [B, T, H, d] -> [T / C, B, H, C, d]
        return x.reshape(B, T // C, C, H, d).transpose(1, 0, 3, 2, 4)

    causal = jnp.tril(jnp.ones((C, C), bool))
    steps = jnp.moveaxis(token_mask.reshape(B, T // C, C), 1, 0)

    def one_chunk(state, xs):
        qc, kc, vc, mc = xs                     # [B, H, C, d], [B, C]
        a = jnp.cumsum(mc.astype(jnp.float32), axis=1)[:, None]  # [B, 1, C]
        lam = log_lam[None, :, None]                             # [1, H, 1]
        # lam ** (a_i - a_j) m_j under the causal mask: [B, H, C, C]
        weight = jnp.where(
            causal & mc[:, None, None, :],
            jnp.exp(lam[..., None] * (a[..., :, None] - a[..., None, :])),
            0.0)
        scores = jnp.einsum("bhid,bhjd->bhij", qc, kc,
                            preferred_element_type=jnp.float32)
        intra = jnp.einsum("bhij,bhjd->bhid", (scores * weight).astype(dt),
                           vc, preferred_element_type=jnp.float32)
        # what the state before the chunk gives: (q_i lam ** a_i) S
        inter = jnp.einsum("bhid,bhde->bhie", qc, state.astype(dt),
                           preferred_element_type=jnp.float32
                           ) * jnp.exp(lam * a)[..., None]
        # the state after it: keys weighted by lam ** (a_C - a_j) m_j
        k_decay = jnp.where(mc[:, None, :],
                            jnp.exp(lam * (a[..., -1:] - a)), 0.0)
        added = jnp.einsum(
            "bhjd,bhje->bhde", (kc.astype(jnp.float32)
                                * k_decay[..., None]).astype(dt), vc,
            preferred_element_type=jnp.float32)
        state = jnp.exp(lam * a[..., -1:])[..., None] * state + added
        return state, (intra + inter) * scale

    state, out = jax.lax.scan(
        one_chunk, state.astype(jnp.float32),
        (chunks(q), chunks(k), chunks(v), steps))
    return out.transpose(1, 0, 3, 2, 4).reshape(B, T, H, d), state
