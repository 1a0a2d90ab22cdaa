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

  in one Pallas TPU kernel, ``kda_chunks`` (compiled on the chip,
  interpreted off it, as ``ops/flash_attention.py``'s are): a grid step is
  one chunk of ``heads_a_step`` heads, every value ``[heads, ...]`` and
  every product one batched product over them, so the program is as long
  for eight heads as for one and the heads' independent chains of small
  products share the matrix unit. XLA keeps only ``G`` (the running sum)
  before the call; nothing the size of a chunk's pairs reaches HBM.

  1. *The pairs by sub-chunks of* ``SUB`` *= 16.* A sub-chunk ``I``
     against an earlier one goes through ``G_ref``, the running sum at the
     last row before ``I``: ``(k_I exp(G_I - G_ref)) (k_j exp(G_ref -
     G_j))^T``, one ``[2 SUB, d] x [d, C]`` product a sub-chunk for ``A``
     and ``B`` together. The diagonal ``SUB x SUB`` blocks are formed
     exactly, per channel from the difference ``G_i - G_j``, a head and
     sub-chunk at a time with its rows held in registers: ``SUB`` turns,
     turn ``s`` every position against the one ``s`` before it (the rows
     rolled down by one a turn), a lane reduction a turn. (The same turns
     over all heads' values at once go through fast memory for every
     operand and are bound by that; a loop over turns that is not unrolled
     is bound by a turn's latency: ``tools/kda_sweep.py``, PERF.md.)
     **Every exponent is a non-positive difference** (``G`` only falls),
     so the smallest decays underflow to 0 and nothing overflows; a factor
     through ``G_ref`` underflows only where the true decay is under
     1e-38.
  2. *``X = (I + A)^-1`` by blocks:* the inverses of the diagonal blocks
     of ``m`` rows give those of ``2 m`` rows by two products (``X_21 =
     -X_22 A_21 X_11``, every pair of blocks at once under a mask), from
     ``m = 2`` (``I - A``) to the chunk: block forward substitution, no
     loop over rows.
  3. *The walk over a slice's chunks with the state resident:* the chunk
     is the innermost, sequential grid axis, and the heads' states (held
     transposed, ``[d_v, d_k]``, so a decay a key channel scales columns)
     stay in the output's block in fast memory from the slice's first
     chunk to its last: ``U = X b (V - (K exp G) S)`` (the state is at
     hand, so the solve is applied once, to the corrected values), ``O =
     (Q exp G) S + B U``, ``S' = exp(G_C) S + (K exp(G_C - G))^T U``.

  The lightning chunk form (``lam ** (a_i - a_j)``, one scalar a head)
  does not carry over: the decay is a vector and the delta rule couples a
  chunk's writes.

Everything here is fp32: the state, the decays, the solve, and the
products (``Precision.HIGHEST``: a read of the state feeds the next write,
so a rounded read is a rounded state).

:func:`short_conv` is the causal depthwise convolution before it, with the
last ``K - 1`` rows carried between calls beside the state.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from determined_clone_tpu.ops.flash_attention import _should_interpret

CHUNK = 64
SUB = 16           # a sub-chunk: the pairs within one are formed exactly
HEADS_A_STEP = 8
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


def heads_a_step(heads: int) -> int:
    """Heads a grid step takes: the most that divide ``heads``, up to
    ``HEADS_A_STEP`` (independent chains of small products keep the matrix
    unit fed; ``tools/kda_sweep.py`` holds the sweep)."""
    return max(n for n in range(1, min(heads, HEADS_A_STEP) + 1)
               if heads % n == 0)


# batched products over a leading axis of heads: A·Bᵀ, A·B and Aᵀ·B
_NT = (((2,), (2,)), ((0,), (0,)))
_NN = (((2,), (1,)), ((0,), (0,)))
_TN = (((1,), (1,)), ((0,), (0,)))


def _dot(a: jax.Array, b: jax.Array, dims) -> jax.Array:
    return jax.lax.dot_general(a, b, dims, precision=_HI,
                               preferred_element_type=jnp.float32)


def _kernel(q_ref, k_ref, v_ref, G_ref, b_ref, s0_ref, o_ref, s_ref,
            q_rows, k_rows, G_rows, A_rows, B_rows, *,
            heads: int, d: int, C: int):
    """One chunk of ``heads`` heads, every value ``[heads, ...]`` so that a
    product is one batched operation whatever the group: ``q_ref`` ..
    ``G_ref`` and ``o_ref`` [1, C, heads * d] (a head its own columns),
    ``b_ref`` [1, 1, C, heads], ``s0_ref`` / ``s_ref`` [1, heads, d_v, d_k]
    the states *transposed*, so a decay a key channel scales columns;
    ``s_ref`` stays in fast memory over the slice's chunks, the innermost
    grid axis. Scratch: ``q_rows``, ``k_rows``, ``G_rows`` [heads, C, d] the
    inputs a head its own rows, ``A_rows``, ``B_rows`` [heads, C, C] the
    diagonal sub-blocks."""
    f32 = jnp.float32
    n_sub = C // SUB
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    sub_first = row // SUB * SUB      # the first column of a row's sub-chunk

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    for h in range(heads):       # a head's columns as its own [C, d] rows
        for ref, rows in zip((q_ref, k_ref, G_ref), (q_rows, k_rows, G_rows)):
            rows[h] = ref[0, :, h * d:(h + 1) * d]

    at = jax.lax.broadcasted_iota(jnp.int32, (SUB, C), 1) \
        - jax.lax.broadcasted_iota(jnp.int32, (SUB, C), 0)
    turn = jax.lax.broadcasted_iota(jnp.int32, (SUB, C), 0)

    def exact(n, _):
        """The diagonal sub-block of ``A / b`` and of ``B`` of one head and
        sub-chunk, exactly, all of it in registers: turn ``s`` every
        position against the one ``s`` before it (the rows rolled down by
        one a turn), the decay between the two from their difference
        ``G_i - G_{i-s}``, a lane reduction a turn."""
        h, first = n // n_sub, pl.multiple_of(n % n_sub * SUB, SUB)
        mine = pl.ds(first, SUB)
        q, k, G = q_rows[h, mine], k_rows[h, mine], G_rows[h, mine]
        A = Bm = jnp.zeros((SUB, C), f32)
        k_s, G_s = k, G                      # k_s[i] = k[i - s], G_s alike
        for s in range(SUB):
            with_s = jnp.exp(jnp.minimum(G - G_s, 0.0)) * k_s
            here = (at == first - s) & (turn >= s)
            if s:
                A = jnp.where(here, jnp.sum(k * with_s, axis=1,
                                            keepdims=True), A)
            Bm = jnp.where(here, jnp.sum(q * with_s, axis=1, keepdims=True),
                           Bm)
            k_s, G_s = pltpu.roll(k_s, 1, 0), pltpu.roll(G_s, 1, 0)
        A_rows[h, mine], B_rows[h, mine] = A, Bm

    jax.lax.fori_loop(0, heads * n_sub, exact, None)
    q, k, G = q_rows[...], k_rows[...], G_rows[...]        # [heads, C, d]
    v = jnp.stack([v_ref[0, :, h * d:(h + 1) * d] for h in range(heads)])
    b = jnp.stack([b_ref[0, 0, :, h:h + 1] for h in range(heads)])
    A, Bm = A_rows[...], B_rows[...]

    # the sub-blocks under the diagonal, as products: sub-chunk ``i``
    # against every earlier position through ``G_ref``, the running sum at
    # the last row before ``i``; both exponents are non-positive
    under = [jnp.zeros((heads, 2 * SUB, C), f32)]
    for i in range(1, n_sub):
        mine = slice(i * SUB, (i + 1) * SUB)
        ref = G[:, i * SUB - 1:i * SUB]
        since = jnp.exp(G[:, mine] - ref)
        until = k * jnp.exp(jnp.minimum(ref - G, 0.0))
        under.append(_dot(
            jnp.concatenate([k[:, mine] * since, q[:, mine] * since],
                            axis=1), until, _NT))
    earlier = col < sub_first
    A, Bm = (m + jnp.where(earlier, jnp.concatenate(
        [u[:, x * SUB:(x + 1) * SUB] for u in under], axis=1), 0.0)
        for x, m in enumerate((A, Bm)))

    # ``(I + A)^-1``, ``A`` strictly lower: the inverses of the diagonal
    # blocks of ``m`` rows give those of ``2 m`` by two products, ``X_21 =
    # -X_22 A_21 X_11`` for every pair at once
    A = b * A
    X, m = jnp.where(row == col, 1.0, 0.0) \
        - jnp.where(row // 2 == col // 2, A, 0.0), 2
    while m < C:
        pair = (row // (2 * m) == col // (2 * m)) & (row // m != col // m)
        X, m = X - _dot(X, _dot(jnp.where(pair, A, 0.0), X, _NN), _NN), 2 * m

    decay = jnp.exp(G)
    S = s_ref[0]                                      # [heads, d_v, d_k]
    U = _dot(X, b * (v - _dot(k * decay, S, _NT)), _NN)
    out = _dot(q * decay, S, _NT) + _dot(Bm, U, _NN)
    for h in range(heads):
        o_ref[0, :, h * d:(h + 1) * d] = out[h]
    last = G[:, C - 1:]
    s_ref[0] = jnp.exp(last) * S + _dot(U, k * jnp.exp(last - G), _TN)


@functools.partial(jax.jit, static_argnames=("C", "heads"))
def _chunked(q, k, v, g, b, state, C: int, heads: Optional[int] = None):
    """The slice form over whole chunks: q, k, v, g [B, N * C, H, d] fp32
    (``g`` and ``b`` [B, N * C, H] zero at masked positions), state [B, H,
    d_k, d_v]. ``heads`` a grid step, ``heads_a_step``'s unless given."""
    B, T, H, d = q.shape
    heads = heads or heads_a_step(H)
    N, f32 = T // C, jnp.float32
    G = jnp.cumsum(g.reshape(B, N, C, H * d), axis=2).reshape(B, T, H * d)
    q, k, v = (x.reshape(B, T, H * d) for x in (q, k, v))
    b = jnp.swapaxes(b.reshape(B, T, H // heads, heads), 1, 2)
    wide = pl.BlockSpec((1, C, heads * d), lambda i, j, n: (i, n, j))
    held = pl.BlockSpec((1, heads, d, d), lambda i, j, n: (i, j, 0, 0))
    ops = 2 * (5 * C * C * d + 3 * C * d * d) * N * H * B
    out, state = pl.pallas_call(
        functools.partial(_kernel, heads=heads, d=d, C=C),
        grid=(B, H // heads, N),
        in_specs=[wide, wide, wide, wide,
                  pl.BlockSpec((1, 1, C, heads), lambda i, j, n: (i, j, n, 0)),
                  held],
        out_specs=[wide, held],
        out_shape=[jax.ShapeDtypeStruct((B, T, H * d), f32),
                   jax.ShapeDtypeStruct((B, H, d, d), f32)],
        scratch_shapes=[pltpu.VMEM((heads, C, d), f32)] * 3
        + [pltpu.VMEM((heads, C, C), f32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=ops, transcendentals=(SUB + 6) * C * d * N * H * B,
            bytes_accessed=4 * (5 * B * T * H * d + 2 * B * H * d * d)),
        interpret=_should_interpret(),
        name="kda_chunks",
    )(q, k, v, G, b, jnp.swapaxes(state, -1, -2))
    return out.reshape(B, T, H, d), jnp.swapaxes(state, -1, -2)


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
    T = q.shape[1]
    f32 = jnp.float32
    state = state.astype(f32)
    if T == 1:
        return _step(q, k, v, g, b, state, token_mask)
    real = token_mask[:, :, None]
    g = jnp.where(real[..., None], g.astype(f32), 0.0)
    b = jnp.where(real, b.astype(f32), 0.0)
    pad = ((0, 0), (0, -T % chunk), (0, 0), (0, 0))
    q, k, v, g = (jnp.pad(x.astype(f32), pad) for x in (q, k, v, g))
    out, state = _chunked(q, k, v, g, jnp.pad(b, pad[:3]), state, chunk)
    return out[:, :T], state
