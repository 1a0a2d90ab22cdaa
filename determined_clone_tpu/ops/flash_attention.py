"""Pallas TPU flash attention — the fused hot-path kernel.

The reference has no custom kernels (SURVEY.md: no CUDA anywhere; attention
lives inside torch). On TPU the idiomatic equivalent is a Pallas kernel that
keeps the O(T²) score matrix out of HBM AND out of VMEM: the grid is
(batch·head, q_block, k_block) with k innermost, so only one
[block_q, D] q tile and one [block_k, D] k/v tile are resident per step
while the online-softmax state (m, l, acc — the flash recurrence) lives in
VMEM scratch that persists across the k iterations. Memory is O(block²),
sequences bound only by HBM, and the MXU sees back-to-back
[block_q, D]×[D, block_k] matmuls.

Backward pass: custom VJP that recomputes attention with the XLA blockwise
path (ops/attention.py) — fwd gets the fused kernel + no residual scores,
bwd stays memory-efficient via rematerialization (jax.checkpoint-style).

Off-TPU (CPU tests) the kernel runs in Pallas interpret mode, so tests
exercise the same code path. On a TPU backend the compiled Mosaic kernel is
the only path unless a caller passes ``interpret=True`` explicitly: nothing
here selects interpret mode on a chip by itself.

XLA cannot partition a Mosaic kernel: under a multi-device mesh the caller
wraps the call in ``shard_map`` (models/gpt.py ``_flash`` does).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from determined_clone_tpu.ops.attention import causal_blockwise_attention

NEG_INF = -1e30


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                scale: float, causal: bool, block_q: int, block_k: int,
                n_kb: int):
    """Grid (BH, q_blocks, k_blocks), k innermost. Scratch (m/l/acc)
    persists across the k iterations of one (bh, qi)."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale      # [bq, D]
        k_blk = k_ref[0].astype(jnp.float32)          # [bk, D]
        v_blk = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(                      # [bq, bk] on the MXU
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if causal:
            q_pos = (qi * block_q +
                     jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
            k_pos = (ki * block_k +
                     jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_ref[:, 0]                          # [bq]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        # fully-masked-so-far rows: exp(NEG_INF - NEG_INF) must not be 1
        alpha = jnp.exp(jnp.where(m_prev > NEG_INF / 2,
                                  m_prev - m_new, NEG_INF))
        p = jnp.exp(s - m_new[:, None])
        if causal:
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_ref[:] = acc_ref[:] * alpha[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new[:, None]
        l_ref[:] = l_new[:, None]

    if causal:
        # skip K blocks strictly above this q block's last row
        pl.when((qi * block_q + block_q - 1) >= ki * block_k)(_compute)
    else:
        _compute()

    @pl.when(ki == n_kb - 1)
    def _finalize():
        o_ref[0] = (acc_ref[:] /
                    jnp.maximum(l_ref[:, 0], 1e-30)[:, None]).astype(
                        o_ref.dtype)


def _flash_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
               block_q: int, block_k: int,
               interpret: Optional[bool]) -> jax.Array:
    """q,k,v: [B, T, H, D] (the mha layout); returns [B, Tq, H, D]."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = 1.0 / (D ** 0.5)
    if interpret is None:
        interpret = _should_interpret()
    n_kb = Tk // block_k

    # [B, T, H, D] -> [B*H, T, D]: one grid row per (batch·head)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Tq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Tk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Tk, D)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, n_kb=n_kb,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B * H, Tq // block_q, n_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # m (row max)
            pltpu.VMEM((block_q, 1), jnp.float32),   # l (row denominator)
            pltpu.VMEM((block_q, D), jnp.float32),   # acc (unnormalized out)
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qf, kf, vf)
    return out.reshape(B, H, Tq, D).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention_cvjp(q, k, v, causal, block_q, block_k, interpret):
    return _flash_fwd(q, k, v, causal=causal, block_q=block_q,
                      block_k=block_k, interpret=interpret)


def _vjp_fwd(q, k, v, causal, block_q, block_k, interpret):
    out = _flash_fwd(q, k, v, causal=causal, block_q=block_q,
                     block_k=block_k, interpret=interpret)
    return out, (q, k, v)


def _vjp_bwd(causal, block_q, block_k, interpret, residuals, g):
    q, k, v = residuals
    # rematerialize with the XLA blockwise path: same math (online softmax
    # in fp32), O(T·block) memory — causal or not — and XLA differentiates
    # the scan cleanly
    ref = functools.partial(causal_blockwise_attention, block_size=block_k,
                            causal=causal)
    _, pullback = jax.vjp(ref, q, k, v)
    return pullback(g)


_flash_attention_cvjp.defvjp(_vjp_fwd, _vjp_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Fused attention. q,k,v: [B, T, H, D]; matches ``mha`` numerically
    (fp32 softmax). Block sizes clamp to the sequence lengths, which must
    then divide evenly (static shapes; the grid can't tile ragged tails)."""
    block_q = min(block_q, q.shape[1])
    block_k = min(block_k, k.shape[1])
    if q.shape[1] % block_q != 0:
        raise ValueError(
            f"q length {q.shape[1]} not divisible by block_q {block_q}")
    if k.shape[1] % block_k != 0:
        raise ValueError(
            f"k length {k.shape[1]} not divisible by block_k {block_k}")
    return _flash_attention_cvjp(q, k, v, causal, block_q, block_k,
                                 interpret)
