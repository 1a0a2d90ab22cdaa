"""Attention ops, including the sequence-parallel paths the reference lacks.

Four implementations, one semantic:
 - ``mha``: plain XLA attention (einsum + softmax). XLA fuses this well on
   TPU; correct reference implementation for tests.
 - ``decode_attention_rows``: ``mha`` for one query position over a
   context kept as rows of all heads side by side (the paged KV pool's
   layout), as two matmuls that read the rows as they lie.
 - ``eva_attention`` / ``eva_chunk_summary``: EVA's joint softmax over
   exact rows and chunk summaries, and the pooling that makes a summary,
   both over the same row-major context.
 - ``causal_blockwise_attention``: lax.scan over key/value blocks with a
   streaming (online-softmax) accumulator — the memory-efficient form that
   long sequences need; the basis for ring attention.
 - ``ring_attention``: context-parallel attention over the mesh's ``sp``
   axis: each shard holds a sequence slice, K/V blocks rotate around the
   ring via ppermute while compute overlaps (SURVEY.md §5.7 — absent in the
   reference, first-class here).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _causal_mask(q_len: int, k_len: int, q_offset: int = 0, k_offset: int = 0):
    """[q_len, k_len] bool mask; True = attendable."""
    q_pos = q_offset + jnp.arange(q_len)[:, None]
    k_pos = k_offset + jnp.arange(k_len)[None, :]
    return q_pos >= k_pos


def mha(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
        mask: Optional[jax.Array] = None) -> jax.Array:
    """Multi-head attention. q,k,v: [B, T, H, D]. Softmax in fp32."""
    d = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(d))
    if causal:
        cm = _causal_mask(q.shape[1], k.shape[1])
        scores = jnp.where(cm[None, None], scores, NEG_INF)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _query_over_rows(q: jax.Array, R: int):
    """q [B, 1, H, D] spread block-diagonally over rows of width R:
    ``own`` [H, R] (column r of a row belongs to head h) and ``q_diag``
    [B, H, R], head h's query in its own columns and zero elsewhere."""
    B, _, H, D = q.shape
    own = jnp.arange(R)[None, :] // D == jnp.arange(H)[:, None]
    q_rows = jnp.pad(q.reshape(B, 1, H * D), ((0, 0), (0, 0), (0, R - H * D)))
    return own, jnp.where(own[None], q_rows, 0)


def decode_attention_rows(q: jax.Array, k: jax.Array, v: jax.Array,
                          mask: jax.Array) -> jax.Array:
    """``mha`` for a single query position over row-major context.

    q: [B, 1, H, D]; k, v: [B, S, R] — position s's K (V) for all heads
    side by side in columns ``0..H*D``, any further columns ignored;
    mask: [B, 1, 1, S]. Returns [B, 1, H, D], the same numbers as
    ``mha(q, k[..., :H*D].reshape(B, S, H, D), ..., causal=False,
    mask=mask)`` (bf16 products summed in fp32, fp32 softmax).

    ``mha``'s per-head contraction over D = 64 makes XLA on TPU transpose
    the whole context (S into the lanes) before it multiplies. Here q is
    spread block-diagonally over [R, H], so the scores are one
    [H, R] x [R, S] matmul per row that reads k as it lies, and the
    output is [H, S] x [S, R] with each head's own columns picked out
    afterwards. The zeros cost (H - 1) / H of the MXU's work, which at
    one query position is far below the time to read the context.
    """
    B, _, H, D = q.shape
    own, q_diag = _query_over_rows(q, k.shape[-1])
    scores = jnp.einsum("bhr,bsr->bhs", q_diag, k,
                        preferred_element_type=jnp.float32)
    scores = scores.astype(q.dtype).astype(jnp.float32)  # as mha rounds them
    scores = scores / jnp.sqrt(jnp.float32(D))
    scores = jnp.where(mask[:, 0], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)         # [B, H, S]
    out = jnp.einsum("bhs,bsr->bhr", probs, v,
                     preferred_element_type=jnp.float32)
    out = jnp.sum(jnp.where(own[None], out, 0.0), axis=1)           # [B, R]
    return out[:, :H * D].astype(q.dtype).reshape(B, 1, H, D)


def eva_chunk_summary(k: jax.Array, v: jax.Array, phi: jax.Array,
                      mu: jax.Array) -> tuple:
    """EVA's summary of whole chunks of rotated keys and their values.

    k, v: [..., C, R] — the C positions of a chunk as pool rows, all heads
    side by side in columns ``0..H*D``; phi, mu: [H, D], learned per head.
    Per head: ``a_j = softmax_j(D**-0.5 * k_j . phi)`` over the chunk's C
    positions, ``K_c = sum_j a_j k_j + mu``, ``V_c = sum_j a_j v_j``.
    Computed in fp32; returns (K_c, V_c) as rows [..., R] in k's dtype,
    columns past ``H*D`` zero.
    """
    H, D = phi.shape
    R = k.shape[-1]
    lead = k.shape[:-2]
    kh = k[..., :H * D].astype(jnp.float32).reshape(*lead, -1, H, D)
    vh = v[..., :H * D].astype(jnp.float32).reshape(*lead, -1, H, D)
    scores = jnp.einsum("...chd,hd->...ch", kh, phi.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST) * D ** -0.5
    a = jax.nn.softmax(scores, axis=-2)[..., None]            # over C
    k_sum = jnp.sum(a * kh, axis=-3) + mu.astype(jnp.float32)
    v_sum = jnp.sum(a * vh, axis=-3)
    pad = [(0, 0)] * len(lead) + [(0, R - H * D)]

    def rows(x):
        return jnp.pad(x.reshape(*lead, H * D).astype(k.dtype), pad)

    return rows(k_sum), rows(v_sum)


def eva_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  mask: jax.Array, *, q_block: int = 256) -> jax.Array:
    """EVA's one softmax over exact rows and chunk summaries.

    q: [B, T, H, D]; k, v: [B, S, R] — the gathered context as rows, all
    heads side by side: a sequence's window rows, then its summary rows
    (``eva_chunk_summary``), in any order the mask knows; mask: [B, T, S],
    True where query t may see slot s. Returns [B, T, H, D] in q's dtype:
    ``softmax_s(D**-0.5 * q_t . k_s) v_s`` over the admitted slots, scores
    and softmax in fp32, the probabilities rounded to q's dtype for the
    product with v (fp32 sums).

    ``T == 1`` (a decode step whose shapes ``ops/eva_paged_attention.py``
    cannot take, and that kernel's oracle in the tests) reads the rows as
    they lie, as ``decode_attention_rows`` does. ``T > 1`` (a prefill slice) runs row
    by row and ``q_block`` queries at a time, so that the fp32 scores
    ``[H, q_block, S]`` are what is live, not ``[B, H, T, S]``.
    """
    B, T, H, D = q.shape
    S, R = k.shape[-2:]
    scale = D ** -0.5
    if T == 1:
        own, q_diag = _query_over_rows(q, R)
        scores = jnp.einsum("bhr,bsr->bhs", q_diag, k,
                            preferred_element_type=jnp.float32) * scale
        # kept as they are computed: left to itself the TPU compiler
        # recomputes the product, and reads k again, for the softmax's
        # second pass (4.5 of a 28 ms step at 8 x 24576 x 4096)
        scores = jax.lax.optimization_barrier(scores)
        scores = jnp.where(mask, scores, NEG_INF)       # [B, 1, S] -> heads
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhs,bsr->bhr", probs, v,
                         preferred_element_type=jnp.float32)
        out = jnp.sum(jnp.where(own[None], out, 0.0), axis=1)       # [B, R]
        return out[:, :H * D].astype(q.dtype).reshape(B, 1, H, D)

    tq = min(T, q_block)
    if T % tq:
        raise ValueError(f"q_block {tq} must divide the slice length {T}")

    def one_row(row):
        q_r, k_r, v_r, m_r = row
        kh = k_r[:, :H * D].reshape(S, H, D).transpose(1, 0, 2)  # [H, S, D]
        vh = v_r[:, :H * D].reshape(S, H, D).transpose(1, 0, 2)
        qh = q_r.reshape(T // tq, tq, H, D).transpose(0, 2, 1, 3)

        def one_block(blk):
            qb, mb = blk                               # [H, tq, D], [tq, S]
            s = jnp.einsum("hqd,hkd->hqk", qb, kh,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(mb[None], s, NEG_INF)
            p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
            return jnp.einsum("hqk,hkd->hqd", p, vh,
                              preferred_element_type=jnp.float32
                              ).astype(q.dtype)

        out = jax.lax.map(one_block, (qh, m_r.reshape(T // tq, tq, S)))
        return out.transpose(0, 2, 1, 3).reshape(T, H, D)

    return jax.lax.map(one_row, (q, k, v, mask))


def _online_softmax_block(carry, qkv_block, *, scale):
    """One streaming-softmax step: merge a new K/V block into (acc, m, l).

    acc: running unnormalized output [B, Tq, H, D] (fp32)
    m:   running row max           [B, H, Tq]     (fp32)
    l:   running row denominator   [B, H, Tq]     (fp32)
    """
    acc, m, l = carry
    q, k_blk, v_blk, block_mask = qkv_block
    # f32 accumulation ON the dot (the MXU's native bf16-in/f32-out mode),
    # not a bf16 dot cast afterwards: under jit, XLA fuses the cast into
    # the scan backward in a way that overflows bf16 intermediates
    # (non-finite dq/dk on real TPU); preferred_element_type sidesteps the
    # bf16 intermediate entirely and is faster
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(block_mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # guard fully-masked rows: exp(NEG_INF - NEG_INF) would be 1
    alpha = jnp.exp(jnp.where(m > NEG_INF / 2, m - m_new, NEG_INF))
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(block_mask, p, 0.0)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    acc_new = acc * alpha.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p, v_blk.astype(jnp.float32)
    )
    return (acc_new, m_new, l_new)


def causal_blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                               block_size: int = 512,
                               causal: bool = True) -> jax.Array:
    """Streaming attention over K/V blocks via lax.scan; O(T·block) memory
    instead of O(T²). Matches ``mha`` numerically (fp32 softmax); pass
    causal=False for the unmasked variant (same streaming memory)."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    block_size = min(block_size, Tk)
    if Tk % block_size != 0:
        raise ValueError(
            f"block_size {block_size} must evenly divide the K/V sequence length {Tk}"
        )
    n_blocks = Tk // block_size
    scale = 1.0 / (D ** 0.5)

    k_blocks = k.reshape(B, n_blocks, block_size, H, D).transpose(1, 0, 2, 3, 4)
    v_blocks = v.reshape(B, n_blocks, block_size, H, D).transpose(1, 0, 2, 3, 4)

    def step(carry, inputs):
        idx, k_blk, v_blk = inputs
        if causal:
            bmask = _causal_mask(Tq, block_size, q_offset=0,
                                 k_offset=idx * block_size)
        else:
            bmask = jnp.ones((Tq, block_size), bool)
        carry = _online_softmax_block(
            carry, (q, k_blk, v_blk, bmask[None, None]), scale=scale
        )
        return carry, None

    acc0 = jnp.zeros((B, Tq, H, D), jnp.float32)
    m0 = jnp.full((B, H, Tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Tq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        step, (acc0, m0, l0), (jnp.arange(n_blocks), k_blocks, v_blocks)
    )
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *, axis_name: str,
                   axis_index: jax.Array, axis_size: int) -> jax.Array:
    """Causal ring attention inside shard_map: the sequence axis is sharded
    over ``axis_name``; K/V shards rotate via ppermute so every query shard
    sees the full sequence with only neighbor ICI traffic.

    q,k,v: [B, T_local, H, D] — the local sequence slice. Global positions of
    this shard's queries are axis_index*T_local + [0, T_local).
    """
    B, T, H, D = q.shape
    scale = 1.0 / (D ** 0.5)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def body(i, state):
        acc, m, l, k_cur, v_cur = state
        # K/V currently held arrived from shard (axis_index - i) mod size.
        src = (axis_index - i) % axis_size
        bmask = _causal_mask(T, T, q_offset=axis_index * T, k_offset=src * T)
        acc, m, l = _online_softmax_block(
            (acc, m, l), (q, k_cur, v_cur, bmask[None, None]), scale=scale
        )
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (acc, m, l, k_nxt, v_nxt)

    acc0 = jnp.zeros((B, T, H, D), jnp.float32)
    m0 = jnp.full((B, H, T), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, T), jnp.float32)
    # the zero-init carry is a replicated constant but every loop output
    # varies over the sp axis — mark it varying or shard_map's vma check
    # rejects the fori_loop carry
    acc0, m0, l0 = jax.tree.map(
        lambda x: jax.lax.pcast(x, axis_name, to="varying"),
        (acc0, m0, l0))
    acc, m, l, _, _ = jax.lax.fori_loop(
        0, axis_size, body, (acc0, m0, l0, k, v)
    )
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      axis_name: str, causal: bool = True) -> jax.Array:
    """All-to-all (DeepSpeed-Ulysses-style) sequence parallelism inside
    shard_map: the complement of ring_attention for long sequences.

    The sequence axis arrives sharded over ``axis_name``; all-to-alls
    reshard q/k/v to head-parallel layout ([B, T, H/sp, D] — every device
    holds the FULL sequence for a slice of heads), attention runs locally
    with zero communication, and a final all-to-all reshards the output
    back. Four all-to-alls total (the standard Ulysses accounting) versus
    the ring's axis_size ppermute hops per K/V tensor — the better trade
    when the head count divides the axis and the full sequence fits per
    device.

    q,k,v: [B, T_local, H, D]; H must be divisible by the axis size.
    """

    sp = jax.lax.axis_size(axis_name)
    if q.shape[2] % sp != 0:
        raise ValueError(
            f"ulysses_attention requires the head count ({q.shape[2]}) to "
            f"be divisible by the '{axis_name}' axis size ({sp}); use "
            f"ring_attention for indivisible head counts")

    def a2a(x, scatter_dim, concat_dim):
        return jax.lax.all_to_all(x, axis_name, split_axis=scatter_dim,
                                  concat_axis=concat_dim, tiled=True)

    # [B, T/sp, H, D] -> [B, T, H/sp, D]: scatter heads, gather sequence
    qh = a2a(q, 2, 1)
    kh = a2a(k, 2, 1)
    vh = a2a(v, 2, 1)
    out = mha(qh, kh, vh, causal=causal)
    # [B, T, H/sp, D] -> [B, T/sp, H, D]
    return a2a(out, 1, 2)


def rotary_embedding(x: jax.Array, positions: jax.Array, *,
                     base: float = 10000.0) -> jax.Array:
    """RoPE. x: [B, T, H, D] (D even), positions: [T] or [B, T]."""
    D = x.shape[-1]
    half = D // 2
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, T, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).astype(x.dtype)
