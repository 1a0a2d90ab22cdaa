"""Multi-head latent attention (MLA) over a paged cache of latents, in the
absorbed form.

The cache holds, a position and layer, one row ``[c (rank) | k_R (rope) |
0]``: the normed key/value latent all heads share, the rotary key they
share, and zeros up to a whole number of the TPU's 128 lanes (576 -> 640
at the published sizes), so that a donated pool is updated in place
(``serving/kv_cache.py:init_kv_pools`` says why). Head ``i``'s key at
position ``s`` is ``[W_UK_i c_s | k_R_s]`` and its value ``W_UV_i c_s``;
neither is ever formed. With ``q~_i = W_UK_i^T q_N_i`` the score is ``q~_i
. c_s + q_R_i . k_R_s``, one product of the row with ``[q~_i | q_R_i |
0]`` (:func:`absorbed_query`), and the output ``W_UV_i sum_s p_s c_s``: the
sum is taken over whole rows and its first ``rank`` columns go through
``W_UV`` (:func:`expand_values`); the rows are read as they lie.

Three paths over the same rows:

- :func:`mla_decode` — one query a sequence that attends a chosen few: the
  rows of the chosen positions (at most ``index_topk``) are gathered by
  position through the block table, and nothing else of the table is read;
- :func:`mla_decode_dense` — one query a sequence that attends every
  cached position (no selection): a row's blocks are read through its
  table a chunk of blocks at a time, as far as the row's real length and
  no further, under an online softmax;
- :func:`mla_slice` — a prefill slice: a block of queries at a time over
  the sequence's blocks a chunk of positions at a time, as far as the
  block's last real query reaches, an online softmax under the mask of
  allowed positions (``ops/dsa_index.py``) or, with none, under the causal
  mask alone. The cost is the dense one (ROADMAP B-M keeps the gathered
  form).

Products take bfloat16 operands and sum in fp32; scores, softmax and the
running sums are fp32; the probabilities are rounded to the rows' dtype
for their product with the rows.
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def rope_interleaved(x: jax.Array, positions: jax.Array, *, base: float,
                     rotary: Optional[int] = None) -> jax.Array:
    """RoPE over interleaved pairs ``(x[2i], x[2i + 1])`` of the first
    ``rotary`` dimensions (all by default), angle ``pos * base^(-2i /
    rotary)``; the other dimensions pass (angle 0, so nothing is cut out
    of a lane tile). x: [B, T, ..., d] fp32, positions [B, T]."""
    d = x.shape[-1]
    rotary = d if rotary is None else rotary
    i = jnp.arange(d // 2, dtype=jnp.float32)
    freqs = jnp.where(i < rotary // 2, base ** (-i * 2.0 / rotary), 0.0)
    ang = positions.astype(jnp.float32)[..., None] * freqs   # [B, T, d/2]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[2:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _per_head(x: jax.Array, w: jax.Array) -> jax.Array:
    """x [B, T, H, a] through head h's own w [H, a, b]: [B, T, H, b] fp32.
    The product's result has the heads first, as the product makes it (the
    CPU backend multiplies bfloat16 in no other order)."""
    return jnp.moveaxis(jnp.einsum("btha,hac->hbtc", x, w,
                                   preferred_element_type=jnp.float32), 0, 2)


def absorbed_query(q_nope: jax.Array, q_rope: jax.Array, w_uk: jax.Array,
                   row_width: int, dtype: Any) -> jax.Array:
    """``[W_UK^T q_N | q_R | 0]`` [B, T, H, row_width] in ``dtype``, for
    q_nope [B, T, H, n], q_rope [B, T, H, r] (rotated) and w_uk [H, n,
    rank]."""
    q_lat = _per_head(q_nope.astype(w_uk.dtype), w_uk)
    pad = row_width - q_lat.shape[-1] - q_rope.shape[-1]
    return jnp.concatenate(
        [q_lat, q_rope.astype(jnp.float32),
         jnp.zeros(q_lat.shape[:-1] + (pad,), jnp.float32)],
        axis=-1).astype(dtype)


def expand_values(o_rows: jax.Array, w_uv: jax.Array) -> jax.Array:
    """``W_UV`` applied after the sum: o_rows [B, T, H, row_width] fp32
    (probability-weighted sums of whole rows) and w_uv [H, rank, v] ->
    [B, T, H, v] fp32."""
    rank = w_uv.shape[1]
    return _per_head(o_rows[..., :rank].astype(w_uv.dtype), w_uv)


def mla_decode(q: jax.Array, rows: jax.Array, row_ids: jax.Array,
               valid: jax.Array, *, scale: float) -> jax.Array:
    """q [B, 1, H, R] (:func:`absorbed_query`); ``rows`` [n, R] the pool as
    rows; ``row_ids`` [B, K] the rows of each query's chosen positions,
    ``valid`` [B, K] which of them exist. Returns the probability-weighted
    sums of the chosen rows, [B, 1, H, R] fp32; zeros where none is
    valid."""
    chosen = rows[jnp.where(valid, row_ids, 0)]               # [B, K, R]
    scores = jnp.einsum("bhr,bkr->bhk", q[:, 0], chosen,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(valid[:, None, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.where(valid[:, None, :], jnp.exp(scores - m), 0.0)
    out = jnp.einsum("bhk,bkr->bhr", p.astype(rows.dtype), chosen,
                     preferred_element_type=jnp.float32)
    return (out / jnp.maximum(jnp.sum(p, axis=-1), 1e-30)[..., None])[:, None]


def mla_decode_dense(q: jax.Array, blocks: jax.Array, tables: jax.Array,
                     lengths: jax.Array, *, scale: float,
                     key_blocks: int = 32) -> jax.Array:
    """q [B, 1, H, R] (:func:`absorbed_query`); ``blocks`` [n, block, R]
    the pool as blocks; ``tables`` [B, W] each sequence's blocks in order;
    ``lengths`` [B] how many cached positions each query attends, its own
    among them (0: none, the row is padding). Returns the
    probability-weighted sums of rows ``[0, length)``, [B, 1, H, R] fp32;
    zeros for a row of length 0. A row at a time, ``key_blocks`` blocks of
    positions a pass, ``ceil(length / (key_blocks * block))`` passes: what
    is read is the row's own blocks up to its length, not the table."""
    B, _, H, R = q.shape
    bs, W = blocks.shape[1], tables.shape[1]
    nb = min(key_blocks, W)
    S = nb * bs
    tables = jnp.pad(tables, ((0, 0), (0, -W % nb)))
    lengths = lengths.astype(jnp.int32)
    queries = q[:, 0]

    def one_row(r, out):
        q_r = jax.lax.dynamic_index_in_dim(queries, r, keepdims=False)
        table = jax.lax.dynamic_index_in_dim(tables, r, keepdims=False)
        n = lengths[r]

        def step(s, carry):
            m_run, l_run, acc = carry
            phys = jax.lax.dynamic_slice_in_dim(table, s * nb, nb)
            chunk = blocks[phys].reshape(S, R)
            scores = jnp.einsum("hr,sr->hs", q_r, chunk,
                                preferred_element_type=jnp.float32) * scale
            seen = (s * S + jnp.arange(S) < n)[None, :]
            m_new = jnp.maximum(m_run, jnp.max(
                jnp.where(seen, scores, NEG_INF), axis=-1))
            p = jnp.where(seen, jnp.exp(scores - m_new[:, None]), 0.0)
            fade = jnp.exp(m_run - m_new)
            acc = acc * fade[:, None] + jnp.einsum(
                "hs,sr->hr", p.astype(blocks.dtype), chunk,
                preferred_element_type=jnp.float32)
            return m_new, l_run * fade + jnp.sum(p, axis=-1), acc

        stat = jnp.full((H,), NEG_INF, jnp.float32)
        _, l_run, acc = jax.lax.fori_loop(
            0, (n + S - 1) // S, step,
            (stat, jnp.zeros_like(stat), jnp.zeros((H, R), jnp.float32)))
        return jax.lax.dynamic_update_index_in_dim(
            out, acc / jnp.maximum(l_run, 1e-30)[:, None], r, axis=0)

    out = jax.lax.fori_loop(0, B, one_row,
                            jnp.zeros((B, H, R), jnp.float32))
    return out[:, None]


def mla_slice(q: jax.Array, blocks: jax.Array, tables: jax.Array,
              allowed: Optional[jax.Array], positions: jax.Array,
              token_mask: jax.Array, *, scale: float,
              key_blocks: int = 32, query_block: int = 512) -> jax.Array:
    """q [B, T, H, R]; ``blocks`` [n, block, R] the pool as blocks;
    ``tables`` [B, W] each sequence's blocks in order; ``allowed`` [B, T,
    W * block] the positions each query attends (causality included), or
    None: every real query attends the positions up to its own.
    Returns [B, T, H, R] fp32 as :func:`mla_decode`. ``query_block``
    queries at a time attend ``key_blocks`` blocks of positions a pass, as
    far as the last real query among them reaches: the scores of one pass,
    [B, query_block, H, key_blocks * block] fp32, are the largest value
    made, and the running sums that every pass rescales are one block of
    queries', not the slice's."""
    B, T, H, R = q.shape
    bs, W = blocks.shape[1], tables.shape[1]
    nb = min(key_blocks, W)
    S = nb * bs
    pad = -W % nb
    tables = jnp.pad(tables, ((0, 0), (0, pad)))
    if allowed is not None:
        allowed = jnp.pad(allowed, ((0, 0), (0, 0), (0, pad * bs)))
    qb = math.gcd(T, query_block)

    def queries(_, block):
        q, *selection, positions, token_mask = block

        def step(s, carry):
            m_run, l_run, acc = carry
            phys = jax.lax.dynamic_slice_in_dim(tables, s * nb, nb, axis=1)
            chunk = blocks[phys].reshape(B, S, R)
            scores = jnp.einsum("bthr,bsr->bths", q, chunk,
                                preferred_element_type=jnp.float32) * scale
            if selection:
                seen = jax.lax.dynamic_slice_in_dim(
                    selection[0], s * S, S, axis=2)[:, :, None, :]
            else:
                seen = ((s * S + jnp.arange(S) <= positions[:, :, None])
                        & token_mask[:, :, None])[:, :, None, :]
            m_new = jnp.maximum(m_run, jnp.max(
                jnp.where(seen, scores, NEG_INF), axis=-1))
            p = jnp.where(seen, jnp.exp(scores - m_new[..., None]), 0.0)
            fade = jnp.exp(m_run - m_new)
            acc = acc * fade[..., None] + jnp.einsum(
                "bths,bsr->bthr", p.astype(blocks.dtype), chunk,
                preferred_element_type=jnp.float32)
            return m_new, l_run * fade + jnp.sum(p, axis=-1), acc

        # chunks up to the last real query's position, no further
        last = jnp.max(jnp.where(token_mask, positions, 0))
        stat = jnp.full((B, qb, H), NEG_INF, jnp.float32)
        _, l_run, acc = jax.lax.fori_loop(
            0, last // S + 1, step,
            (stat, jnp.zeros_like(stat),
             jnp.zeros((B, qb, H, R), jnp.float32)))
        return None, acc / jnp.maximum(l_run, 1e-30)[..., None]

    def by_block(a):
        return jnp.moveaxis(a.reshape(B, T // qb, qb, *a.shape[2:]), 1, 0)

    inputs = (q, positions, token_mask) if allowed is None \
        else (q, allowed, positions, token_mask)
    _, out = jax.lax.scan(queries, None, tuple(map(by_block, inputs)))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H, R)
