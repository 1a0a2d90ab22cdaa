"""Attention of grouped query heads over a paged K/V cache, between a first
and a last position a row: the sliding-window and the full-attention layers
of one stack (``models/afmoe.py``), a decode step and a prefill slice.

The cache is K and V pools of blocks ``[n, block, R]``, one row a position,
the ``Hkv`` KV heads of ``D`` side by side (``R = Hkv * D``, whole lanes at
``D`` = 128), and a table a row: position ``x`` lies in row ``x % block`` of
block ``tables[b, (x // block) % W]``. Where ``W * block`` covers a
sequence's whole length that is the uniform cache's table; where it does
not, the table is a **ring**: ``W * block`` positions are kept, a newer one
over the one ``W * block`` before it. Query head ``h`` reads KV head ``h //
(Hq / Hkv)``.

Both forms read, a row, the blocks that hold positions ``[lo, hi)`` and no
others, a few blocks a pass under an online softmax (a running maximum, a
running sum, a rescaled accumulator: ``ops/mla_attention.py:
mla_decode_dense``'s form), so what is live is one pass's scores and a
row's context is never gathered whole: 34816 positions of a full layer
would be 285 MB of fast memory in ``ops/paged_attention.py``'s kernel, which
keeps a whole context (its limit is 96 MB), and a slice's scores ``[48,
2048, 34816]`` fp32 14 GB. A position outside ``[lo, hi)`` that a pass's
blocks also hold is masked by its weight, not its value: a pool row is only
ever finite (zero, or a real row of some position).

Scores and softmax are fp32, the products' operands the cache's dtype
(bfloat16 on the chip) summed in fp32, the probabilities rounded to that
dtype for their product with V.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30

# Blocks of 64 positions a pass, and queries a pass of a slice: a v5e's sweep
# at 48 query heads over 8 KV heads of 128 (tools/window_attention_sweep.py;
# PERF.md, PR 47), ms a layer. A decode step of 16 rows over a window of 4096
# rows a row (a ring of 96 blocks) / over contexts of 1-34 k (12 k a row on
# average): 8 blocks a pass 1.26 / 2.99, 16 1.17 / 2.53, 32 1.15 / 2.16, 64
# 1.28 / 2.04 (a pass past a window's end reads blocks it masks; a longer
# pass is fewer turns of the loop over a long context). A slice of 2048
# queries at positions 16384.. under the window / under the causal mask
# alone: 256 queries x 16 blocks 3.56 / 11.26, 256 x 32 4.75 / 12.87, 512 x
# 16 3.98 / 12.17, 512 x 32 11.46 / 32.86, 1024 x 16 9.21 / 30.90 (a pass's
# scores [8, 6 x queries, 64 x blocks] fp32 past 100 MB spill).
WINDOW_KEY_BLOCKS, CONTEXT_KEY_BLOCKS = 32, 64   # a decode step's
SLICE_QUERIES, SLICE_KEY_BLOCKS = 256, 16


def _gather(blocks: jax.Array, table: jax.Array, first: jax.Array,
            n: int) -> jax.Array:
    """The rows ``[n * block, R]`` of logical blocks ``first .. first + n``
    of one sequence, through its table (a ring where it is shorter)."""
    at = (first + jnp.arange(n)) % table.shape[0]
    return blocks[table[at]].reshape(-1, blocks.shape[-1])


def decode_rows(q: jax.Array, k_blocks: jax.Array, v_blocks: jax.Array,
                tables: jax.Array, lo: jax.Array, hi: jax.Array, *,
                key_blocks: int) -> jax.Array:
    """One query position a row: q [B, Hq, D]; ``k_blocks``, ``v_blocks``
    [n, block, Hkv * D]; ``tables`` [B, W]; row ``b`` attends positions
    ``[lo[b], hi[b])`` (none where ``hi <= lo``: zeros, the row is
    padding). Returns [B, Hq, D] fp32.

    A row at a time, ``key_blocks`` blocks a pass from the block that holds
    ``lo`` to the one that holds ``hi - 1``. The query is spread
    block-diagonally over ``[Hq, R]`` (head ``h`` in the columns of its KV
    head, zeros elsewhere: ``ops/attention.py:decode_attention_rows``), so
    the cached rows are read as they lie; the zeros cost the MXU ``(Hkv -
    1) / Hkv`` of its work, which at one query position stays under the
    time to read the rows (48 operations a byte against the v5e's 240)."""
    B, Hq, D = q.shape
    bs, R = k_blocks.shape[1:]
    Hkv = R // D
    G = Hq // Hkv
    nb = min(key_blocks, tables.shape[1])
    S = nb * bs
    dtype = k_blocks.dtype
    own = jnp.arange(R)[None, :] // D == (jnp.arange(Hq) // G)[:, None]
    q_diag = jnp.where(own[None], jnp.tile(q, (1, 1, Hkv)), 0).astype(dtype)
    scale = D ** -0.5
    lo, hi = lo.astype(jnp.int32), hi.astype(jnp.int32)

    def one_row(r, out):
        q_r = jax.lax.dynamic_index_in_dim(q_diag, r, keepdims=False)
        table = jax.lax.dynamic_index_in_dim(tables, r, keepdims=False)
        first, last = lo[r], hi[r]
        first_block = first // bs

        def step(s, carry):
            m_run, l_run, acc = carry
            k = _gather(k_blocks, table, first_block + s * nb, nb)
            v = _gather(v_blocks, table, first_block + s * nb, nb)
            scores = jnp.einsum("hr,sr->hs", q_r, k,
                                preferred_element_type=jnp.float32) * scale
            at = (first_block + s * nb) * bs + jnp.arange(S)
            seen = ((at >= first) & (at < last))[None, :]
            m_new = jnp.maximum(m_run, jnp.max(
                jnp.where(seen, scores, NEG_INF), axis=-1))
            p = jnp.where(seen, jnp.exp(scores - m_new[:, None]), 0.0)
            fade = jnp.exp(m_run - m_new)
            acc = acc * fade[:, None] + jnp.einsum(
                "hs,sr->hr", p.astype(dtype), v,
                preferred_element_type=jnp.float32)
            return m_new, l_run * fade + jnp.sum(p, axis=-1), acc

        stat = jnp.full((Hq,), NEG_INF, jnp.float32)
        passes = jnp.where(last > first,
                           ((last - 1) // bs - first_block) // nb + 1, 0)
        _, l_run, acc = jax.lax.fori_loop(
            0, passes, step,
            (stat, jnp.zeros_like(stat), jnp.zeros((Hq, R), jnp.float32)))
        return jax.lax.dynamic_update_index_in_dim(
            out, acc / jnp.maximum(l_run, 1e-30)[:, None], r, axis=0)

    out = jax.lax.fori_loop(0, B, one_row,
                            jnp.zeros((B, Hq, R), jnp.float32))
    # head h's own columns: those of KV head h // G
    kv = jnp.arange(Hkv)
    return out.reshape(B, Hkv, G, Hkv, D)[:, kv, :, kv].transpose(
        1, 0, 2, 3).reshape(B, Hq, D)


def slice_rows(q: jax.Array, k_blocks: jax.Array, v_blocks: jax.Array,
               tables: jax.Array, positions: jax.Array,
               token_mask: jax.Array, *, window: Optional[int] = None,
               q_block: int = SLICE_QUERIES,
               key_blocks: int = SLICE_KEY_BLOCKS) -> jax.Array:
    """A prefill slice: q [B, T, Hq, D], its rows' real tokens first and at
    consecutive ``positions`` [B, T] (``token_mask`` [B, T]), their own K/V
    rows already in the cache. The query at position ``i`` attends
    positions ``max(0, i - window + 1) .. i`` (``window`` None: ``0 ..
    i``). Returns [B, T, Hq, D] fp32, zeros for padding.

    A row and ``q_block`` queries of all heads at a time, ``key_blocks``
    blocks a pass from the block that holds the first position the block's
    first query attends to the one that holds its last real query's own:
    under a window a pass is skipped whole on both sides, under the causal
    mask alone after the queries. A pass's K and V are turned to ``[Hkv,
    S, D]`` and multiplied a KV head at a time with its ``Hq / Hkv`` query
    heads' rows ``[q_block * Hq / Hkv, D]``."""
    B, T, Hq, D = q.shape
    bs, R = k_blocks.shape[1:]
    Hkv = R // D
    G = Hq // Hkv
    tq = math.gcd(T, q_block)
    nb = min(key_blocks, tables.shape[1])
    S = nb * bs
    dtype = k_blocks.dtype
    scale = D ** -0.5
    # [B, T / tq, Hkv, G * tq, D]: a KV head's query rows, head after head
    q = q.astype(dtype).reshape(B, T // tq, tq, Hkv, G, D).transpose(
        0, 1, 3, 4, 2, 5).reshape(B, T // tq, Hkv, G * tq, D)
    positions = positions.astype(jnp.int32).reshape(B, T // tq, tq)
    real = token_mask.reshape(B, T // tq, tq)

    def heads(rows):                      # [S, R] -> [Hkv, S, D]
        return rows.reshape(S, Hkv, D).transpose(1, 0, 2)

    def one_block(table, q_b, pos, ok):
        n = jnp.sum(ok, dtype=jnp.int32)
        last = pos[0] + n                               # one past the last
        first = jnp.maximum(pos[0] - window + 1, 0) if window else 0
        first_pass = first // S

        def step(s, carry):
            m_run, l_run, acc = carry
            c = first_pass + s
            k = heads(_gather(k_blocks, table, c * nb, nb))
            v = heads(_gather(v_blocks, table, c * nb, nb))
            scores = jnp.einsum("gmd,gsd->gms", q_b, k,
                                preferred_element_type=jnp.float32) * scale
            at = c * S + jnp.arange(S)
            seen = ok[:, None] & (at[None, :] <= pos[:, None])
            if window:
                seen &= pos[:, None] - at[None, :] < window
            seen = jnp.tile(seen, (G, 1))[None]          # [1, G * tq, S]
            m_new = jnp.maximum(m_run, jnp.max(
                jnp.where(seen, scores, NEG_INF), axis=-1))
            p = jnp.where(seen, jnp.exp(scores - m_new[..., None]), 0.0)
            fade = jnp.exp(m_run - m_new)
            acc = acc * fade[..., None] + jnp.einsum(
                "gms,gsd->gmd", p.astype(dtype), v,
                preferred_element_type=jnp.float32)
            return m_new, l_run * fade + jnp.sum(p, axis=-1), acc

        stat = jnp.full((Hkv, G * tq), NEG_INF, jnp.float32)
        passes = jnp.where(n > 0, (last - 1) // S - first_pass + 1, 0)
        _, l_run, acc = jax.lax.fori_loop(
            0, passes, step, (stat, jnp.zeros_like(stat),
                              jnp.zeros((Hkv, G * tq, D), jnp.float32)))
        return acc / jnp.maximum(l_run, 1e-30)[..., None]

    def one_row(row):
        table, q_r, pos_r, ok_r = row
        return jax.lax.map(lambda blk: one_block(table, *blk),
                           (q_r, pos_r, ok_r))

    out = jax.lax.map(one_row, (tables, q, positions, real))
    return out.reshape(B, T // tq, Hkv, G, tq, D).transpose(
        0, 1, 4, 2, 3, 5).reshape(B, T, Hq, D)
