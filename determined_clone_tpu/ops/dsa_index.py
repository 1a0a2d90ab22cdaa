"""The learned selection of sparse attention with an indexer (DSA): which
cached positions a query attends.

An indexer with heads and keys of its own scores every cached position:
``I[t, s] = sum_j w[t, j] * relu(q_I[t, j] . k_I[s])`` over its ``J``
heads, for the positions ``s <= t``; the query attends the ``topk``
positions of largest ``I`` (all of them while ``t + 1 <= topk``). There are
no blocks in it: the choice is by position. The choice is exact: the set
``lax.top_k`` takes, ties going to the lower position.

The indexer's keys lie in a paged pool of their own, one ``[d]`` row a
position, found by the block ids that find the latents
(``models/glm_moe_dsa.py``). :func:`index_scores` reads a sequence's keys
a chunk of blocks at a time, as far as the last real query reaches, so that
the scores of one chunk, ``[B, T, J, chunk]`` fp32, are the largest value
made beside ``I`` itself.

A selection is made in the form its reader wants:

- a slice: :func:`select_mask`, ``allowed`` [B, T, S] bool, for attention
  under a mask: ``I > v_K``, and of the positions that tie with the K-th
  value ``v_K`` the lowest, up to K in all. No sort: a ``lax.top_k`` of
  16 x 32768 scores was a third of a decode step on the chip, and of 2048
  x 32768 most of a slice;
- one query a sequence (a decode step): :func:`select`, the same set as
  ``positions`` [B, 1, K] int32, -1 where fewer than ``K`` exist, for a
  gather through the block table.

Products take bfloat16 operands and sum in fp32; scores are fp32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -jnp.inf


def index_scores(q: jax.Array, w: jax.Array, blocks: jax.Array,
                 tables: jax.Array, positions: jax.Array,
                 token_mask: jax.Array, *, key_blocks: int = 16
                 ) -> jax.Array:
    """q [B, T, J, d] the indexer's queries, w [B, T, J] fp32 their
    weights; ``blocks`` [n, block, d] the indexer's pool as blocks,
    ``tables`` [B, W] each sequence's blocks in order (the slice's own keys
    already written). Returns ``I`` [B, T, W * block] fp32, ``-inf`` at
    positions past the query's own and for masked queries."""
    B, T, J, d = q.shape
    bs, W = blocks.shape[1], tables.shape[1]
    nb = min(key_blocks, W)
    S = nb * bs
    pad = -W % nb
    tables = jnp.pad(tables, ((0, 0), (0, pad)))

    def step(s, scores):
        phys = jax.lax.dynamic_slice_in_dim(tables, s * nb, nb, axis=1)
        keys = blocks[phys].reshape(B, S, d)
        dots = jnp.einsum("btjd,bsd->btjs", q, keys,
                          preferred_element_type=jnp.float32)
        part = jnp.sum(w[..., None] * jax.nn.relu(dots), axis=2)
        seen = (s * S + jnp.arange(S) <= positions[:, :, None]) \
            & token_mask[:, :, None]
        return jax.lax.dynamic_update_slice_in_dim(
            scores, jnp.where(seen, part, NEG_INF), s * S, axis=2)

    last = jnp.max(jnp.where(token_mask, positions, 0))
    scores = jax.lax.fori_loop(
        0, last // S + 1, step,
        jnp.full((B, T, (W + pad) * bs), NEG_INF, jnp.float32))
    return scores[:, :, :W * bs]


_GROUP = 128  # positions a group: one lane tile


def select(scores: jax.Array, topk: int) -> jax.Array:
    """The ``topk`` positions of largest score, exactly and without a sort
    (the set ``lax.top_k`` takes, ties to the lower position), in order of
    position: [B, T, K] int32, -1 where a query has fewer than K scored
    positions; K = min(topk, S). What a decode step gathers by.

    The set is :func:`select_mask`'s. Its j-th position is found by
    counting: the group of 128 positions that holds it is the first whose
    running count passes j, and its place in the group the number of the
    group's running counts that do not (the group's counts are picked by a
    one-hot product, exact in bfloat16: they are integers up to 128)."""
    B, T, S = scores.shape
    K = min(topk, S)
    allowed = select_mask(scores, topk)
    allowed = jnp.pad(allowed, ((0, 0), (0, 0), (0, -S % _GROUP)))
    within = jnp.cumsum(allowed.reshape(B, T, -1, _GROUP), axis=-1,
                        dtype=jnp.int32)                   # [B, T, C, G]
    ends = jnp.cumsum(within[..., -1], axis=-1)            # [B, T, C]
    slot = jnp.arange(K, dtype=jnp.int32)
    group = jnp.sum(ends[:, :, None, :] <= slot[:, None], axis=-1,
                    dtype=jnp.int32)                       # [B, T, K]
    pick = jax.nn.one_hot(group, ends.shape[-1], dtype=jnp.bfloat16)
    counts = jnp.einsum("btkc,btcg->btkg", pick,
                        within.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    before = jnp.einsum("btkc,btc->btk", pick.astype(jnp.float32),
                        (ends - within[..., -1]).astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    rank = slot.astype(jnp.float32) - before               # within the group
    place = jnp.sum(counts <= rank[..., None], axis=-1, dtype=jnp.int32)
    return jnp.where(slot < ends[..., -1:], group * _GROUP + place, -1)


def _one_zero(scores: jax.Array) -> jax.Array:
    """-0.0 as 0.0: a comparison of floats counts them equal, a sort's
    total order does not."""
    return jnp.where(scores == 0, 0.0, scores)


def _ordered(scores: jax.Array) -> jax.Array:
    """fp32 scores as uint32 keys of the same order."""
    bits = jax.lax.bitcast_convert_type(_one_zero(scores), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def select_mask(scores: jax.Array, topk: int) -> jax.Array:
    """The set :func:`select` chooses, as a mask [B, T, S] bool, without a
    sort (a slice of 2048 queries over 32768 positions would sort 67 M
    scores in each ``full`` layer): the K-th largest score of every query
    is found bit by bit, 32 counts of the scores at or above a candidate;
    a query attends the positions that score above it and, of those that
    tie with it, the lowest positions up to K in all (``lax.top_k``'s
    order; how far they reach is found bit by bit too). A query with no
    more than K scored positions attends them all."""
    S = scores.shape[-1]
    K = min(topk, S)
    keys = _ordered(scores)

    def bit(i, kth):
        candidate = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(keys >= candidate, axis=-1, keepdims=True,
                         dtype=jnp.int32) >= K
        return jnp.where(enough, candidate, kth)

    kth = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(scores.shape[:-1] + (1,), jnp.uint32))
    above = keys > kth
    ties = keys == kth
    room = K - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    where = jnp.arange(S, dtype=jnp.int32)
    bits = S.bit_length()

    def tie_bit(i, first):
        # the largest ``first`` with no more than ``room`` ties below it
        candidate = first | (jnp.int32(1) << (bits - 1 - i))
        fits = jnp.sum(ties & (where < candidate), axis=-1, keepdims=True,
                       dtype=jnp.int32) <= room
        return jnp.where(fits, candidate, first)

    first = jax.lax.fori_loop(0, bits, tie_bit, jnp.zeros_like(room))
    return (scores > NEG_INF) & (above | (ties & (where < first)))
