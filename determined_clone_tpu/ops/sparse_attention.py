"""Block-sparse attention with a compressed-key index (InfLLM-V2, the
``minicpm4`` mixer of MiniCPM-SALA; MiniCPM4 report, arXiv:2506.07900),
over a paged cache.

``H`` query heads share ``G`` key/value heads (groups of ``H / G``). A
query at position ``t`` with ``t + 1 <= dense_len`` attends every key up to
``t``. Past that it attends the exact K/V rows of at most ``topk`` blocks
of ``block`` positions, one choice per group and query token:

1. *compress*: ``kc_j = mean(k[stride * j : stride * j + kernel])``, a key
   per ``stride`` positions, complete once its last position is cached;
2. *score*: ``p_h = softmax_j(d ** -0.5 * q_h . kc_j)`` over the complete
   ``kc_j`` that end at or before ``t``; *group-sum* ``r_g = sum_{h in g}
   p_h``; a *block's* score is the largest ``r_g`` over the ``kc_j`` that
   overlap it;
3. *choice*: the first ``init_blocks`` blocks and the blocks that hold the
   last ``window`` positions up to ``t`` are always taken and count toward
   ``topk``; the best-scored other blocks fill the rest; ties go to the
   lower block (``lax.top_k``'s order);
4. causal softmax attention over the rows of the chosen blocks.

The softmax of step 2 is exact (the released kernels' coarse normaliser is
a speed device and is not reproduced). :func:`sparse_select` is steps 2-3
and :func:`sparse_attend` step 4, each in two forms by the shapes:

- a decode step (``T == 1``) gathers the chosen blocks from the pool by
  ``block_tables[choice]``, computed on the device;
- a prefill slice applies the choice as a per-token, per-group block mask
  inside a blocked causal attention over the cached rows (online softmax,
  a few key blocks at a time: the ``[H, T, S]`` scores never exist). It
  costs what dense attention costs, a few percent of a slice's products.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from determined_clone_tpu.ops.attention import NEG_INF


@dataclasses.dataclass(frozen=True)
class SparseConfig:
    """MiniCPM4's published ``sparse_config``."""
    kernel: int = 32       # positions a compressed key averages
    stride: int = 16       # positions between compressed keys
    block: int = 64        # positions a chosen block holds (the cache's)
    topk: int = 64         # blocks a query attends, forced ones included
    init_blocks: int = 1   # always taken, from the start of the sequence
    window: int = 2048     # the blocks holding the last positions, too
    dense_len: int = 8192  # up to this length attention is dense

    def __post_init__(self) -> None:
        if self.kernel % self.stride or self.block % self.stride:
            raise ValueError(
                f"kernel {self.kernel} and block {self.block} must be "
                f"whole strides of {self.stride}")

    @property
    def keys_per_block(self) -> int:
        """Compressed keys that start in one block."""
        return self.block // self.stride


def compressed_keys(k: jax.Array, sp: SparseConfig) -> jax.Array:
    """k: [B, S, R], S whole strides -> [B, S / stride - (kernel / stride
    - 1), R]: entry i is the mean of ``k[stride * i : stride * i +
    kernel]``, summed in fp32, in k's dtype."""
    B, S, R = k.shape
    m = sp.kernel // sp.stride
    part = jnp.mean(k.reshape(B, S // sp.stride, sp.stride, R
                              ).astype(jnp.float32), axis=2)
    n = part.shape[1] - (m - 1)
    return (sum(part[:, i:i + n] for i in range(m)) / m).astype(k.dtype)


def block_scores(q: jax.Array, kc: jax.Array, positions: jax.Array,
                 sp: SparseConfig) -> jax.Array:
    """Steps 2: q [B, T, H, d]; kc [B, J, G * d], the sequence's compressed
    keys in order (entry j starts at position ``stride * j``; entries not
    complete by a query's position count for nothing), J whole blocks'
    worth; positions [B, T]. Returns the blocks' scores [B, G, T, J /
    keys_per_block] fp32, all >= 0."""
    B, T, H, d = q.shape
    J = kc.shape[1]
    G = kc.shape[2] // d
    per, m = sp.keys_per_block, sp.kernel // sp.stride
    s = jnp.einsum("btghd,bjgd->bghtj", q.reshape(B, T, G, H // G, d),
                   kc.reshape(B, J, G, d),
                   preferred_element_type=jnp.float32) * d ** -0.5
    complete = (jnp.arange(J) * sp.stride + sp.kernel - 1
                <= positions[:, None, None, :, None])
    p = jax.nn.softmax(jnp.where(complete, s, NEG_INF), axis=-1)
    r = jnp.sum(jnp.where(complete, p, 0.0), axis=2)          # [B, G, T, J]
    # block b is overlapped by the keys that start in it and by the last
    # kernel / stride - 1 that start before it
    best = jnp.max(r.reshape(B, G, T, J // per, per), axis=-1)
    for i in range(1, m):
        before = jnp.pad(r, ((0, 0),) * 3 + ((i, 0),))[..., :J]
        best = jnp.maximum(best, before.reshape(B, G, T, J // per, per
                                                )[..., 0])
    return best


def _priority(scores: jax.Array, positions: jax.Array, sp: SparseConfig
              ) -> Tuple[jax.Array, jax.Array]:
    """(+inf for a forced block, its score for another block that holds a
    position up to the query's, -inf for the rest; which blocks hold
    one) for scores [B, G, T, W] and positions [B, T]."""
    b = jnp.arange(scores.shape[-1])
    t = positions[:, None, :, None]
    exists = b <= t // sp.block
    forced = exists & ((b < sp.init_blocks)
                       | (b >= jnp.maximum(t - sp.window + 1, 0) // sp.block))
    return jnp.where(forced, jnp.inf,
                     jnp.where(exists, scores, -jnp.inf)), exists


def sparse_select(q: jax.Array, kc: jax.Array, positions: jax.Array,
                  token_mask: jax.Array, sp: SparseConfig
                  ) -> Tuple[jax.Array, jax.Array]:
    """Steps 2-3 for q [B, T, H, d], kc [B, J, G * d], positions and
    token_mask [B, T].

    ``T == 1``: ``(choice [B, G, K] int32, valid [B, G, K])``, the chosen
    blocks as entries of the sequence's block table, a sparse row's first
    in its first ``topk`` slots; ``K = max(topk, blocks of dense_len)`` so
    that a row still under ``dense_len`` has a slot for each of its blocks.
    ``T > 1``: ``(mask [B, G, T, W] bool, None)``, True where the query
    attends the block; the scoring is skipped where every real query of
    the slice is under ``dense_len``.
    """
    B, T = positions.shape
    W = kc.shape[1] // sp.keys_per_block
    k = min(sp.topk, W)
    dense = positions + 1 <= sp.dense_len                      # [B, T]

    if T == 1:
        prio, _ = _priority(block_scores(q, kc, positions, sp), positions,
                            sp)
        vals, idx = jax.lax.top_k(prio[:, :, 0], k)            # [B, G, k]
        K = min(W, max(k, -(-sp.dense_len // sp.block)))
        slots = jnp.arange(K, dtype=jnp.int32)
        choice = jnp.where(dense[:, :, None], slots,
                           jnp.pad(idx, ((0, 0), (0, 0), (0, K - k))))
        valid = jnp.where(dense[:, :, None],
                          slots <= positions[:, :, None] // sp.block,
                          jnp.pad(vals > -jnp.inf,
                                  ((0, 0), (0, 0), (0, K - k))))
        return choice, valid

    def chosen():
        prio, exists = _priority(block_scores(q, kc, positions, sp),
                                 positions, sp)
        # the k best, ties to the lower block, without their indices: all
        # above the k-th value, and the first of those equal to it
        kth = jax.lax.top_k(prio, k)[0][..., -1:]
        above, equal = prio > kth, prio == kth
        room = k - jnp.sum(above, axis=-1, keepdims=True)
        took = above | (equal & (jnp.cumsum(equal, axis=-1) <= room))
        return jnp.where(dense[:, None, :, None], exists,
                         took & (prio > -jnp.inf))

    def every_block():
        G = kc.shape[2] // q.shape[-1]
        exists = jnp.arange(W) <= positions[:, None, :, None] // sp.block
        return jnp.broadcast_to(exists, (B, G, T, W))

    return jax.lax.cond(jnp.all(dense | ~token_mask), every_block,
                        chosen), None


def _decode_attend(q, k_blocks, v_blocks, tables, choice, valid, positions,
                   sp):
    B, _, H, d = q.shape
    G, K = choice.shape[1:]
    bs = sp.block
    phys = jnp.take_along_axis(tables[:, None, :], choice, axis=2)

    def own(blocks):  # group g's columns of the blocks group g chose
        # by index on a [.., G, d] view: on the TPU a column slice of the
        # gathered rows, [..., g * d:(g + 1) * d], read group 0's columns
        # for every group (found on the chip, PR 31: PERF.md section 6)
        rows = blocks[phys].reshape(B, G, K * bs, G, d)
        return jnp.stack([rows[:, g, :, g] for g in range(G)], axis=1)

    kk, vv = own(k_blocks), own(v_blocks)
    scores = jnp.einsum("bghd,bgkd->bghk", q.reshape(B, G, H // G, d), kk,
                        preferred_element_type=jnp.float32) * d ** -0.5
    key_pos = (choice[..., None] * bs + jnp.arange(bs)).reshape(B, G, K * bs)
    seen = jnp.repeat(valid, bs, axis=-1) \
        & (key_pos <= positions[:, :, None])
    probs = jax.nn.softmax(jnp.where(seen[:, :, None], scores, NEG_INF),
                           axis=-1).astype(q.dtype)
    out = jnp.einsum("bghk,bgkd->bghd", probs, vv,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, d)


def _slice_attend(q, k_blocks, v_blocks, tables, mask, positions,
                  token_mask, sp, key_blocks):
    B, T, H, d = q.shape
    G, W = mask.shape[1], mask.shape[3]
    bs, nb = sp.block, min(key_blocks, mask.shape[3])
    Hg, S = H // G, nb * bs
    pad = -W % nb
    tables = jnp.pad(tables[:, :W], ((0, 0), (0, pad)))
    mask = jnp.pad(mask, ((0, 0),) * 3 + ((0, pad),))
    qg = q.reshape(B, T, G, Hg, d)
    scale = d ** -0.5

    def step(s, carry):
        m_run, l_run, acc = carry
        phys = jax.lax.dynamic_slice_in_dim(tables, s * nb, nb, axis=1)
        kk = k_blocks[phys].reshape(B, S, G, d)
        vv = v_blocks[phys].reshape(B, S, G, d)
        scores = jnp.einsum("btghd,bkgd->bghtk", qg, kk,
                            preferred_element_type=jnp.float32) * scale
        seen = jnp.repeat(
            jax.lax.dynamic_slice_in_dim(mask, s * nb, nb, axis=3), bs,
            axis=-1) & (s * S + jnp.arange(S)
                        <= positions[:, None, :, None])      # [B, G, T, S]
        seen = seen[:, :, None]
        m_new = jnp.maximum(m_run, jnp.max(
            jnp.where(seen, scores, NEG_INF), axis=-1))
        p = jnp.where(seen, jnp.exp(scores - m_new[..., None]), 0.0)
        fade = jnp.exp(m_run - m_new)
        acc = acc * fade[..., None] + jnp.einsum(
            "bghtk,bkgd->bghtd", p.astype(q.dtype), vv,
            preferred_element_type=jnp.float32)
        return m_new, l_run * fade + jnp.sum(p, axis=-1), acc

    # key blocks up to the last real query's, no further
    last = jnp.max(jnp.where(token_mask, positions, 0))
    stat = jnp.full((B, G, Hg, T), NEG_INF, jnp.float32)
    _, l_run, acc = jax.lax.fori_loop(
        0, last // S + 1, step,
        (stat, jnp.zeros_like(stat), jnp.zeros((B, G, Hg, T, d),
                                               jnp.float32)))
    out = acc / jnp.maximum(l_run, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, d)


def sparse_attend(q: jax.Array, k_blocks: jax.Array, v_blocks: jax.Array,
                  block_tables: jax.Array, selection: Tuple[jax.Array, ...],
                  positions: jax.Array, token_mask: jax.Array,
                  sp: SparseConfig, *, key_blocks: int = 8) -> jax.Array:
    """Step 4 for q [B, T, H, d] over a paged cache: ``k_blocks``,
    ``v_blocks`` [n, block, G * d], the pool's blocks with each position's
    K (V) of all G heads side by side; ``block_tables`` [B, W] the ids in
    them of each sequence's blocks in order; ``selection`` what
    :func:`sparse_select` gave for the same queries. Returns [B, T, H, d]
    fp32; scores and softmax fp32, the probabilities rounded to q's dtype
    for the product with v. The output of a masked query means nothing."""
    if q.shape[1] == 1:
        choice, valid = selection
        k = min(sp.topk, choice.shape[-1])

        def attend(n):
            return _decode_attend(q, k_blocks, v_blocks, block_tables,
                                  choice[..., :n], valid[..., :n],
                                  positions, sp)

        if choice.shape[-1] == k:
            return attend(k)
        # the wide gather only while a row is still under dense_len
        dense = (positions + 1 <= sp.dense_len) & token_mask
        return jax.lax.cond(jnp.any(dense),
                            lambda: attend(choice.shape[-1]),
                            lambda: attend(k))
    return _slice_attend(q, k_blocks, v_blocks, block_tables, selection[0],
                         positions, token_mask, sp, key_blocks)


def block_sparse_attention(q: jax.Array, kc: jax.Array, k_blocks: jax.Array,
                           v_blocks: jax.Array, block_tables: jax.Array,
                           positions: jax.Array, token_mask: jax.Array,
                           sp: SparseConfig) -> jax.Array:
    """Select, then attend (the module's doc-string)."""
    return sparse_attend(
        q, k_blocks, v_blocks, block_tables,
        sparse_select(q, kc, positions, token_mask, sp), positions,
        token_mask, sp)
