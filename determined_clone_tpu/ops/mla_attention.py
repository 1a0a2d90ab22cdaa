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
- :func:`mla_slice` — a prefill slice, in one Pallas kernel (compiled on
  the chip, interpreted off it): a tile of queries with all their heads
  stays in fast memory while the sequence's blocks are copied through the
  table a tile of positions at a time, as far as the tile's last real
  query reaches; scores, the mask of allowed positions
  (``ops/dsa_index.py``) or, with none, the causal mask, the online softmax
  and the weighted sum never leave it. The cost is the dense one (ROADMAP
  B-M keeps the gathered form).

Products take bfloat16 operands and sum in fp32; scores, softmax and the
running sums are fp32; the probabilities are rounded to the rows' dtype
for their product with the rows.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from determined_clone_tpu.ops.flash_attention import (
    LANES,
    NEG_INF,
    _NN,
    _NT,
    _dot,
    _should_interpret,
)

# a slice's kernel: rows of one product (queries x heads) and positions a
# key tile (tools/mla_slice_sweep.py), and the fast memory a call may ask
SLICE_ROWS = 2048
SLICE_KEYS = 512
_VMEM_LIMIT = 100 * 2 ** 20


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


class Tiles(NamedTuple):
    """``queries`` of all heads a grid step (``queries * H`` rows of one
    product); ``key_blocks`` pool blocks a key tile."""
    queries: int
    key_blocks: int


def tiles(T: int, heads: int, table_width: int, block: int) -> Tiles:
    """The tiles from the shapes (the v5e sweep of PERF.md section 6,
    ``tools/mla_slice_sweep.py``): ``SLICE_ROWS`` rows a product (whole
    32-row tiles of the one-byte mask), ``SLICE_KEYS`` positions a key
    tile, or what of ``T`` and of the table there is."""
    queries = math.gcd(T, max(32, SLICE_ROWS // heads))
    return Tiles(queries, max(1, min(table_width, SLICE_KEYS // block)))


def key_tiles(starts: Sequence[int], counts: Sequence[int], length: int,
              heads: int, table_width: int, block: int,
              layers: int = 1) -> Dict[str, int]:
    """On the host, for a prefill call of ``length`` tokens a row (padded
    to whole blocks, as the models pad a slice) whose row ``b`` holds
    ``counts[b]`` real tokens from position ``starts[b]`` on (0 of them: a
    row of padding): ``mla_key_tiles``, the key tiles :func:`mla_slice`
    multiplies at :func:`tiles`' sizes in ``layers`` layers, and
    ``mla_key_tiles_dense``, the tiles whole tables would cost (the args a
    family's ``PagedModel.prefill_counts`` puts on the call's span)."""
    T = length + -length % block
    tl = tiles(T, heads, table_width, block)
    tk = tl.key_blocks * block
    run = 0
    for lo, n in zip(starts, counts):
        for first in range(0, T, tl.queries):
            real = min(n - first, tl.queries)
            run += (lo + first + real - 1 if real > 0 else 0) // tk + 1
    dense = len(starts) * (T // tl.queries) * -(-table_width // tl.key_blocks)
    return {"mla_key_tiles": run * layers,
            "mla_key_tiles_dense": dense * layers}


_PARTS = frozenset({"copy", "mask", "softmax", "weigh"})


def _slice_kernel(tables_ref, tiles_ref, q_ref, seen_ref, pool, o_ref,
                  k_buf, m_ref, l_ref, acc, sems, *, scale: float, nb: int,
                  table_width: int, causal: bool, parts=_PARTS):
    """One tile of queries, all heads: ``q_ref`` [1, H, tq, R]; ``seen_ref``
    [1, tq, 1] int32, the last position each query attends (-1: none), or
    [1, tq, S] int8, the positions it attends; ``pool`` [n, block, R] in
    HBM; ``o_ref`` [1, H, tq, rank]. ``k_buf`` [2, tk, R] two slots of key
    tiles: tile ``j + 1`` is copied while tile ``j`` is multiplied.
    ``parts`` is the sweep's: what of the body to leave in."""
    b, i = pl.program_id(0), pl.program_id(1)
    _, H, tq, R = q_ref.shape
    _, tk, _ = k_buf.shape
    bs, rank = tk // nb, acc.shape[1]
    n = tiles_ref[b * pl.num_programs(1) + i]
    q = q_ref[0].reshape(H * tq, R)

    def copy(slot, j, u):
        return pltpu.make_async_copy(
            pool.at[tables_ref[b * table_width + j * nb + u]],
            k_buf.at[slot, pl.ds(u * bs, bs)], sems.at[slot])

    def start(slot, j):
        for u in range(nb):
            copy(slot, j, u).start()

    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc[...] = jnp.zeros(acc.shape, jnp.float32)
    if "copy" in parts:
        start(0, 0)

    def tile(j, _):
        slot = j % 2

        if "copy" in parts:
            @pl.when(j + 1 < n)
            def _():
                start(1 - slot, j + 1)
            for u in range(nb):      # a wait counts a block's bytes
                copy(slot, 0, u).wait()
        k = k_buf[slot]
        s = _dot(q, k, _NT) * scale
        if "mask" in parts:
            first = pl.multiple_of(j * tk, tk)
            if causal:
                seen = first + jax.lax.broadcasted_iota(
                    jnp.int32, (tq, tk), 1) <= seen_ref[0]
            else:   # compared in 4-byte tiles, as the scores lie
                seen = seen_ref[0, :, pl.ds(first, tk)].astype(jnp.int32) != 0
            s = jnp.where(seen[None], s.reshape(H, tq, tk),
                          NEG_INF).reshape(H * tq, tk)
        if "softmax" in parts:
            m_run = m_ref[...]
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1, keepdims=True))
            # a masked score's is 0 beside any real one; a row with none
            # yet sums rows that the first real score fades to nothing
            p = jnp.exp(s - m_new)
            fade = jnp.exp(m_run - m_new)
            l_ref[...] = l_ref[...] * fade + jnp.sum(p, axis=-1,
                                                     keepdims=True)
            m_ref[...] = m_new
        else:
            p, fade = s, 1.0
        if "weigh" in parts:
            acc[...] = acc[...] * fade + _dot(p.astype(k.dtype),
                                              k[:, :rank], _NN)
        else:
            acc[:, :LANES] += p[:, :LANES]

    jax.lax.fori_loop(0, n, tile, None)
    out = jnp.where(m_ref[...] > 0.5 * NEG_INF,
                    acc[...] / jnp.maximum(l_ref[...], 1e-30), 0.0)
    o_ref[0] = out.reshape(H, tq, rank)


def slice_cost(queries: int, heads: int, positions: int, row_width: int,
               rank: int, dtype: Any) -> pl.CostEstimate:
    """What ``queries`` queries of ``heads`` heads over ``positions``
    cached positions each need: a score over the whole row, a weighted sum
    over its first ``rank`` columns, one exponential a score; the queries
    read and the sums written once, the rows once a tile of ``SLICE_ROWS``
    query rows."""
    pairs = queries * heads * positions
    item = jnp.dtype(dtype).itemsize
    return pl.CostEstimate(
        flops=2 * pairs * (row_width + rank), transcendentals=pairs,
        bytes_accessed=queries * heads * (row_width * item + 4 * rank)
        + pairs // SLICE_ROWS * row_width * item)


@functools.partial(jax.jit, static_argnames=("scale", "rank", "tl", "parts",
                                             "interpret"))
def _slice_call(q, seen, blocks, tables, n_tiles, *, scale: float,
                rank: int, tl: Tiles, interpret: bool, parts=_PARTS):
    """The kernel over q [B, H, T, R] (heads first: a tile's rows are a
    head's queries together, so one mask tile serves every head where it
    lies); ``seen`` as ``_slice_kernel`` takes it; ``tables`` [B, W'] whole
    key tiles; ``n_tiles`` [B, T / tq] key tiles each query tile
    multiplies. Returns [B, H, T, rank] fp32. A ``jit`` of its own: the
    layers of a program share one function, traced once a process and
    shape and lowered once a program (a serving ladder lowers the kernel
    in every slice program at start-up)."""
    B, H, T, R = q.shape
    bs, Wp = blocks.shape[1], tables.shape[1]
    tq, nb = tl
    item = jnp.dtype(q.dtype).itemsize
    rows = H * tq
    vmem = (2 * rows * R * item + 3 * rows * rank * 4 + 2 * nb * bs * R * item
            + 2 * tq * seen.shape[2] * jnp.dtype(seen.dtype).itemsize
            + 2 * rows * LANES * 4            # the running maximum and sum
            + 5 * rows * nb * bs * 4)         # score-shaped temporaries
    return pl.pallas_call(
        functools.partial(_slice_kernel, scale=scale, nb=nb, table_width=Wp,
                          causal=seen.shape[2] == 1, parts=parts),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, T // tq),
            in_specs=[
                pl.BlockSpec((1, H, tq, R), lambda b, i, *_: (b, 0, i, 0)),
                pl.BlockSpec((1, tq, seen.shape[2]),
                             lambda b, i, *_: (b, i, 0)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, tq, rank),
                                   lambda b, i, *_: (b, 0, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, nb * bs, R), blocks.dtype),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, rank), jnp.float32),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((B, H, T, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=min(_VMEM_LIMIT, vmem + 8 * 2 ** 20)),
        cost_estimate=slice_cost(B * T, H, Wp * bs, R, rank, q.dtype),
        interpret=interpret,
        name="mla_slice",
    )(tables.reshape(-1), n_tiles.reshape(-1), q, seen, blocks)


def mla_slice(q: jax.Array, blocks: jax.Array, tables: jax.Array,
              allowed: Optional[jax.Array], positions: jax.Array,
              token_mask: jax.Array, *, scale: float,
              rank: Optional[int] = None, key_blocks: Optional[int] = None,
              query_block: Optional[int] = None) -> jax.Array:
    """q [B, T, H, R]; ``blocks`` [n, block, R] the pool as blocks;
    ``tables`` [B, W] each sequence's blocks in order; ``allowed`` [B, T,
    W * block] the positions each query attends (causality included), or
    None: every real query attends the positions up to its own. Returns the
    probability-weighted sums of the rows' first ``rank`` columns (all
    ``R`` by default), [B, T, H, rank] fp32: what :func:`expand_values`
    reads of :func:`mla_decode`'s sums, and zeros where a query attends
    nothing.

    One Pallas kernel (``mla_slice``; interpreted off the chip): a tile of
    queries with all their heads, ``[tq * H, R]``, stays in fast memory
    while the sequence's blocks come through the table a key tile at a
    time, as far as the tile's last real query reaches and no further;
    scores, mask, running maximum and sum and the weighted sum never leave
    it. ``query_block`` and ``key_blocks`` cap the tiles (the tests';
    :func:`tiles` otherwise). A row within a key tile that a real query
    reaches is multiplied by a weight of 0 where it is masked, so it has to
    be finite."""
    B, T, H, R = q.shape
    bs, W = blocks.shape[1], tables.shape[1]
    tl = tiles(T, H, W, bs)
    tl = Tiles(math.gcd(tl.queries, query_block or tl.queries),
               min(tl.key_blocks, key_blocks or tl.key_blocks))
    pad = -W % tl.key_blocks
    # a copy, unlike a gather, does not clamp a block id for itself
    tables = jnp.pad(jnp.clip(tables, 0, blocks.shape[0] - 1).astype(
        jnp.int32), ((0, 0), (0, pad)))
    reach = jnp.where(token_mask, positions, -1).astype(jnp.int32)
    # key tiles up to the last real query's position, no further
    n_tiles = jnp.maximum(jnp.max(reach.reshape(B, -1, tl.queries), axis=-1),
                          0) // (tl.key_blocks * bs) + 1
    if allowed is None:
        seen = reach[..., None]
    else:
        seen = jnp.pad(allowed, ((0, 0), (0, 0), (0, pad * bs))).astype(
            jnp.int8)
    out = _slice_call(jnp.swapaxes(q, 1, 2), seen, blocks, tables, n_tiles,
                      scale=scale, rank=R if rank is None else rank, tl=tl,
                      interpret=_should_interpret())
    return jnp.swapaxes(out, 1, 2)
