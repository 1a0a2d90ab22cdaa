"""Pallas TPU paged decode attention for EVA's two-kind cache: one query
position a row, read from the KV pool through the row's block table, at the
row's real window and summary lengths.

``ops/attention.py:eva_attention`` (``T == 1``) over a gathered context is
the plain form of the same arithmetic: it reads a whole table a row
(``window + summaries`` slots, K and V) under a mask. This kernel takes the
pool as it lies in HBM (``[L*N, block, R]``, one row of all heads per
position) and a row's table, the ring's entries then the summary entries
(``serving/kv_cache.py:WindowSummaryLayout``), and copies only the
``ceil(window_rows / block)`` ring blocks and ``ceil(summary_rows / block)``
summary blocks the row attends, K and V each once. A row with no rows to
attend (batch padding) copies nothing and writes zeros.

**Streaming.** A table is far larger than fast memory holds twice over
(3072 rows x 4096 x 2 B = 25 MB for K alone at the published widths), so
the two ranges are walked as *one* sequence of blocks, ``Sizes.chunk`` rows
(whole blocks) a buffer, through two buffers for K and two for V: a chunk's
copies are started before the product of the chunk before it, one
``[block, R]`` pool block (contiguous in HBM) a copy, a buffer's copies on
one semaphore. K streams first, for the scores; V second, for the sums; V's
first chunk is in flight while K streams, and the next row's first K chunk
while V does, so only the first row of a call waits for its own copy.

**Arithmetic**, at ``eva_attention``'s ``T == 1`` rounding points. The
query is spread block-diagonally over ``[H, R]`` (head ``h``'s values in
its own columns, zeros elsewhere; rows past ``H`` zero), so the cached rows
are read as they lie: scores ``[H, R] x [chunk, R]^T`` in the operands'
dtype summed in fp32, times ``D ** -0.5``, kept in fast memory for the
whole context (``[H, S]`` fp32: 393 KB at the published widths); masked
past each range's length; one exact fp32 softmax over window and summary
rows together (two sweeps, no running maximum: the probabilities are the
plain form's, not a rescaled sum's); probabilities in the operands' dtype
times V, fp32 sums; each head's own columns picked out. What a block holds
past a range's length (the rest of the window's last block, of the
summaries' last block, and whatever a buffer held before) is masked out of
the scores and zeroed in V's buffer, block by block where there is any, so
nothing a row does not attend reaches a sum, whatever it holds.

**Grid.** One batch row a step, in order (``paged_attention.sizes``' note:
more rows a step compile longer for every batch bucket, for nothing).

``sizes`` chooses the chunk from the shapes; ``fits`` says from shapes
alone whether the compiled kernel takes them. Off the chip the kernel runs
in Pallas interpret mode, as the flash kernels do.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

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
from determined_clone_tpu.ops.paged_attention import (
    _TEMPORARIES,
    _VMEM_LIMIT,
    _padded_heads,
    _sublanes,
    paged_cost,
)


class Sizes(NamedTuple):
    """``chunk`` rows a buffer and a product (whole pool blocks);
    ``chunks`` of them hold a whole table."""
    chunk: int
    chunks: int


def sizes(table_width: int, block: int) -> Sizes:
    """256 rows a buffer (``paged_attention.sizes``' sweep value: 2 MB at
    4096 bf16 columns), or the whole table where it is shorter, in whole
    blocks."""
    length = table_width * block
    chunk = max(block, min(256, length) // block * block)
    return Sizes(chunk, -(-length // chunk))


def _scratch_bytes(sz: Sizes, heads: int, row_width: int, dtype: Any) -> int:
    item = jnp.dtype(dtype).itemsize
    buffers = 2 * 2 * sz.chunk * row_width * item
    scores = sz.chunks * heads * sz.chunk * (4 + item)
    return buffers + scores + heads * row_width * 4


def fits(table_width: int, block: int, heads: int, row_width: int,
         dtype: Any) -> bool:
    """Whether the kernel can take these shapes. Compiled: a pool block is
    whole tiles (its copy lands on a tile boundary), a row whole lanes, and
    the buffers and a whole table's scores fit fast memory. The interpreter
    takes any."""
    if _should_interpret():
        return True
    sz = sizes(table_width, block)
    return (block % _sublanes(dtype) == 0 and row_width % LANES == 0
            and _scratch_bytes(sz, _padded_heads(heads, dtype), row_width,
                               dtype) + _TEMPORARIES <= _VMEM_LIMIT)


def _kernel(tables_ref, window_ref, summary_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, scores, probs, acc, sems, *,
            sz: Sizes, batch: int, table_width: int, window_blocks: int,
            block: int, heads: int, head_dim: int):
    padded_heads, row_width = acc.shape
    dtype = q_ref.dtype
    per_chunk = sz.chunk // block
    pools = ((k_hbm, k_buf), (v_hbm, v_buf))

    def blocks_of(row):
        """(ring blocks, all blocks) that ``row`` attends."""
        ring = pl.cdiv(window_ref[row], block)
        return ring, ring + pl.cdiv(summary_ref[row], block)

    def in_chunk(c, n_blocks):
        return jnp.minimum(per_chunk, n_blocks - c * per_chunk)

    def copy(which, slot, block_id, j):
        pool, buf = pools[which]
        return pltpu.make_async_copy(
            pool.at[block_id],
            buf.at[slot, pl.ds(pl.multiple_of(j * block, block), block)],
            sems.at[which, slot])

    def start(which, row, c):
        """Start the copies of chunk ``c`` of ``row``'s blocks: the ring's,
        then the summaries', as one sequence. No blocks (a chunk past the
        row's last), no copies."""
        ring, n_blocks = blocks_of(row)

        def one(j, _):
            at = c * per_chunk + j
            entry = jnp.where(at < ring, at, window_blocks + at - ring)
            copy(which, c % 2, tables_ref[row * table_width + entry],
                 j).start()
        jax.lax.fori_loop(0, in_chunk(c, n_blocks), one, None)

    def wait(which, c, n_blocks):
        def one(j, _):  # a wait counts a block's bytes, whichever block
            copy(which, c % 2, 0, 0).wait()
        jax.lax.fori_loop(0, in_chunk(c, n_blocks), one, None)

    row = pl.program_id(0)
    window, summary = window_ref[row], summary_ref[row]
    ring, n_blocks = blocks_of(row)
    n_chunks = pl.cdiv(n_blocks, per_chunk)
    # the sequence's rows: the window's, a gap to the end of the ring's last
    # block, the summaries', and the rest of their last block
    gap_end = ring * block
    end = gap_end + summary

    def attended(at):
        return (at < window) | ((at >= gap_end) & (at < end))

    @pl.when(row == 0)
    def _():
        start(0, 0, 0)

    @pl.when(n_blocks > 0)
    def _():
        start(1, row, 0)

    # column r of a row belongs to head r // head_dim
    first = head_dim * jax.lax.broadcasted_iota(
        jnp.int32, (padded_heads, row_width), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (padded_heads, row_width), 1)
    own = (lane >= first) & (lane < first + head_dim) \
        & (first < heads * head_dim)

    @pl.when(n_blocks > 0)
    def _():
        # selected in fp32: the mask comes in 4-byte tiles
        q_diag = jnp.where(own, q_ref[0].astype(jnp.float32),
                           0.0).astype(dtype)

        def score(c, _):
            start(0, row, c + 1)
            wait(0, c, n_blocks)
            scores[c] = _dot(q_diag, k_buf[c % 2], _NT)
        jax.lax.fori_loop(0, n_chunks, score, None)

    # both K buffers are free: the next row's first chunk streams in beside
    # this row's V
    @pl.when(row + 1 < batch)
    def _():
        start(0, row + 1, 0)

    @pl.when(n_blocks == 0)
    def _():
        o_ref[0] = jnp.zeros((1, row_width), o_ref.dtype)

    @pl.when(n_blocks > 0)
    def _():
        s = scores[...] * head_dim ** -0.5
        at = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) * sz.chunk
              + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2))
        s = jnp.where(attended(at), s, NEG_INF)
        top = jnp.max(jnp.max(s, axis=0), axis=-1, keepdims=True)
        e = jnp.exp(s - top[None])
        total = jnp.sum(jnp.sum(e, axis=0), axis=-1, keepdims=True)
        probs[...] = (e / total[None]).astype(dtype)
        acc[...] = jnp.zeros(acc.shape, jnp.float32)
        gap_chunk = pl.cdiv(ring, per_chunk) - 1

        def weigh(c, _):
            slot = c % 2
            start(1, row, c + 1)
            wait(1, c, n_blocks)

            def clean(j, _):
                """Zero what block ``j`` of the buffer holds unattended."""
                rows = pl.ds(pl.multiple_of(j * block, block), block)
                at = c * sz.chunk + j * block + jax.lax.broadcasted_iota(
                    jnp.int32, (block, 1), 0)
                v_buf[slot, rows, :] = jnp.where(
                    attended(at), v_buf[slot, rows, :], jnp.zeros((), dtype))

            # the blocks that hold rows not attended: the window's last,
            # and in the last chunk the summaries' last with whatever the
            # buffer holds after it
            @pl.when(c == gap_chunk)
            def _():
                clean(ring - 1 - c * per_chunk, None)

            @pl.when(c == n_chunks - 1)
            def _():
                jax.lax.fori_loop((end - c * sz.chunk) // block, per_chunk,
                                  clean, None)
            acc[...] += _dot(probs[c], v_buf[slot], _NN)
        jax.lax.fori_loop(0, n_chunks, weigh, None)
        out = jnp.sum(jnp.where(own, acc[...], 0.0), axis=0, keepdims=True)
        o_ref[0] = out.astype(o_ref.dtype)


def eva_paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        block_tables: jax.Array, window_rows: jax.Array,
                        summary_rows: jax.Array, *, window_blocks: int,
                        sz: Optional[Sizes] = None,
                        interpret: Optional[bool] = None) -> jax.Array:
    """EVA attention of one query position a row over its paged context.

    q: [B, 1, H, D]; k_pool, v_pool: [blocks, block, R], all heads side by
    side in columns ``0..H*D``, the columns past them zero; block_tables:
    int32 [B, Wt] pool block ids (a layer's offset added by the caller):
    ``window_blocks`` entries of the ring, slot ``j`` of the window in row
    ``j % block`` of entry ``j // block``, then the summary entries, chunk
    ``c``'s summary in row ``c % block`` of entry ``window_blocks + c //
    block``; window_rows, summary_rows: int32 [B], the window slots
    ``0..window_rows[b]`` and the summaries ``0..summary_rows[b]`` that row
    ``b`` attends: only the entries that hold them are read. Returns
    [B, 1, H, D] in q's dtype: the numbers of ``eva_attention`` over the
    gathered context under the mask of those two lengths, and zeros for a
    row that attends nothing.

    ``sz`` defaults to ``sizes(...)`` (the tests pass others);
    ``interpret`` to running interpreted off the chip.
    """
    batch, _, heads, head_dim = q.shape
    _, block, row_width = k_pool.shape
    table_width = block_tables.shape[1]
    dtype = q.dtype
    if sz is None:
        sz = sizes(table_width, block)
    if interpret is None:
        interpret = _should_interpret()
    padded_heads = _padded_heads(heads, dtype)
    q_rows = jnp.pad(q.reshape(batch, 1, heads * head_dim),
                     ((0, 0), (0, 0), (0, row_width - heads * head_dim)))
    buffers = (2, sz.chunk, row_width)
    spread = (sz.chunks, padded_heads, sz.chunk)

    def row_block(step, tables, window, summary):
        return (step, 0, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, sz=sz, batch=batch,
                          table_width=table_width,
                          window_blocks=window_blocks, block=block,
                          heads=heads, head_dim=head_dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(batch,),
            in_specs=[pl.BlockSpec((1, 1, row_width), row_block),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, 1, row_width), row_block),
            scratch_shapes=[
                pltpu.VMEM(buffers, dtype), pltpu.VMEM(buffers, dtype),
                pltpu.VMEM(spread, jnp.float32), pltpu.VMEM(spread, dtype),
                pltpu.VMEM((padded_heads, row_width), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct((batch, 1, row_width), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(_VMEM_LIMIT, _TEMPORARIES + _scratch_bytes(
                sz, padded_heads, row_width, dtype))),
        cost_estimate=paged_cost(batch * table_width * block, row_width,
                                 heads=heads, dtype=dtype),
        interpret=interpret,
        name="eva_paged_attn",
    )(block_tables.reshape(-1).astype(jnp.int32),
      window_rows.astype(jnp.int32), summary_rows.astype(jnp.int32),
      q_rows, k_pool, v_pool)
    return out[:, :, :heads * head_dim].reshape(batch, 1, heads, head_dim)
