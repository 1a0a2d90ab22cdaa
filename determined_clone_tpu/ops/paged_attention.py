"""Pallas TPU paged decode attention: one query position a row, read from
the KV pool through the row's block table, at the row's real length.

``ops/attention.py:decode_attention_rows`` over a gathered context is the
plain form of the same arithmetic: the paged forward gathers every row's
whole table (``table_width * block`` positions, K and V) into a ``[B, S,
R]`` temporary and reads it again under a mask. This kernel takes the pool
as it lies in HBM (``[L*N, block, R]``, one row of all heads per position)
and, for each batch row, copies only the ``ceil(length / block)`` blocks the
row's context fills into fast memory, K and V each once. A row of length 0
(batch padding) copies nothing and writes zeros.

**Grid.** ``Sizes.rows`` batch rows a grid step (one by the rule; the sweep
tries more), in order. The copies of
row ``r + 1`` are started before row ``r`` is computed (two slots of fast
memory, ``[2, S, R]`` for K and for V), so the memory system works while
the products run; only the first row of a call waits for its own copies. A
copy is one ``[block, R]`` pool block (contiguous in HBM), all of a row's K
blocks on one semaphore and all its V blocks on another.

**Arithmetic**, at ``decode_attention_rows``' rounding points. The query is
spread block-diagonally over ``[H, R]`` (head ``h``'s values in its own
columns, zeros elsewhere; rows past ``H`` zero), so the cached rows are read
as they lie: scores ``[H, R] x [chunk, R]^T`` in the operands' dtype summed
in fp32, ``Sizes.chunk`` positions a product, kept in fast memory for the
whole context (``[H, S]`` fp32); rounded to the operands' dtype as ``mha``
rounds them; scaled, masked past the length, an exact fp32 softmax over the
whole context (two sweeps, no running maximum: the probabilities are the
plain form's, not a rescaled sum's); probabilities in the operands' dtype
times V, fp32 sums; each head's own columns picked out. Positions past the
length in the last chunk (the rest of the last block, and what the slot
held before) are masked out of the scores and zeroed in V, so nothing a
row does not attend reaches a sum, whatever it holds.

``sizes`` chooses rows and chunk from the shapes (the rule a sweep on a v5e
gave: ``tools/paged_sweep.py``, PERF.md section 6). ``paged_cost`` gives the
operations and bytes of attending a number of cache rows; the call carries
it at the table's worst case, since the real lengths are not known when it
is traced. Off the chip the kernel runs in Pallas interpret mode, as the
flash kernels do.
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

# fast memory a call may ask for: two slots of K and V at the table's
# whole length, the scores and the products' temporaries
_VMEM_LIMIT = 96 * 2 ** 20
_TEMPORARIES = 16 * 2 ** 20


class Sizes(NamedTuple):
    """``rows`` batch rows a grid step; ``chunk`` positions a product
    (whole pool blocks); ``chunks`` of them hold a whole table."""
    rows: int
    chunk: int
    chunks: int


def _sublanes(dtype: Any) -> int:
    """Rows of one tile of fast memory: 8 of 4 bytes, 16 of 2."""
    return 32 // jnp.dtype(dtype).itemsize


def _padded_heads(heads: int, dtype: Any) -> int:
    """The query's rows ``[H, R]`` as whole tiles; the rows past ``H`` zero."""
    sub = _sublanes(dtype)
    return -(-heads // sub) * sub


def sizes(table_width: int, block: int) -> Sizes:
    """Rows and chunk from the shapes (the v5e sweep of PERF.md section 6):
    256 positions a product, or the whole table where it is shorter, in
    whole blocks; one row a grid step (more moved the time by under 3 %,
    and the kernel unrolls them: four rows compile three times as long,
    for every batch bucket of the ladder)."""
    length = table_width * block
    chunk = max(block, min(256, length) // block * block)
    return Sizes(1, chunk, -(-length // chunk))


def _scratch_bytes(sz: Sizes, heads: int, row_width: int, dtype: Any) -> int:
    item = jnp.dtype(dtype).itemsize
    slots = 2 * 2 * sz.chunks * sz.chunk * row_width * item
    scores = sz.chunks * heads * sz.chunk * (4 + item)
    return slots + scores + heads * row_width * 4


def fits(table_width: int, block: int, heads: int, row_width: int,
         dtype: Any) -> bool:
    """Whether the kernel can take these shapes. Compiled: a pool block is
    whole tiles (its copy lands on a tile boundary), a row whole lanes, and
    two slots of the table's whole length fit fast memory. The interpreter
    takes any."""
    if _should_interpret():
        return True
    sz = sizes(table_width, block)
    return (block % _sublanes(dtype) == 0 and row_width % LANES == 0
            and _scratch_bytes(sz, _padded_heads(heads, dtype), row_width,
                               dtype) + _TEMPORARIES <= _VMEM_LIMIT)


def paged_cost(rows: int, row_width: int, layers: int = 1, *, heads: int,
               dtype: Any) -> pl.CostEstimate:
    """What attending ``rows`` cache rows needs by the algorithm (rows
    summed over the batch, at whatever lengths): a K and a V row of
    ``row_width`` values read once in each of ``layers`` layers, every
    value in one multiply-add, one exponential a head and row."""
    item = jnp.dtype(dtype).itemsize
    return pl.CostEstimate(
        flops=2 * 2 * rows * row_width * layers,
        transcendentals=rows * heads * layers,
        bytes_accessed=2 * rows * row_width * item * layers)


def _kernel(tables_ref, lengths_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, scores, probs, acc, sems, *,
            sz: Sizes, batch: int, table_width: int, block: int,
            heads: int, head_dim: int):
    padded_heads, row_width = acc.shape
    dtype = q_ref.dtype

    def copy(pool, buf, which, slot, block_id, j):
        return pltpu.make_async_copy(
            pool.at[block_id],
            buf.at[slot, pl.ds(pl.multiple_of(j * block, block), block)],
            sems.at[which, slot])

    def start(row):
        """Start the copies of ``row``'s blocks, K first."""
        slot = row % 2
        n_blocks = pl.cdiv(lengths_ref[row], block)
        for which, (pool, buf) in enumerate(((k_hbm, k_buf),
                                             (v_hbm, v_buf))):
            def one(j, _):
                copy(pool, buf, which, slot,
                     tables_ref[row * table_width + j], j).start()
            jax.lax.fori_loop(0, n_blocks, one, None)

    def wait(which, slot, n_blocks):
        pool, buf = ((k_hbm, k_buf), (v_hbm, v_buf))[which]

        def one(j, _):  # a wait counts a block's bytes, whichever block
            copy(pool, buf, which, slot, 0, 0).wait()
        jax.lax.fori_loop(0, n_blocks, one, None)

    def attend(g, row):
        length = lengths_ref[row]
        slot = row % 2

        @pl.when(length == 0)
        def _():
            o_ref[g] = jnp.zeros((1, row_width), o_ref.dtype)

        @pl.when(length > 0)
        def _():
            n_blocks = pl.cdiv(length, block)
            n_chunks = pl.cdiv(length, sz.chunk)
            head = jax.lax.broadcasted_iota(
                jnp.int32, (padded_heads, row_width), 0)
            lane = jax.lax.broadcasted_iota(
                jnp.int32, (padded_heads, row_width), 1)
            own = (lane // head_dim == head) & (head < heads)
            # selected in fp32: the mask comes in 4-byte tiles
            q_diag = jnp.where(own, q_ref[g].astype(jnp.float32),
                               0.0).astype(dtype)

            def rows_of(c):
                return pl.ds(pl.multiple_of(c * sz.chunk, sz.chunk),
                             sz.chunk)

            wait(0, slot, n_blocks)

            def score(c, _):
                scores[c] = _dot(q_diag, k_buf[slot, rows_of(c), :], _NT)
            jax.lax.fori_loop(0, n_chunks, score, None)

            s = scores[...].astype(dtype).astype(jnp.float32)
            s = s / jnp.sqrt(jnp.float32(head_dim))
            pos = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) * sz.chunk
                   + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2))
            s = jnp.where(pos < length, s, NEG_INF)
            top = jnp.max(jnp.max(s, axis=0), axis=-1, keepdims=True)
            e = jnp.exp(s - top[None])
            total = jnp.sum(jnp.sum(e, axis=0), axis=-1, keepdims=True)
            probs[...] = (e / total[None]).astype(dtype)

            wait(1, slot, n_blocks)
            acc[...] = jnp.zeros(acc.shape, jnp.float32)

            def weigh(c, last):
                v = v_buf[slot, rows_of(c), :]
                if last:  # what the slot holds past the length: zeroed
                    at = c * sz.chunk + jax.lax.broadcasted_iota(
                        jnp.int32, (sz.chunk, 1), 0)
                    v = jnp.where(at < length, v, jnp.zeros((), dtype))
                acc[...] += _dot(probs[c], v, _NN)
            jax.lax.fori_loop(0, n_chunks - 1,
                              lambda c, _: weigh(c, False), None)
            weigh(n_chunks - 1, True)

            out = jnp.sum(jnp.where(own, acc[...], 0.0), axis=0,
                          keepdims=True)
            o_ref[g] = out.astype(o_ref.dtype)

    step = pl.program_id(0)
    for g in range(sz.rows):
        row = step * sz.rows + g
        if g == 0:
            @pl.when(step == 0)
            def _():
                start(0)

        @pl.when(row + 1 < batch)
        def _():
            start(row + 1)
        attend(g, row)


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    block_tables: jax.Array, lengths: jax.Array, *,
                    sz: Optional[Sizes] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Attention of one query position a row over its paged context.

    q: [B, 1, H, D]; k_pool, v_pool: [blocks, block, R], position ``p`` of a
    sequence in row ``p % block`` of the block its table names at ``p //
    block``, all heads side by side in columns ``0..H*D``, the columns past
    them zero; block_tables: int32 [B, W] pool block ids (a layer's offset
    added by the caller); lengths: int32 [B], the positions row ``b``
    attends, ``0..lengths[b]``: only table entries ``0..ceil(lengths[b] /
    block)`` are read. Returns [B, 1, H, D] in q's dtype: the numbers of
    ``decode_attention_rows`` over the gathered context, and zeros for a
    row of length 0.

    ``sz`` defaults to ``sizes(...)`` (the sweep passes others);
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
    slot = (2, sz.chunks * sz.chunk, row_width)
    spread = (sz.chunks, padded_heads, sz.chunk)

    def row_block(step, tables, lens):
        return (step, 0, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, sz=sz, batch=batch,
                          table_width=table_width, block=block,
                          heads=heads, head_dim=head_dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch // sz.rows,),
            in_specs=[pl.BlockSpec((sz.rows, 1, row_width), row_block),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((sz.rows, 1, row_width), row_block),
            scratch_shapes=[
                pltpu.VMEM(slot, dtype), pltpu.VMEM(slot, dtype),
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
        name="paged_attn",
    )(block_tables.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      q_rows, k_pool, v_pool)
    return out[:, :, :heads * head_dim].reshape(batch, 1, heads, head_dim)
