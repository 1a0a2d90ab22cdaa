"""Products of rows that lie sorted by group with that group's matrix — the
held experts' products of a trained expert layer (``ops/moe.py``), as two
Pallas kernels for the TPU:

- :func:`grouped_matmul`: ``out[r] = rows[r] @ stack[group of r]`` (or that
  matrix transposed), the rows' products of both passes;
- :func:`grouped_outer`: ``into[g] + lhs[g's rows].T @ rhs[g's rows]``, the
  weights' gradients, summed into fp32 slabs that a loop carries; a group
  with no row is neither read nor written;
- :func:`add_rows`: ``y[index[r]] += rows[r]``, the sums into the tokens'
  rows, a group's rows as one batch of row copies.

``sizes[g]`` rows belong to group ``g``, the groups in order from row 0; rows
past ``sum(sizes)`` belong to none: :func:`grouped_matmul` leaves them
unwritten (mask them before use), the other two do not use them.
All walk the row tiles that hold a row of some group, a tile that two groups
share once for each (the grid's length is a value of the call, from
``sizes``), so the work is in proportion to ``sum(sizes)`` and not to the
rows' buffer. The layout follows ``jax.experimental.pallas.ops.tpu.megablox``
(a grid over output columns, row-tile visits and the contraction; the visits'
group and row tile prefetched as scalars); what is this module's own: tiles
as large as a stated limit of fast memory allows (a visit reads its group's
``[k, tn]`` strip of weights, so 1024 rows a visit against 128 is an eighth
of the traffic), and the skipped groups.

Operands as they come (bfloat16 on the chip), fp32 accumulation and output.
Off the chip the kernels run interpreted.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM_LIMIT = 64 * 2 ** 20


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _fit(tile: int, dim: int) -> int:
    """The largest divisor of ``dim`` that is at most ``tile`` and whole
    128-lane blocks; ``dim`` itself where it is no larger, or has none."""
    if dim <= tile:
        return dim
    for t in range(tile - tile % 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


def _visits(sizes: jax.Array, m: int, tm: int):
    """The row tiles to visit: ``(offsets [G + 1], group [V], row tile [V],
    how many of the V are real)``; V = m / tm + G - 1 is the most there can
    be. A group's visits are consecutive, and so are a tile's."""
    n_groups = sizes.shape[0]
    ends = jnp.cumsum(sizes).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = offsets[:-1] // tm
    tiles = jnp.where(sizes > 0, -(-ends // tm) - first, 0)
    most = m // tm + n_groups - 1
    group = jnp.repeat(jnp.arange(n_groups, dtype=jnp.int32), tiles,
                       total_repeat_length=most)
    before = jnp.cumsum(tiles) - tiles
    row_tile = first[group] + jnp.arange(most, dtype=jnp.int32) \
        - before[group]
    return offsets, group, jnp.clip(row_tile, 0, m // tm - 1).astype(
        jnp.int32), jnp.sum(tiles).astype(jnp.int32)


def _rows_of_group(offsets, group, row_tile, t, tm: int, width: int):
    """[tm, width] bool: the rows of visit ``t``'s tile that are its
    group's."""
    g = group[t]
    row = row_tile[t] * tm + jax.lax.broadcasted_iota(
        jnp.int32, (tm, width), 0)
    return (row >= offsets[g]) & (row < offsets[g + 1])


@functools.partial(jax.jit, static_argnames=(
    "transpose", "tiles", "interpret"))
def _grouped_matmul(rows, stack, sizes, *, transpose, tiles, interpret):
    m, k = rows.shape
    n = stack.shape[1] if transpose else stack.shape[2]
    tm, tk, tn = min(tiles[0], m), _fit(tiles[1], k), _fit(tiles[2], n)
    if m % tm:
        raise ValueError(f"{m} rows are not whole tiles of {tm}")
    offsets, group, row_tile, real = _visits(sizes, m, tm)
    k_tiles = k // tk
    dims = (((1,), (1,)), ((), ())) if transpose else (((1,), (0,)), ((), ()))

    def kernel(offsets, group, row_tile, lhs, rhs, out, acc):
        t, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        acc[...] += jax.lax.dot_general(
            lhs[...], rhs[...], dims, preferred_element_type=jnp.float32)

        @pl.when(k_i == k_tiles - 1)
        def _():
            # a tile two groups share is visited by one after the other and
            # stays where it is between the visits
            mine = _rows_of_group(offsets, group, row_tile, t, tm, tn)
            out[...] = jnp.where(mine, acc[...], out[...])

    def weights(n_i, t, k_i, offsets, group, row_tile):
        return (group[t], n_i, k_i) if transpose else (group[t], k_i, n_i)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, t, k_i, o, g, r:
                             (r[t], k_i)),
                pl.BlockSpec((None, tn, tk) if transpose else (None, tk, tn),
                             weights),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda n_i, t, k_i, o, g, r:
                                   (r[t], n_i)),
            grid=(n // tn, real, k_tiles),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="grouped_matmul",
    )(offsets, group, row_tile, rows, stack)


def grouped_matmul(rows: jax.Array, stack: jax.Array, sizes: jax.Array, *,
                   transpose: bool = False,
                   tiles: Tuple[int, int, int] = (256, 2048, 1536)
                   ) -> jax.Array:
    """rows [m, k], stack [G, k, n] (``transpose``: [G, n, k]), sizes [G]
    int32 -> [m, n] fp32, ``rows[r] @ stack[g]`` for the rows of each group
    ``g``; rows of no group are left unwritten. ``tiles`` = the most rows,
    contraction and columns a grid step takes."""
    return _grouped_matmul(rows, stack, sizes.astype(jnp.int32),
                           transpose=transpose, tiles=tuple(tiles),
                           interpret=_should_interpret())


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def _grouped_outer(lhs_t, rhs, sizes, into, *, tiles, interpret):
    k, m = lhs_t.shape
    n = rhs.shape[1]
    tm, tk, tn = min(tiles[0], m), _fit(tiles[1], k), _fit(tiles[2], n)
    if m % tm:
        raise ValueError(f"{m} rows are not whole tiles of {tm}")
    offsets, group, row_tile, real = _visits(sizes, m, tm)

    def kernel(offsets, group, row_tile, a_t, b, before, out, acc):
        t = pl.program_id(2)
        last = pl.num_programs(2) - 1
        g = group[t]

        @pl.when((t == 0) | (group[jnp.maximum(t - 1, 0)] != g))
        def _():
            acc[...] = jnp.zeros_like(acc)

        # the other groups' rows of the tile count for nothing once one
        # side's are zero; a mask selects fp32, not bfloat16 (Mosaic)
        mine = _rows_of_group(offsets, group, row_tile, t, tm, tn)
        acc[...] += jax.lax.dot_general(
            a_t[...], jnp.where(mine, b[...].astype(jnp.float32), 0.0
                                ).astype(b.dtype),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        @pl.when((t == last) | (group[jnp.minimum(t + 1, last)] != g))
        def _():
            out[...] = before[...] + acc[...]

    slab = pl.BlockSpec((None, tk, tn), lambda n_i, k_i, t, o, g, r:
                        (g[t], k_i, n_i))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(into.shape, jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tk, tm), lambda n_i, k_i, t, o, g, r:
                             (k_i, r[t])),
                pl.BlockSpec((tm, tn), lambda n_i, k_i, t, o, g, r:
                             (r[t], n_i)),
                slab,
            ],
            out_specs=slab,
            grid=(n // tn, k // tk, real),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="grouped_outer",
    )(offsets, group, row_tile, lhs_t, rhs, into)


def grouped_outer(lhs_t: jax.Array, rhs: jax.Array, sizes: jax.Array,
                  into: jax.Array, *,
                  tiles: Tuple[int, int, int] = (512, 2048, 768)
                  ) -> jax.Array:
    """lhs_t [k, m] (the rows' side **transposed**: the kernel multiplies
    plain tiles, and a transpose is cheaper made once outside than a tile
    at a time inside), rhs [m, n], sizes [G] int32, into [G, k, n] fp32 ->
    ``into[g] + lhs_t[:, g's rows] @ rhs[g's rows]`` [G, k, n] fp32,
    written over ``into``; a group with no row keeps its slab untouched.
    Every entry of both sides is finite, rows of no group too (they are
    multiplied by zeros). ``tiles`` = the most rows, and the most of the
    slab's two sides, a grid step takes."""
    return _grouped_outer(lhs_t, rhs, sizes.astype(jnp.int32), into,
                          tiles=tuple(tiles), interpret=_should_interpret())


@functools.partial(jax.jit, static_argnames=("rows_a_step", "interpret"),
                   donate_argnums=(0,))
def _add_rows(y, index, rows, sizes, *, rows_a_step, interpret):
    m = rows.shape[0]
    tm = min(rows_a_step, m)
    if m % tm:
        raise ValueError(f"{m} rows are not whole tiles of {tm}")
    offsets, group, row_tile, real = _visits(sizes, m, tm)

    def kernel(offsets, group, row_tile, index, y_in, rows, y_out, held, sems):
        del y_in  # y_out is the same memory
        t = pl.program_id(0)
        g, first = group[t], row_tile[t] * tm
        lo = jnp.maximum(offsets[g], first) - first
        hi = jnp.minimum(offsets[g + 1], first + tm) - first

        def fetch(r):
            return pltpu.make_async_copy(
                y_out.at[index[first + r]], held.at[r], sems.at[0])

        def store(r):
            return pltpu.make_async_copy(
                held.at[r], y_out.at[index[first + r]], sems.at[1])

        def each(do):
            jax.lax.fori_loop(lo, hi, lambda r, _: do(r), None)

        # the rows of one group go to rows of y that differ, so all are
        # fetched, added to and stored together; the next visit (which may
        # hold a row for the same row of y) starts after the last store
        each(lambda r: fetch(r).start())
        each(lambda r: fetch(r).wait())
        held[...] += rows[...]
        each(lambda r: store(r).start())
        each(lambda r: store(r).wait())

    block = (tm,) + rows.shape[1:]
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(block, lambda t, o, g, r, i:
                                   (r[t], 0, 0))],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            grid=(real,),
            scratch_shapes=[pltpu.VMEM(block, y.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="grouped_add_rows",
    )(offsets, group, row_tile, index, y, rows)


def add_rows(y: jax.Array, index: jax.Array, rows: jax.Array,
             sizes: jax.Array, *, rows_a_step: int = 256) -> jax.Array:
    """``y[index[r]] += rows[r]`` for the rows of every group, written over
    ``y``: y [N, S, 128] fp32, index [m] int32, rows [m, S, 128] fp32, sizes
    [G] as above. A row is ``S`` whole (8, 128) tiles (``S`` a multiple of
    8), so that it lies in one piece and a copy can address it: a row of a
    ``[N, width]`` array shares its tiles with seven others.

    **Within a group the indices differ** (a token meets an expert once), so
    a group's rows in a tile are fetched, added to and stored as one batch
    of row copies; groups go one after the other, so a row of ``y`` that two
    groups add to is added to twice. Rows of no group are not read, and
    their index may be anything."""
    return _add_rows(y, index.astype(jnp.int32), rows,
                     sizes.astype(jnp.int32), rows_a_step=rows_a_step,
                     interpret=_should_interpret())
