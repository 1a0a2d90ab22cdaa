"""Pallas TPU flash attention — the fused hot-path kernels, both passes.

The reference has no custom kernels (SURVEY.md: no CUDA anywhere; attention
lives inside torch). On TPU the idiomatic equivalent is a Pallas kernel
family that keeps the O(T²) score matrix out of HBM in the forward *and*
the backward pass. Three kernels, one custom VJP:

``flash_fwd``       grid (rows of heads, q block, k block), k innermost.
                    The online-softmax state (m, l, acc — the flash
                    recurrence) lives in fp32 VMEM scratch across the k
                    iterations; the row statistics are held replicated
                    over the 128 lanes (a ``[block_q, 1]`` column would use
                    one lane of each vreg). When differentiated it also
                    writes the log-sum-exp of every row, fp32, the rows
                    along the lanes.
``flash_bwd_dkv``   grid (rows of heads, k block, q block), q innermost.
                    Works on *transposed* score pieces ``[tk, tq]``, so the
                    row statistics broadcast as they lie (rows along lanes)
                    and dV = Pᵀ·dO, dK = dSᵀ·Q are plain products.
``flash_bwd_dq``    grid (rows of heads, q block, k block), k innermost;
                    the forward's orientation, dQ = dS·K.

The backward recomputes a block's probabilities from the saved log-sum-exp
(residuals ``q, k, v, o, lse``; ``delta = rowsum(dO · O)`` in fp32) and
never writes a score or probability block to HBM.

**Operand rule.** Every product takes its operands in the dtype the tensors
arrive in (bf16 in training, fp32 in the fp32 unit tests) and accumulates
in fp32 (``preferred_element_type``). Probabilities are cast to ``v.dtype``
for P·V and Pᵀ·dO, dS to the operand dtype for its two products; scores,
the softmax, m, l, the log-sum-exp, delta and every accumulator stay fp32.
The 1/sqrt(D) scale is folded into the q (k) tile when it is a power of two
— exact in any float dtype — and otherwise applied to the fp32 scores; the
gradients take it once, on the fp32 accumulator. There is no exception to
the rule and no switch.

**Layout.** ``[B, T, H, D]`` is read as it lies, viewed ``[B, T, H*D]``
(``layout``): a grid step takes a 128-lane block of 128 / D heads side by
side (two at D = 64), or one head of D = 128·n lanes. A product that
contracts the lanes sees one head by zeroing the others' lanes in one
operand, and a product whose result has the heads side by side keeps each
head's own lanes — at D = 64 both cost the MXU the passes one head costs
anyway, and nothing is transposed outside the kernels. Where the head count
is no multiple (25 heads of D = 64) the last lane block of a row hangs over
the array's edge: what a step reads there is zeroed as it is loaded, and
what it would write there is never written. Only a D that neither divides
128 nor is a multiple of it is transposed to ``[B*H, T, D]``, one head a
step, by XLA around the calls. Same kernels either way.

**Causal skipping.** A block wholly above the diagonal costs no DMA (its
index map is clamped to the nearest live block, whose tile is then already
resident) and no arithmetic. A square block is worked through in square
pieces (``Blocks.tile``): on the diagonal the pieces above it are skipped
and only the pieces that straddle it build a mask.

**Blocks** are chosen here from what the kernel can see — the sequence
lengths, the head dimension, the dtype — by ``block_sizes`` (the rule a
sweep on a v5e gave, PERF.md section 6), not by the caller. A sequence is
padded by the caller to ``seq_multiple(T)``.

``flash_cost`` returns each kernel's FLOPs, transcendentals and HBM bytes
from the shapes; every ``pallas_call`` carries it as its cost estimate.

Off-TPU (CPU tests) the kernels run in Pallas interpret mode, so tests
exercise the same code path. On a TPU backend the compiled Mosaic kernels
are the only path unless a caller passes ``interpret=True`` explicitly:
nothing here selects interpret mode on a chip by itself.

XLA cannot partition a Mosaic kernel: under a multi-device mesh the caller
wraps the call in ``shard_map`` (models/gpt.py ``_flash`` does), and the
VJP's kernels then run inside that ``shard_map``'s transpose.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
# products: contract the last dim of both (A·Bᵀ), or last with first (A·B)
_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _dot(a: jax.Array, b: jax.Array, dims) -> jax.Array:
    """Operands as they come, fp32 accumulation."""
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# shapes -> blocks, padding, cost
# ---------------------------------------------------------------------------

class Blocks(NamedTuple):
    """One kernel's grid step: ``q`` x ``k`` rows of the score matrix,
    worked through in ``tile`` x ``tile`` pieces where the block is square
    (so the dead half of a block on the diagonal is skipped piece by
    piece), else whole."""
    q: int
    k: int
    tile: int

    @property
    def tiles(self) -> Tuple[int, int]:
        """(q rows, k rows) of one piece."""
        if self.q == self.k and self.q % self.tile == 0:
            return self.tile, self.tile
        return self.q, self.k


class BlockSizes(NamedTuple):
    fwd: Blocks
    dkv: Blocks
    dq: Blocks


def seq_multiple(seq_len: int) -> int:
    """What a sequence length has to be a multiple of: a whole lane tile
    once it is tiled into blocks, a bf16 sublane tile while it is one
    block."""
    return LANES if seq_len > LANES else 16


def _largest_block(seq_len: int, cap: int) -> int:
    """The whole sequence if it fits under ``cap``, else the largest
    multiple of 128 under it that divides the sequence."""
    if seq_len <= cap:
        return seq_len
    for blk in range(cap - cap % LANES, 0, -LANES):
        if seq_len % blk == 0:
            return blk
    raise ValueError(
        f"sequence length {seq_len} is past one block ({cap}) and no "
        f"multiple of {LANES}: pad it to seq_multiple()")


# the largest (q rows, k rows, tile) of a grid step; the v5e sweep of
# PERF.md section 6: the forward is fastest with a head's whole score block
# at once (a piece's softmax bookkeeping on lane-replicated statistics
# costs more than the dead half it skips), the backward kernels with the
# block on the diagonal skipped piece by piece
_CAPS = BlockSizes(fwd=Blocks(1024, 1024, 1024), dkv=Blocks(1024, 1024, 128),
                   dq=Blocks(1024, 1024, 512))
_VMEM_LIMIT = 48 * 2 ** 20


def block_sizes(q_len: int, k_len: int, head_dim: int,
                dtype: Any) -> BlockSizes:
    """Blocks from the shapes: as large as the caps allow, halved (k rows
    first) while a step's double-buffered operand tiles, its fp32
    accumulators and the score-shaped temporaries of one piece pass half
    the VMEM the kernels ask for — a wide head or a 4-byte operand shrinks
    them."""
    item = jnp.dtype(dtype).itemsize
    width = max(head_dim, LANES)        # a tile's rows are padded to lanes
    chosen = []
    for cap in _CAPS:
        cap_q, cap_k = cap.q, cap.k
        while True:
            block = Blocks(_largest_block(q_len, cap_q),
                           _largest_block(k_len, cap_k), cap.tile)
            tq, tk = block.tiles
            operands = 2 * 2 * (block.q + block.k) * width * item
            accumulators = 2 * max(block.q, block.k) * width * 4
            pieces = 4 * tq * tk * 4
            if operands + accumulators + pieces <= _VMEM_LIMIT // 2 \
                    or max(cap_q, cap_k) <= LANES:
                break
            if cap_k >= cap_q:
                cap_k //= 2
            else:
                cap_q //= 2
        chosen.append(block)
    return BlockSizes(*chosen)


class Layout(NamedTuple):
    """How ``[B, T, H, D]`` reaches the kernels. ``in_place``: as it lies,
    viewed ``[B, T, H*D]``, a grid step taking a 128-lane (or D-lane, for
    D a multiple of 128) block of ``heads`` heads side by side, ``groups``
    of them a row — the last one short of heads where the head count is no
    multiple (25 heads of D = 64: twelve pairs and one alone), its missing
    lanes read as zeros and never written. Otherwise, for a D that neither
    divides 128 nor is a multiple of it, transposed to ``[B*H, T, D]``,
    one head a step."""
    in_place: bool
    heads: int
    groups: int


def layout(n_heads: int, head_dim: int) -> Layout:
    if head_dim % LANES == 0:
        return Layout(True, 1, n_heads)
    if LANES % head_dim == 0:
        side_by_side = LANES // head_dim
        return Layout(True, side_by_side, -(-n_heads // side_by_side))
    return Layout(False, 1, 1)


def _attended_pairs(q_len: int, k_len: int, causal: bool) -> int:
    """(query, key) pairs one head attends over; causal is top-left
    aligned: query i sees keys 0..i."""
    if not causal:
        return q_len * k_len
    n = min(q_len, k_len)
    return n * (n + 1) // 2 + max(q_len - k_len, 0) * k_len


def flash_cost(batch: int, heads: int, q_len: int, k_len: int,
               head_dim: int, causal: bool,
               dtype: Any) -> Dict[str, pl.CostEstimate]:
    """What each kernel needs by the algorithm, from the shapes alone:
    FLOPs of its products over the attended pairs (2 in the forward, 4 in
    dK/dV, 3 in dQ), one exponential a pair, and every operand and result
    moved between HBM and the chip once."""
    n = batch * heads
    pairs = n * _attended_pairs(q_len, k_len, causal)
    item = jnp.dtype(dtype).itemsize
    q_bytes = n * q_len * head_dim * item
    k_bytes = n * k_len * head_dim * item
    row_bytes = n * q_len * 4          # lse or delta, fp32

    def cost(products: int, nbytes: int) -> pl.CostEstimate:
        return pl.CostEstimate(flops=2 * products * pairs * head_dim,
                               transcendentals=pairs, bytes_accessed=nbytes)

    return {
        # q, k, v in; o, lse out
        "flash_fwd": cost(2, 2 * q_bytes + 2 * k_bytes + row_bytes),
        # q, dO, k, v, lse, delta in; dK, dV out
        "flash_bwd_dkv": cost(4, 2 * q_bytes + 4 * k_bytes + 2 * row_bytes),
        # q, dO, k, v, lse, delta in; dQ out
        "flash_bwd_dq": cost(3, 3 * q_bytes + 2 * k_bytes + 2 * row_bytes),
    }


# ---------------------------------------------------------------------------
# pieces the kernels share
# ---------------------------------------------------------------------------

def _lanes(x: jax.Array, n: int) -> jax.Array:
    """A row statistic held replicated over 128 lanes, ``[rows, 128]``,
    widened or narrowed to ``[rows, n]``."""
    if n <= LANES:
        return x[:, :n]
    if n % LANES == 0:
        return jnp.tile(x, (1, n // LANES))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _col_to_row(x: jax.Array) -> jax.Array:
    """``[rows, 128]`` replicated over the lanes -> ``[1, rows]``."""
    return jnp.transpose(x)[:1, :]


def _row_to_col(x: jax.Array) -> jax.Array:
    """``[1, rows]`` -> ``[rows, 128]`` replicated over the lanes."""
    return jnp.transpose(jnp.broadcast_to(x, (LANES, x.shape[1])))


def _head_lanes(x: jax.Array, h: int, heads: int) -> jax.Array:
    """``x [rows, heads*D]`` with the lanes of every head but ``h`` zeroed:
    a product that contracts the lanes then sees head ``h`` alone, at the
    MXU passes one head of D lanes costs anyway."""
    if heads == 1:
        return x
    head_dim = x.shape[1] // heads
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane // head_dim == h, x, jnp.zeros_like(x))


def _by_head(parts) -> jax.Array:
    """``[rows, heads*D]`` whose lanes of head ``h`` come from
    ``parts[h]``."""
    out = parts[0]
    if len(parts) > 1:
        head_dim = out.shape[1] // len(parts)
        lane = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
        for h in range(1, len(parts)):
            out = jnp.where(lane >= h * head_dim, parts[h], out)
    return out


def _lane_guard(lanes: int, width: int, groups: int):
    """What a step applies to every operand tile it loads. Where the
    array's ``lanes`` are no multiple of the step's ``width``, the last
    lane block of a row hangs over the array's edge and what it reads
    there is undefined: those lanes are zeroed."""
    if lanes % width == 0:
        return lambda x: x
    valid = lanes - (pl.program_id(0) % groups) * width

    def guard(x: jax.Array) -> jax.Array:
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        return jnp.where(lane < valid, x, jnp.zeros_like(x))
    return guard


def _scale_rule(head_dim: int) -> Tuple[float, bool]:
    """(1/sqrt(D), whether it is a power of two and so exact on a tile of
    any float dtype)."""
    scale = 1.0 / math.sqrt(head_dim)
    return scale, math.frexp(scale)[0] == 0.5


def _causal_where(s: jax.Array, q_start, k_start, q_axis: int) -> jax.Array:
    """Scores with the keys past each query set to NEG_INF; ``q_axis`` is
    the axis of ``s`` the queries run along."""
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                               1 - q_axis)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _run_live(step, causal: bool, q_start, k_start, block: "Blocks") -> None:
    """Run ``step(diagonal)`` for the (q block, k block) at these offsets:
    not at all where the block lies wholly above the diagonal, with
    ``diagonal`` only where it straddles it."""
    if not causal:
        step(False)
        return
    live = q_start + block.q - 1 >= k_start
    straddles = q_start < k_start + block.k - 1
    pl.when(jnp.logical_and(live, straddles))(lambda: step(True))
    pl.when(jnp.logical_and(live, jnp.logical_not(straddles)))(
        lambda: step(False))


class _Call(NamedTuple):
    """What the three ``pallas_call``s share: the kernel-layout shapes and
    the index maps over grid (row of heads, outer block, inner block)."""
    lay: Layout
    causal: bool
    block: Blocks
    width: int                      # lanes a step takes: heads * D
    lanes: int                      # lanes of an array row

    @classmethod
    def of(cls, q: jax.Array, lay: Layout, causal: bool, block: Blocks,
           head_dim: int) -> "_Call":
        return cls(lay, causal, block, lay.heads * head_dim, q.shape[2])

    def kernel_args(self) -> Dict[str, Any]:
        scale, fold_scale = _scale_rule(self.width // self.lay.heads)
        return dict(scale=scale, fold_scale=fold_scale, causal=self.causal,
                    block=self.block, heads=self.lay.heads,
                    lanes=(self.lanes, self.width, self.lay.groups))

    def rows_of(self, g):
        """(array row, lane block) of grid row ``g``."""
        return g // self.lay.groups, g % self.lay.groups

    def specs(self, q_major: bool):
        """(q-side tensor, k-side tensor, q-side statistic) BlockSpecs for
        grid (g, qi, ki) when ``q_major`` else (g, kj, qi). A block the
        diagonal leaves dead re-names the nearest live one, whose tile is
        then already resident: no DMA."""
        bq, bk = self.block.q, self.block.k

        def blocks_of(a, b):
            qi, ki = (a, b) if q_major else (b, a)
            if self.causal and q_major:   # the last k block a q block reads
                ki = jnp.minimum(ki, ((qi + 1) * bq - 1) // bk)
            elif self.causal:        # the first q block that reads a k block
                qi = jnp.maximum(qi, (ki * bk) // bq)
            return qi, ki

        def q_map(g, a, b):
            row, lane = self.rows_of(g)
            return row, blocks_of(a, b)[0], lane

        def k_map(g, a, b):
            row, lane = self.rows_of(g)
            return row, blocks_of(a, b)[1], lane

        return (pl.BlockSpec((1, self.block.q, self.width), q_map),
                pl.BlockSpec((1, self.block.k, self.width), k_map),
                pl.BlockSpec((1, self.lay.heads, self.block.q),
                             lambda g, a, b: (g, 0, blocks_of(a, b)[0])))


def _compiler_params() -> pltpu.CompilerParams:
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale: float,
                fold_scale: bool, causal: bool, block: Blocks, heads: int,
                lanes: Tuple[int, int, int], n_kb: int, with_lse: bool):
    """Grid (rows of heads, q blocks, k blocks), k innermost. Scratch (m,
    l, acc) persists across the k iterations of one (g, qi); m and l are
    ``[heads, block_q, 128]``, every lane the row's value; acc holds the
    step's heads side by side as the output does."""
    if with_lse:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        m_ref, l_ref, acc_ref = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    width = acc_ref.shape[-1]
    tq, tk = block.tiles
    guard = _lane_guard(*lanes)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _step(diagonal: bool):
        for i in range(block.q // tq):
            rows = slice(i * tq, (i + 1) * tq)
            q = guard(q_ref[0, rows, :])              # [tq, heads*D]
            if fold_scale:
                q = q * scale
            q = [_head_lanes(q, h, heads) for h in range(heads)]
            m = [m_ref[h, rows, :] for h in range(heads)]
            l = [l_ref[h, rows, :] for h in range(heads)]
            acc = acc_ref[rows, :]
            # on the diagonal, the pieces right of piece (i, i) are dead
            for j in range(i + 1 if diagonal else block.k // tk):
                cols = slice(j * tk, (j + 1) * tk)
                k, v = guard(k_ref[0, cols, :]), guard(v_ref[0, cols, :])
                alpha, pv = [], []
                for h in range(heads):
                    s = _dot(q[h], k, _NT)            # [tq, tk] fp32
                    if not fold_scale:
                        s = s * scale
                    if diagonal and j == i:
                        s = _causal_where(s, qi * block.q + i * tq,
                                          ki * block.k + j * tk, 0)
                    m_new = jnp.maximum(
                        m[h], jnp.max(s, axis=1, keepdims=True))
                    # key 0 is live for every causal row, so m is finite
                    # from the first piece on and a masked score's exp is
                    # exactly 0
                    alpha.append(jnp.exp(m[h] - m_new))
                    p = jnp.exp(s - m_new[:, :1])
                    l[h] = alpha[h] * l[h] + jnp.sum(p, axis=1,
                                                     keepdims=True)
                    m[h] = m_new
                    pv.append(_dot(p.astype(v.dtype), v, _NN))
                acc = (acc * _by_head([_lanes(a, width) for a in alpha])
                       + _by_head(pv))
            for h in range(heads):
                m_ref[h, rows, :], l_ref[h, rows, :] = m[h], l[h]
            acc_ref[rows, :] = acc

    _run_live(_step, causal, qi * block.q, ki * block.k, block)

    @pl.when(ki == n_kb - 1)
    def _finalize():
        l = [l_ref[h] for h in range(heads)]
        o_ref[0] = (acc_ref[...] * _by_head(
            [_lanes(1.0 / x, width) for x in l])).astype(o_ref.dtype)
        if with_lse:
            for h in range(heads):
                lse_ref[0, h:h + 1, :] = _col_to_row(
                    m_ref[h] + jnp.log(l[h]))


def _fwd_call(q: jax.Array, k: jax.Array, v: jax.Array, *, lay: Layout,
              head_dim: int, causal: bool, block: Blocks,
              cost: pl.CostEstimate, interpret: bool, with_lse: bool):
    """Kernel-layout q, k, v -> o, and when asked the log-sum-exp,
    ``[rows of heads, heads, Tq]`` fp32."""
    Tq, Tk = q.shape[1], k.shape[1]
    n_g = q.shape[0] * lay.groups
    call = _Call.of(q, lay, causal, block, head_dim)
    q_spec, k_spec, row_spec = call.specs(q_major=True)
    n_kb = Tk // block.k

    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    out_specs = [q_spec]
    if with_lse:
        out_shape.append(
            jax.ShapeDtypeStruct((n_g, lay.heads, Tq), jnp.float32))
        out_specs.append(row_spec)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, n_kb=n_kb, with_lse=with_lse,
                          **call.kernel_args()),
        grid=(n_g, Tq // block.q, n_kb),
        in_specs=[q_spec, k_spec, k_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((lay.heads, block.q, LANES), jnp.float32),  # m
            pltpu.VMEM((lay.heads, block.q, LANES), jnp.float32),  # l
            pltpu.VMEM((block.q, call.width), jnp.float32),  # acc
        ],
        compiler_params=_compiler_params(),
        cost_estimate=cost,
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return tuple(out) if with_lse else out[0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, scale: float, fold_scale: bool,
                causal: bool, block: Blocks, heads: int,
                lanes: Tuple[int, int, int], n_qb: int):
    """Grid (rows of heads, k blocks, q blocks), q innermost; dK and dV of
    one k block accumulate in fp32 scratch over the q blocks. Pieces are
    transposed, ``[tk, tq]``: lse and delta are ``[1, block_q]`` rows."""
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    tq, tk = block.tiles
    guard = _lane_guard(*lanes)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _step(diagonal: bool):
        for j in range(block.k // tk):
            cols = slice(j * tk, (j + 1) * tk)
            k = guard(k_ref[0, cols, :])              # [tk, heads*D]
            v = guard(v_ref[0, cols, :])
            if fold_scale:
                k = k * scale
            k = [_head_lanes(k, h, heads) for h in range(heads)]
            v = [_head_lanes(v, h, heads) for h in range(heads)]
            dk, dv = dk_acc[cols, :], dv_acc[cols, :]
            # on the diagonal, the pieces above piece (j, j) are dead
            for i in range(j if diagonal else 0, block.q // tq):
                rows = slice(i * tq, (i + 1) * tq)
                q, do = guard(q_ref[0, rows, :]), guard(do_ref[0, rows, :])
                dk_h, dv_h = [], []
                for h in range(heads):
                    s_t = _dot(k[h], q, _NT)          # [tk, tq] fp32
                    if not fold_scale:
                        s_t = s_t * scale
                    if diagonal and i == j:
                        s_t = _causal_where(s_t, qi * block.q + i * tq,
                                            kj * block.k + j * tk, 1)
                    p_t = jnp.exp(s_t - lse_ref[0, h:h + 1, rows])
                    dv_h.append(_dot(p_t.astype(do.dtype), do, _NN))
                    dp_t = _dot(v[h], do, _NT)        # [tk, tq]
                    ds_t = p_t * (dp_t - delta_ref[0, h:h + 1, rows])
                    dk_h.append(_dot(ds_t.astype(q.dtype), q, _NN))
                dk, dv = dk + _by_head(dk_h), dv + _by_head(dv_h)
            dk_acc[cols, :], dv_acc[cols, :] = dk, dv

    _run_live(_step, causal, qi * block.q, kj * block.k, block)

    @pl.when(qi == n_qb - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, lse_col, delta_col, *, scale: float,
               fold_scale: bool, causal: bool, block: Blocks, heads: int,
               lanes: Tuple[int, int, int], n_kb: int):
    """Grid (rows of heads, q blocks, k blocks), k innermost; dQ of one q
    block accumulates in fp32 scratch. Pieces are ``[tq, tk]``, so the
    statistics' rows are turned into lane-replicated columns once a q
    block."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    tq, tk = block.tiles
    guard = _lane_guard(*lanes)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        for h in range(heads):
            lse_col[h] = _row_to_col(lse_ref[0, h:h + 1, :])
            delta_col[h] = _row_to_col(delta_ref[0, h:h + 1, :])

    def _step(diagonal: bool):
        for i in range(block.q // tq):
            rows = slice(i * tq, (i + 1) * tq)
            q, do = guard(q_ref[0, rows, :]), guard(do_ref[0, rows, :])
            if fold_scale:
                q = q * scale
            q = [_head_lanes(q, h, heads) for h in range(heads)]
            do = [_head_lanes(do, h, heads) for h in range(heads)]
            lse = [lse_col[h, rows, :1] for h in range(heads)]
            delta = [delta_col[h, rows, :1] for h in range(heads)]
            dq = dq_acc[rows, :]
            for j in range(i + 1 if diagonal else block.k // tk):
                cols = slice(j * tk, (j + 1) * tk)
                k, v = guard(k_ref[0, cols, :]), guard(v_ref[0, cols, :])
                dq_h = []
                for h in range(heads):
                    s = _dot(q[h], k, _NT)            # [tq, tk] fp32
                    if not fold_scale:
                        s = s * scale
                    if diagonal and j == i:
                        s = _causal_where(s, qi * block.q + i * tq,
                                          ki * block.k + j * tk, 0)
                    p = jnp.exp(s - lse[h])
                    dp = _dot(do[h], v, _NT)          # [tq, tk]
                    ds = p * (dp - delta[h])
                    dq_h.append(_dot(ds.astype(k.dtype), k, _NN))
                dq = dq + _by_head(dq_h)
            dq_acc[rows, :] = dq

    _run_live(_step, causal, qi * block.q, ki * block.k, block)

    @pl.when(ki == n_kb - 1)
    def _finalize():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _dkv_call(q, k, v, do, lse, delta, *, lay: Layout, head_dim: int,
              causal: bool, block: Blocks, cost: pl.CostEstimate,
              interpret: bool):
    """Kernel-layout operands and statistics -> (dk, dv)."""
    Tq, Tk = q.shape[1], k.shape[1]
    call = _Call.of(q, lay, causal, block, head_dim)
    q_spec, k_spec, row_spec = call.specs(q_major=False)
    return pl.pallas_call(
        functools.partial(_dkv_kernel, n_qb=Tq // block.q,
                          **call.kernel_args()),
        grid=(q.shape[0] * lay.groups, Tk // block.k, Tq // block.q),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=[k_spec, k_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block.k, call.width), jnp.float32),
                        pltpu.VMEM((block.k, call.width), jnp.float32)],
        compiler_params=_compiler_params(),
        cost_estimate=cost,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)


def _dq_call(q, k, v, do, lse, delta, *, lay: Layout, head_dim: int,
             causal: bool, block: Blocks, cost: pl.CostEstimate,
             interpret: bool):
    """Kernel-layout operands and statistics -> dq."""
    Tq, Tk = q.shape[1], k.shape[1]
    call = _Call.of(q, lay, causal, block, head_dim)
    q_spec, k_spec, row_spec = call.specs(q_major=True)
    return pl.pallas_call(
        functools.partial(_dq_kernel, n_kb=Tk // block.k,
                          **call.kernel_args()),
        grid=(q.shape[0] * lay.groups, Tq // block.q, Tk // block.k),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block.q, call.width), jnp.float32),
            pltpu.VMEM((lay.heads, block.q, LANES), jnp.float32),
            pltpu.VMEM((lay.heads, block.q, LANES), jnp.float32)],
        compiler_params=_compiler_params(),
        cost_estimate=cost,
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)


# ---------------------------------------------------------------------------
# the custom VJP, in the mha layout
# ---------------------------------------------------------------------------

def to_kernel_layout(x: jax.Array, lay: Layout) -> jax.Array:
    """[B, T, H, D] -> [B, T, H*D] (a view) or, transposed, [B*H, T, D]."""
    B, T, H, D = x.shape
    if lay.in_place:
        return x.reshape(B, T, H * D)
    return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)


def from_kernel_layout(x: jax.Array, lay: Layout, batch: int,
                       head_dim: int) -> jax.Array:
    T = x.shape[1]
    if lay.in_place:
        return x.reshape(batch, T, -1, head_dim)
    return x.reshape(batch, -1, T, head_dim).transpose(0, 2, 1, 3)


def _forward(q, k, v, causal, blocks, interpret, with_lse):
    """(o in the mha layout, (q, k, v) in the kernels', lse or None)."""
    B, Tq, H, D = q.shape
    lay = layout(H, D)
    qkv = tuple(to_kernel_layout(x, lay) for x in (q, k, v))
    out = _fwd_call(
        *qkv, lay=lay, head_dim=D, causal=causal, block=blocks.fwd,
        cost=flash_cost(B, H, Tq, k.shape[1], D, causal,
                        q.dtype)["flash_fwd"],
        interpret=interpret, with_lse=with_lse)
    o, lse = out if with_lse else (out, None)
    return from_kernel_layout(o, lay, B, D), qkv, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_attention_cvjp(q, k, v, causal, blocks, interpret):
    return _forward(q, k, v, causal, blocks, interpret, with_lse=False)[0]


# The differentiated forward names its two outputs, so that a remat policy
# can keep them: both come out of a custom call, which no policy over dots
# sees, and without them the backward pass runs ``flash_fwd`` a second time.
# ``save_flash_residuals`` is the ``jax.checkpoint`` policy that keeps those
# two and nothing else (``save_from_both_policies`` adds it to another); the
# names are inert where no policy asks for them.
_RESIDUAL_NAMES = ("flash_o", "flash_lse")
save_flash_residuals = jax.checkpoint_policies.save_only_these_names(
    *_RESIDUAL_NAMES)


def _vjp_fwd(q, k, v, causal, blocks, interpret):
    o, qkv, lse = _forward(q, k, v, causal, blocks, interpret, with_lse=True)
    o, lse = (checkpoint_name(x, name)
              for x, name in zip((o, lse), _RESIDUAL_NAMES))
    return o, (qkv, o, lse)


def _vjp_bwd(causal, blocks, interpret, residuals, g):
    (q, k, v), o, lse = residuals
    B, Tq, H, D = g.shape
    lay = layout(H, D)
    # delta = rowsum(dO * O), fp32, in the layout both arrive in; then one
    # row a head like the log-sum-exp (whose last lane block may hold a
    # head that is not there: its delta is 0)
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    head_rows = lse.shape[0] * lse.shape[1] // B
    delta = jnp.pad(delta.transpose(0, 2, 1),
                    ((0, 0), (0, head_rows - H), (0, 0))).reshape(lse.shape)
    costs = flash_cost(B, H, Tq, k.shape[1], D, causal, q.dtype)
    operands = (q, k, v, to_kernel_layout(g, lay), lse, delta)
    common = dict(lay=lay, head_dim=D, causal=causal, interpret=interpret)
    dk, dv = _dkv_call(*operands, block=blocks.dkv,
                       cost=costs["flash_bwd_dkv"], **common)
    dq = _dq_call(*operands, block=blocks.dq, cost=costs["flash_bwd_dq"],
                  **common)
    return tuple(from_kernel_layout(x, lay, B, D) for x in (dq, dk, dv))


_flash_attention_cvjp.defvjp(_vjp_fwd, _vjp_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Fused attention, differentiable. q,k,v: [B, T, H, D]; matches
    ``mha`` numerically (products in the inputs' dtype summed in fp32, fp32
    softmax). Blocks come from ``block_sizes``; an explicit ``block_q`` /
    ``block_k`` overrides every kernel's, clamps to the sequence length and
    must then divide it (static shapes; the grid can't tile ragged tails)."""
    Tq, Tk, D = q.shape[1], k.shape[1], q.shape[-1]
    blocks = block_sizes(Tq, Tk, D, q.dtype)
    if block_q is not None or block_k is not None:
        block_q = min(block_q or blocks.fwd.q, Tq)
        block_k = min(block_k or blocks.fwd.k, Tk)
        if Tq % block_q != 0:
            raise ValueError(
                f"q length {Tq} not divisible by block_q {block_q}")
        if Tk % block_k != 0:
            raise ValueError(
                f"k length {Tk} not divisible by block_k {block_k}")
        blocks = BlockSizes(*(Blocks(block_q, block_k, b.tile)
                              for b in blocks))
    if interpret is None:
        interpret = _should_interpret()
    return _flash_attention_cvjp(q, k, v, causal, blocks, interpret)


def flash_attention_per_shard(q: jax.Array, k: jax.Array, v: jax.Array,
                              mesh: Optional[Any], spec: Any) -> jax.Array:
    """Causal flash attention, per shard when ``mesh`` spans devices.

    XLA cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"), so under a multi-device mesh the kernel
    runs inside ``shard_map``: each device attends over its own slice of
    ``spec`` (q, k, v ``[B, T, H, D]``: rows and heads, which never
    interact inside attention, so no collective is needed; the sequence
    stays whole). The kernel's custom VJP is differentiated inside the
    ``shard_map``, so the backward kernels run per shard too. Blocks are
    the kernel's own choice, from the shard's shapes."""
    attend = functools.partial(flash_attention, causal=True)
    if mesh is None or mesh.size == 1:
        return attend(q, k, v)
    return jax.shard_map(
        attend, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
        check_vma=False)(q, k, v)
