#!/usr/bin/env python3
"""Time a prefill slice's latent attention (``ops/mla_attention.py``'s
kernel ``mla_slice``) on the attached chip over its tiles and with parts of
its body taken out — the sweep ``SLICE_ROWS`` / ``SLICE_KEYS`` hold the
result of (PERF.md section 6).

    python tools/mla_slice_sweep.py [--queries 32,64,128] [--keys 256,512,1024]
        [--out chiprun_out/mla_slice_sweep.json]

Two shapes, one row of 2048 tokens each, rows of 640 of which 512 are
summed, blocks of 64, a table in scrambled order:

- ``glm`` (``glm-5.2.serve-agent-closed``): 64 heads, a table of 512
  blocks, the slice at positions 8192.., every query attending 2048 chosen
  positions of those before it (``dsa_index.select_mask`` of random
  scores);
- ``kimi`` (``kimi-linear-48b-a3b.serve-longdoc-closed``): 32 heads, a
  table of 800 blocks, the slice at positions 22528.., the causal mask.

One program calls the kernel ``--layers`` times (a call's tile counts wait
for the call before, so nothing is hoisted and no dispatch gap is counted);
best of ``--repeats``. Each row gives the milliseconds a layer and slice
and ``mxu_pct``, the products the call multiplied (key tiles run x rows x
positions x (640 + 512), two operations each; 640 alone with the second
product out) over 197 TFLOP/s. ``without`` names the parts left out:
``copy`` (the key tiles' copies from the pool: the products then read what
the slots hold), ``mask``, ``softmax`` (maximum, exponential, sums, the
accumulator's rescaling), ``weigh`` (the second product). A row
``plain_jax_loop`` times the loop the kernel replaced (the tests' oracle:
512 queries over 2048 positions a pass) on the same operands. The first rows
hold the kernel at its rule against a dense softmax over the gathered rows,
512 queries of each shape. Needs a TPU; prints one JSON line a measurement.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BLOCK, ROW, RANK, SCALE, DTYPE = 64, 640, 512, 192 ** -0.5, "bfloat16"
SHAPES = {  # heads, table width, first position, index_topk (None: causal)
    "glm": (64, 512, 8192, 2048),
    "kimi": (32, 800, 22528, None),
}


def slice_inputs(key, name: str, T: int):
    """(q [1, T, H, R], blocks, tables [1, W], allowed or None, positions,
    token_mask) of one row's slice of ``T`` tokens."""
    import jax
    import jax.numpy as jnp

    from determined_clone_tpu.ops import dsa_index

    heads, width, first, topk = SHAPES[name]
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (1, T, heads, ROW), DTYPE)
    blocks = jax.random.normal(ks[1], (width + 8, BLOCK, ROW), DTYPE)
    tables = jax.random.permutation(ks[2], width + 8)[None, :width].astype(
        jnp.int32)
    positions = first + jnp.arange(T, dtype=jnp.int32)[None]
    allowed = None
    if topk is not None:
        S = width * BLOCK
        scores = jnp.where(jnp.arange(S)[None, None] <= positions[..., None],
                           jax.random.uniform(ks[3], (1, T, S)),
                           dsa_index.NEG_INF)
        allowed = jax.jit(dsa_index.select_mask, static_argnums=1)(
            scores, topk)
    return q, blocks, tables, allowed, positions, jnp.ones((1, T), bool)


def dense(q, blocks, tables, allowed, positions, scale):
    """A softmax over the gathered rows, nothing tiled: [1, T, H, RANK]."""
    import jax
    import jax.numpy as jnp

    rows = blocks[tables[0]].reshape(-1, ROW)
    if allowed is None:
        allowed = jnp.arange(rows.shape[0])[None, None] \
            <= positions[..., None]
    s = jnp.einsum("bthr,sr->bhts", q, rows,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(allowed[:, None], s, -1e30), axis=-1)
    return jnp.einsum("bhts,sr->bthr", p.astype(rows.dtype), rows[:, :RANK],
                      preferred_element_type=jnp.float32)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--queries", default="32,64,128")
    parser.add_argument("--keys", default="256,512,1024")
    parser.add_argument("--tokens", type=int, default=2048)
    parser.add_argument("--layers", type=int, default=4)
    parser.add_argument("--repeats", type=int, default=3)
    parser.add_argument("--out", default="chiprun_out/mla_slice_sweep.json")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from determined_clone_tpu.ops import mla_attention as mla
    from determined_clone_tpu.telemetry import flops
    from tools.paged_sweep import _best_ms

    # the plain-JAX loop the kernel replaced lives on as the tests' oracle
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tests"))
    from test_mla_slice_kernel import plain_slice

    if jax.default_backend() != "tpu":
        print("mla_slice_sweep.py times a chip; none is attached",
              file=sys.stderr)
        return 2
    peak = flops.TPU_PEAK_BF16_FLOPS[
        flops.TPU_DEVICE_KINDS[jax.devices()[0].device_kind]]
    out_rows = []

    def emit(**row):
        out_rows.append(row)
        print(json.dumps(row), flush=True)

    for name in SHAPES:
        q, blocks, tables, allowed, positions, mask = slice_inputs(
            jax.random.PRNGKey(1), name, 512)
        got = jax.jit(lambda *a: mla.mla_slice(
            *a, scale=SCALE, rank=RANK))(q, blocks, tables, allowed,
                                         positions, mask)
        want = jax.jit(dense, static_argnums=5)(q, blocks, tables, allowed,
                                                positions, SCALE)
        emit(shape=name, form="rule_against_dense", tokens=512,
             max_abs_diff=float(jnp.max(jnp.abs(got - want))),
             max_abs=float(jnp.max(jnp.abs(want))))

    T = args.tokens
    for name, (heads, width, first, _) in SHAPES.items():
        q, blocks, tables, allowed, positions, mask = slice_inputs(
            jax.random.PRNGKey(0), name, T)
        q_heads_first = jnp.swapaxes(q, 1, 2)
        seen = positions[..., None] if allowed is None \
            else allowed.astype(jnp.int8)
        rule = mla.tiles(T, heads, width, BLOCK)

        def measure(tl, without):
            parts = mla._PARTS - set(without)
            tk = tl.key_blocks * BLOCK
            n_tiles = jnp.max(positions.reshape(1, -1, tl.queries),
                              axis=-1) // tk + 1
            width_ops = ROW + (RANK if "weigh" in parts else 0)
            ops = 2 * int(n_tiles.sum()) * tl.queries * heads * tk * width_ops

            def run(q, seen, blocks, tables, n_tiles):
                def body(_, out):
                    after = (out[0, 0, 0, 0] * 0).astype(jnp.int32)
                    return mla._slice_call(
                        q, seen, blocks, tables, n_tiles + after,
                        scale=SCALE, rank=RANK, tl=tl, parts=parts,
                        interpret=mla._should_interpret())
                return jax.lax.fori_loop(
                    0, args.layers, body,
                    jnp.zeros((1, heads, T, RANK), jnp.float32))
            row = dict(shape=name, tokens=T, queries=tl.queries, keys=tk,
                       without=sorted(without), rule=tl == rule)
            try:
                ms = _best_ms(jax.jit(run), (q_heads_first, seen, blocks,
                                             tables, n_tiles),
                              args.repeats) / args.layers
                emit(**row, ms_per_layer=round(ms, 3),
                     mxu_pct=round(100 * ops / peak / (ms / 1e3), 1))
            except Exception as e:  # noqa: BLE001 - sizes the chip refuses
                emit(**row, error=str(e).splitlines()[0][:200])

        def plain(q, blocks, tables, positions, mask, allowed=None):
            def body(_, out):
                after = (out[0, 0, 0, 0] * 0).astype(jnp.int32)
                return plain_slice(q, blocks, tables, allowed,
                                   positions + after, mask, scale=SCALE)
            return jax.lax.fori_loop(
                0, args.layers, body,
                jnp.zeros((1, T, heads, ROW), jnp.float32))
        operands = (q, blocks, tables, positions, mask) \
            + (() if allowed is None else (allowed,))
        ms = _best_ms(jax.jit(plain), operands, args.repeats) / args.layers
        emit(shape=name, tokens=T, form="plain_jax_loop",
             ms_per_layer=round(ms, 3))

        for without in ((), ("copy",), ("mask",), ("softmax",), ("weigh",),
                        ("mask", "softmax")):
            measure(rule, without)
        for tq, tk in itertools.product(
                (int(x) for x in args.queries.split(",")),
                (int(x) for x in args.keys.split(","))):
            tl = mla.Tiles(tq, tk // BLOCK)
            if tl != rule and T % tq == 0 and width % tl.key_blocks == 0:
                measure(tl, ())
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out_rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
