#!/usr/bin/env python3
"""Time the flash-attention kernels on the attached chip over a grid of
block sizes — the sweep ``ops/flash_attention.py:block_sizes`` holds the
rule of (PERF.md section 6).

    python tools/flash_sweep.py [--shapes 8x1024x16x64,2x1024x25x64]
        [--blocks 128,256,512,1024] [--tiles 128,256,512,1024]
        [--out chiprun_out/flash_sweep.json]

Each kernel is timed alone on bf16 operands in the layout the kernels read
(``[B, T, H*D]`` or ``[B*H, T, D]``: ``layout``), ``--chain``
calls back to back inside one program (a call's result feeds the next, so
no dispatch gap is counted), best of ``--repeats``. The last rows time the
whole ``jax.grad`` of ``flash_attention`` in the ``[B, T, H, D]`` layout
with the rule's own blocks: kernels plus the layout changes around them.
Needs a TPU; prints one JSON line a measurement.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _best_ms(fn, args, chain: int, repeats: int) -> float:
    import jax

    jax.block_until_ready(fn(*args))  # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return 1e3 * best / chain


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--shapes", default="8x1024x16x64,2x1024x25x64")
    parser.add_argument("--blocks", default="128,256,512,1024")
    parser.add_argument("--tiles", default="128,256,512,1024",
                        help="pieces a square block is worked through in; "
                        "a block no larger than a tile runs whole")
    parser.add_argument("--chain", type=int, default=16)
    parser.add_argument("--repeats", type=int, default=5)
    parser.add_argument("--out", default="chiprun_out/flash_sweep.json")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from determined_clone_tpu.ops import flash_attention as fm

    if jax.default_backend() != "tpu":
        print("flash_sweep.py times a chip; none is attached",
              file=sys.stderr)
        return 2
    sizes = [int(b) for b in args.blocks.split(",")]
    tiles = [int(t) for t in args.tiles.split(",")]
    rows = []

    def emit(**row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    for shape in args.shapes.split(","):
        B, T, H, D = (int(n) for n in shape.split("x"))
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        lay = fm.layout(H, D)
        q, k, v, do = (fm.to_kernel_layout(
            jax.random.normal(key, (B, T, H, D), jnp.bfloat16), lay)
            for key in keys)
        costs = fm.flash_cost(B, H, T, T, D, True, q.dtype)
        common = dict(lay=lay, head_dim=D, causal=True, interpret=False)
        o, lse = fm._fwd_call(q, k, v, block=fm.Blocks(128, 128, 128),
                              cost=costs["flash_fwd"], with_lse=True,
                              **common)
        delta = jnp.sum((do.astype(jnp.float32) * o.astype(jnp.float32)
                         ).reshape(B, T, H, D), axis=-1)
        head_rows = lse.shape[0] * lse.shape[1] // B
        delta = jnp.pad(delta.transpose(0, 2, 1), (
            (0, 0), (0, head_rows - H), (0, 0))).reshape(lse.shape)
        seen = set()
        for bq, bk, tile in itertools.product(sizes, sizes, tiles):
            blk = fm.Blocks(bq, bk, tile)
            if bq > T or bk > T or (bq, bk, blk.tiles) in seen:
                continue        # e.g. a block no tile divides runs whole
            seen.add((bq, bk, blk.tiles))

            def fwd(q, k, v):
                def body(_, q):
                    return fm._fwd_call(
                        q, k, v, block=blk, cost=costs["flash_fwd"],
                        with_lse=True, **common)[0]
                return jax.lax.fori_loop(0, args.chain, body, q)

            def dkv(q, k, v, do, lse, delta):
                def body(_, kv):
                    return tuple(fm._dkv_call(
                        q, kv[0], kv[1], do, lse, delta, block=blk,
                        cost=costs["flash_bwd_dkv"], **common))
                return jax.lax.fori_loop(0, args.chain, body, (k, v))

            def dq(q, k, v, do, lse, delta):
                def body(_, q):
                    return fm._dq_call(
                        q, k, v, do, lse, delta, block=blk,
                        cost=costs["flash_bwd_dq"], **common)
                return jax.lax.fori_loop(0, args.chain, body, q)

            for name, fn, operands in (
                    ("flash_fwd", fwd, (q, k, v)),
                    ("flash_bwd_dkv", dkv, (q, k, v, do, lse, delta)),
                    ("flash_bwd_dq", dq, (q, k, v, do, lse, delta))):
                try:
                    ms = _best_ms(jax.jit(fn), operands, args.chain,
                                  args.repeats)
                    emit(shape=shape, kernel=name, block_q=bq, block_k=bk,
                         tile=list(blk.tiles), ms=round(ms, 4))
                except Exception as e:  # noqa: BLE001 - a refused block
                    emit(shape=shape, kernel=name, block_q=bq, block_k=bk,
                         tile=list(blk.tiles),
                         error=str(e).splitlines()[0][:200])

        # the whole op in the model's layout, rule's blocks
        x = [jax.random.normal(key, (B, T, H, D), jnp.bfloat16)
             for key in keys[:3]]

        def chained(fn):
            def run(q, k, v):
                def body(_, qkv):
                    return tuple(fn(*qkv))
                return jax.lax.fori_loop(0, args.chain, body, (q, k, v))
            return jax.jit(run)

        grad = jax.grad(lambda q, k, v: fm.flash_attention(q, k, v).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))
        emit(shape=shape, kernel="grad_in_model_layout",
             blocks=[list(b) for b in fm.block_sizes(T, T, D, jnp.bfloat16)],
             ms=round(_best_ms(chained(grad), x, args.chain, args.repeats),
                      4))
        emit(shape=shape, kernel="fwd_in_model_layout",
             ms=round(_best_ms(
                 chained(lambda q, k, v: (fm.flash_attention(q, k, v), k, v)),
                 x, args.chain, args.repeats), 4))
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
