#!/usr/bin/env python3
"""Time the paged decode-attention kernel on the attached chip over rows a
grid step and positions a product — the sweep
``ops/paged_attention.py:sizes`` holds the rule of (PERF.md section 6).

    python tools/paged_sweep.py [--shapes 32x16x64,8x25x64]
        [--rows 1,2,4,8] [--chunks 128,256,512]
        [--out chiprun_out/paged_sweep.json]

A shape is batch x heads x head_dim. The pool holds ``--layers`` layers of
bf16 blocks of 16 positions, tables 64 wide (1024 positions), the block ids
shuffled; the rows' lengths are drawn as ``chat-closed`` draws them (prompt
and reply log-normal, a row seen at a uniform point of its reply: a mean of
about 200 positions), the same for every size. One program calls the kernel
once a layer, each call's result the next one's query, so no dispatch gap is
counted; best of ``--repeats``. The last rows time the plain form the kernel
replaces (gather of every table, ``decode_attention_rows``) and the kernel at
the rule's own sizes, full tables and a batch half padding.
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

BLOCK, WIDTH = 16, 64


def _best_ms(fn, args, repeats: int) -> float:
    import jax

    jax.block_until_ready(fn(*args))  # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return 1e3 * best


def chat_lengths(rng, batch: int):
    """Context lengths of ``batch`` rows in a decode step of chat-closed."""
    import numpy as np

    prompt = np.clip(rng.lognormal(np.log(96), 0.8, batch), 16, 512)
    reply = np.clip(rng.lognormal(np.log(96), 0.7, batch), 16, 256)
    return np.minimum(prompt + rng.uniform(0, 1, batch) * reply,
                      BLOCK * WIDTH).astype(np.int32)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--shapes", default="32x16x64,8x25x64")
    parser.add_argument("--rows", default="1,2,4,8")
    parser.add_argument("--chunks", default="128,256,512")
    parser.add_argument("--layers", type=int, default=24)
    parser.add_argument("--repeats", type=int, default=5)
    parser.add_argument("--out", default="chiprun_out/paged_sweep.json")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from determined_clone_tpu.ops import paged_attention as pa
    from determined_clone_tpu.ops.attention import decode_attention_rows
    from determined_clone_tpu.telemetry import flops

    if jax.default_backend() != "tpu":
        print("paged_sweep.py times a chip; none is attached",
              file=sys.stderr)
        return 2
    hbm_bytes_per_s = flops.TPU_HBM_BYTES_PER_S[
        flops.TPU_DEVICE_KINDS[jax.devices()[0].device_kind]]
    out_rows = []

    def emit(**row):
        out_rows.append(row)
        print(json.dumps(row), flush=True)

    for shape in args.shapes.split(","):
        B, H, D = (int(n) for n in shape.split("x"))
        R = -(-H * D // 128) * 128
        L, N = args.layers, B * WIDTH
        rng = np.random.default_rng(0)
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        k_pool, v_pool = (
            jax.random.normal(key, (L * N, BLOCK, R), jnp.bfloat16)
            .at[..., H * D:].set(0) for key in keys[:2])
        q = jax.random.normal(keys[2], (B, 1, H, D), jnp.bfloat16)
        tables = jnp.asarray(rng.permutation(N).reshape(B, WIDTH), jnp.int32)
        full = np.full(B, BLOCK * WIDTH, np.int32)
        half = np.where(np.arange(B) < -(-B // 2), chat_lengths(rng, B), 0)
        drawn = chat_lengths(rng, B)

        def layers_of(attend):
            """One program: ``attend(q, layer's tables)`` a layer."""
            def run(q, k_pool, v_pool, tables, lengths):
                def body(layer, q):
                    return attend(q, k_pool, v_pool, tables + layer * N,
                                  lengths)
                return jax.lax.fori_loop(0, L, body, q)
            return jax.jit(run)

        def kernel(sz):
            return layers_of(lambda q, k, v, t, n: pa.paged_attention(
                q, k, v, t, n, sz=sz))

        def plain(q, k, v, t, n):
            mask = jnp.arange(BLOCK * WIDTH)[None, :] < n[:, None]
            return decode_attention_rows(
                q, k[t].reshape(B, -1, R), v[t].reshape(B, -1, R),
                mask[:, None, None, :])

        def measure(name, fn, lengths, **sizes):
            operands = (q, k_pool, v_pool, tables, jnp.asarray(lengths))
            needed = pa.paged_cost(int(lengths.sum()), H * D, L, heads=H,
                                   dtype=jnp.bfloat16).bytes_accessed
            try:
                ms = _best_ms(fn, operands, args.repeats)
                emit(shape=shape, form=name, **sizes,
                     mean_length=round(float(lengths.mean()), 1),
                     ms_per_layer=round(ms / L, 4), ms=round(ms, 3),
                     hbm_roofline_pct=round(
                         100 * needed / hbm_bytes_per_s / (ms / 1e3), 1))
            except Exception as e:  # noqa: BLE001 - sizes the chip refuses
                emit(shape=shape, form=name, **sizes,
                     error=str(e).splitlines()[0][:200])

        for rows, chunk in itertools.product(
                (int(r) for r in args.rows.split(",")),
                (int(c) for c in args.chunks.split(","))):
            if B % rows:
                continue
            sz = pa.Sizes(rows, chunk, -(-BLOCK * WIDTH // chunk))
            measure("kernel", kernel(sz), drawn, rows=rows, chunk=chunk)
        rule = pa.sizes(WIDTH, BLOCK)
        live = drawn > 0
        emit(shape=shape, form="kernel_rule_against_plain",
             max_abs_diff=float(jnp.max(jnp.abs(
                 pa.paged_attention(q, k_pool, v_pool, tables,
                                    jnp.asarray(drawn))[live].astype(
                                        jnp.float32)
                 - plain(q, k_pool, v_pool, tables, jnp.asarray(drawn))[
                     live].astype(jnp.float32)))))
        for name, lengths in (("drawn", drawn), ("full_tables", full),
                              ("half_padding", half)):
            measure(f"kernel_rule_{name}", kernel(rule), lengths,
                    rows=rule.rows, chunk=rule.chunk)
        measure("plain_gather", layers_of(plain), drawn)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out_rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
