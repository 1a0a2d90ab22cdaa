#!/usr/bin/env python3
"""Time the EVA paged decode-attention kernel on the attached chip over
rows a buffer — the sweep ``ops/eva_paged_attention.py:sizes`` holds the
rule of (PERF.md section 6).

    python tools/eva_paged_sweep.py [--batch 8] [--chunks 128,256,512]
        [--out chiprun_out/eva_paged_sweep.json]

The shapes are ``evabyte-6.5b.serve-doc-closed``'s: 32 heads of 128, bf16
blocks of 16 rows, tables of 128 ring + 64 summary entries, a pool of
``batch`` tables in each of ``--layers`` layers, the block ids shuffled. The
rows' contexts are drawn as ``doc-closed`` draws them (a prompt of 2.3-12 k
bytes, a row seen at a uniform point of its reply), the same for every
size. One program calls the kernel once a layer, ``--passes`` times round
the layers, each call's result the next one's query, so no dispatch gap is
counted and the program's own round trip (0.45 ms) is spread over 64 calls;
best of ``--repeats``. The
last rows time the kernel at the rule's size on full tables and on a batch
half padding, and the plain form it replaces (gather of every table,
``eva_attention`` under the mask). Needs a TPU; prints one JSON line a
measurement.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BLOCK, RING, SUMMARIES, HEADS, HEAD_DIM = 16, 128, 64, 32, 128
WINDOW, WIDTH = RING * BLOCK, RING + SUMMARIES


def doc_rows(rng, batch: int):
    """(window rows, summary rows) of ``batch`` rows in a decode step of
    doc-closed."""
    import numpy as np

    prompt = np.clip(rng.lognormal(np.log(4096), 0.6, batch), 2304, 12288)
    reply = np.clip(rng.lognormal(np.log(256), 0.5, batch), 64, 512)
    pos = (prompt + rng.uniform(0, 1, batch) * reply).astype(np.int32)
    return pos % WINDOW + 1, pos // WINDOW * (WINDOW // BLOCK)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--chunks", default="128,256,512")
    parser.add_argument("--layers", type=int, default=8)
    parser.add_argument("--passes", type=int, default=8)
    parser.add_argument("--repeats", type=int, default=5)
    parser.add_argument("--out", default="chiprun_out/eva_paged_sweep.json")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from determined_clone_tpu.ops import eva_paged_attention as epa
    from determined_clone_tpu.ops.attention import eva_attention
    from determined_clone_tpu.ops.paged_attention import paged_cost
    from determined_clone_tpu.telemetry import flops
    from tools.paged_sweep import _best_ms

    if jax.default_backend() != "tpu":
        print("eva_paged_sweep.py times a chip; none is attached",
              file=sys.stderr)
        return 2
    hbm_bytes_per_s = flops.TPU_HBM_BYTES_PER_S[
        flops.TPU_DEVICE_KINDS[jax.devices()[0].device_kind]]
    B, L, R = args.batch, args.layers, HEADS * HEAD_DIM
    calls = L * args.passes
    N = B * WIDTH
    rng = np.random.default_rng(0)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    k_pool, v_pool = (jax.random.normal(key, (L * N, BLOCK, R), jnp.bfloat16)
                      for key in keys[:2])
    q = jax.random.normal(keys[2], (B, 1, HEADS, HEAD_DIM), jnp.bfloat16)
    tables = jnp.asarray(rng.permutation(N).reshape(B, WIDTH), jnp.int32)
    drawn = doc_rows(rng, B)
    full = (np.full(B, WINDOW), np.full(B, SUMMARIES * BLOCK))
    half = tuple(np.where(np.arange(B) < -(-B // 2), n, 0) for n in drawn)
    out_rows = []

    def emit(**row):
        out_rows.append(row)
        print(json.dumps(row), flush=True)

    def layers_of(attend):
        """One program: ``attend(q, layer's tables)`` a layer."""
        def run(q, k_pool, v_pool, tables, window, summary):
            def body(call, q):
                return attend(q, k_pool, v_pool, tables + call % L * N,
                              window, summary)
            return jax.lax.fori_loop(0, calls, body, q)
        return jax.jit(run)

    def kernel(sz):
        return layers_of(lambda q, k, v, t, w, s: epa.eva_paged_attention(
            q, k, v, t, w, s, window_blocks=RING, sz=sz))

    def plain(q, k, v, t, w, s):
        mask = jnp.concatenate(
            [jnp.arange(WINDOW)[None] < w[:, None],
             jnp.arange(SUMMARIES * BLOCK)[None] < s[:, None]], axis=1)
        return eva_attention(q, k[t].reshape(B, -1, R),
                             v[t].reshape(B, -1, R), mask[:, None, :])

    def measure(name, fn, rows, **sizes):
        window, summary = (jnp.asarray(n, jnp.int32) for n in rows)
        attended = int(rows[0].sum() + rows[1].sum())
        needed = paged_cost(attended, R, calls, heads=HEADS,
                                dtype=jnp.bfloat16).bytes_accessed
        try:
            ms = _best_ms(fn, (q, k_pool, v_pool, tables, window, summary),
                          args.repeats)
            emit(form=name, **sizes, rows_a_row=attended // B,
                 ms_per_layer=round(ms / calls, 4), ms=round(ms, 3),
                 hbm_roofline_pct=round(
                     100 * needed / hbm_bytes_per_s / (ms / 1e3), 1))
        except Exception as e:  # noqa: BLE001 - sizes the chip refuses
            emit(form=name, **sizes, error=str(e).splitlines()[0][:200])

    for chunk in (int(c) for c in args.chunks.split(",")):
        sz = epa.Sizes(chunk, -(-WIDTH * BLOCK // chunk))
        measure("kernel", kernel(sz), drawn, chunk=chunk)
    rule = epa.sizes(WIDTH, BLOCK)
    window, summary = (jnp.asarray(n, jnp.int32) for n in drawn)
    emit(form="kernel_rule_against_plain", max_abs_diff=float(jnp.max(jnp.abs(
        epa.eva_paged_attention(q, k_pool, v_pool, tables, window, summary,
                                window_blocks=RING).astype(jnp.float32)
        - plain(q, k_pool, v_pool, tables, window, summary).astype(
            jnp.float32)))))
    for name, rows in (("drawn", drawn), ("full_tables", full),
                       ("half_padding", half)):
        measure(f"kernel_rule_{name}", kernel(rule), rows, chunk=rule.chunk)
    measure("plain_gather", layers_of(plain), drawn)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out_rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
