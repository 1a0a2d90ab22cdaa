#!/usr/bin/env python3
"""Time the two forms of ``ops/window_attention.py`` on the attached chip at
the shapes of ``trinity-large-preview.serve-mixed-closed`` over their pass
sizes — the sweep the defaults ``key_blocks`` and ``q_block`` hold the
result of (PERF.md section 6).

    python tools/window_attention_sweep.py [--out chiprun_out/window_attention_sweep.json]

48 query heads over 8 KV heads of 128, K and V rows of 1024 bfloat16 in
blocks of 64, tables in scrambled order:

- ``decode``: 16 rows, a sliding layer's window (a ring of 96 blocks; 4096
  rows a row from position 30000 - 4096, across the wrap) and a full
  layer's context (a table of 544 blocks, lengths 1 k-34 k, 10 k on
  average), over ``key_blocks``; each row gives the milliseconds a layer
  and ``hbm_pct``, the attended rows' 4096 B over 819 GB/s over the time;
- ``slice``: one row's 2048 queries at positions 16384.., a sliding layer
  (6143 keys) and a full layer (18432 keys), over ``q_block`` x
  ``key_blocks`` (16, 32); ``mxu_pct`` is the attended (query, key) pairs x 48 heads
  x 128 x 4 operations over 197 TFLOP/s over the time.

One program calls a form ``--layers`` times, each call's query nudged by
the last one's result so that nothing is hoisted; best of ``--repeats``.
Needs a TPU; prints one JSON line a measurement.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BLOCK, HQ, HKV, D = 64, 48, 8, 128
R = HKV * D
WINDOW, RING, CONTEXT = 4096, 6144, 34816


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--layers", type=int, default=8)
    parser.add_argument("--repeats", type=int, default=3)
    parser.add_argument("--out", default="chiprun_out/"
                        "window_attention_sweep.json")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from determined_clone_tpu.ops import window_attention as wa

    if jax.devices()[0].platform != "tpu":
        print("window_attention_sweep: needs a TPU", file=sys.stderr)
        return 1
    rng = np.random.default_rng(0)
    n_blocks = 16 * (CONTEXT // BLOCK)
    key_k, key_v, key_q1, key_q2 = jax.random.split(jax.random.PRNGKey(0), 4)
    k_pool, v_pool = (jax.random.normal(k, (n_blocks, BLOCK, R),
                                        jnp.bfloat16)
                      for k in (key_k, key_v))
    order = rng.permutation(n_blocks).astype(np.int32)
    full_tables = jnp.asarray(order.reshape(16, -1))
    ring_tables = jnp.asarray(order.reshape(16, -1)[:, :RING // BLOCK])
    lengths = np.clip(rng.lognormal(np.log(8192), 0.9, 16), 1024,
                      CONTEXT).astype(np.int32)
    lengths[0] = CONTEXT
    rows = []

    def timed(fn, *operands):
        best = float("inf")
        jax.block_until_ready(fn(*operands))
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*operands))
            best = min(best, time.perf_counter() - t0)
        return 1e3 * best / args.layers

    def report(**row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    q1 = jax.random.normal(key_q1, (16, HQ, D), jnp.bfloat16)
    cases = {
        "window": (ring_tables, np.full(16, 30000 - WINDOW, np.int32),
                   np.full(16, 30000, np.int32)),
        "full": (full_tables, np.zeros(16, np.int32), lengths)}
    for name, (tables, lo, hi) in cases.items():
        attended = int((hi - lo).sum())
        for nb in (8, 16, 32, 64):
            @jax.jit
            def decode(q, k, v, tables, lo, hi, nb=nb):
                def layer(q, _):
                    o = wa.decode_rows(q, k, v, tables, lo, hi,
                                       key_blocks=nb)
                    return (q + 1e-3 * o.astype(q.dtype)), None
                return jax.lax.scan(layer, q, None, length=args.layers)[0]

            ms = timed(decode, q1, k_pool, v_pool, tables, jnp.asarray(lo),
                       jnp.asarray(hi))
            report(form="decode", layer=name, key_blocks=nb, ms=ms,
                   rows=attended,
                   hbm_pct=100 * attended * 2 * R * 2 / 819e9 / (ms / 1e3))

    T, start = 2048, 16384
    q2 = jax.random.normal(key_q2, (1, T, HQ, D), jnp.bfloat16)
    positions = jnp.arange(start, start + T, dtype=jnp.int32)[None]
    mask = jnp.ones((1, T), bool)
    for name, tables, window in (("window", ring_tables[:1], WINDOW),
                                 ("full", full_tables[:1], None)):
        at = np.arange(start, start + T)
        pairs = int(np.minimum(at + 1, window or CONTEXT).sum())
        for tq in (256, 512, 1024):
            for nb in (16, 32):
                @jax.jit
                def sliced(q, k, v, tables, tq=tq, nb=nb, window=window):
                    def layer(q, _):
                        o = wa.slice_rows(q, k, v, tables, positions, mask,
                                          window=window, q_block=tq,
                                          key_blocks=nb)
                        return (q + 1e-3 * o.astype(q.dtype)), None
                    return jax.lax.scan(layer, q, None,
                                        length=args.layers)[0]

                try:
                    ms = timed(sliced, q2, k_pool, v_pool, tables)
                except Exception as e:  # noqa: BLE001 - out of memory
                    report(form="slice", layer=name, q_block=tq,
                           key_blocks=nb, error=repr(e)[:200])
                    continue
                report(form="slice", layer=name, q_block=tq, key_blocks=nb,
                       ms=ms, pairs=pairs, mxu_pct=100 * pairs * HQ * D * 4
                       / 197e12 / (ms / 1e3))
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
