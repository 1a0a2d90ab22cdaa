#!/usr/bin/env python3
"""Time the chunked delta rule of a Kimi-Linear prefill slice on the
attached chip over heads a grid step — the sweep
``ops/kda.py:HEADS_A_STEP`` holds the result of (PERF.md section 6).

    python tools/kda_sweep.py [--lengths 512,1024,2048] [--heads 1,2,4,8]
        [--out chiprun_out/kda_sweep.json]

The shapes are ``kimi-linear-48b-a3b.serve-longdoc-closed``'s: one row of
``T`` tokens, 32 heads of 128 channels, chunks of 64, float32. Log decays
are drawn as the model's are (``-exp(log_a) softplus(.)``, a head's rate
between 1e-3 and 1e2 a token), keys of unit length. One program calls the
slice form ``--layers`` times, a call's state the next one's, so no dispatch
gap is counted and the program's round trip is spread over the calls; best
of ``--repeats``. Each row gives the milliseconds a layer and the share of
the least time ``benchmarks/harness/kda.py:chunk_form_cost`` allows (the
larger of its operations at the bfloat16 peak and its bytes at the memory's
peak). The first row holds the kernel's outputs and state over 512 tokens,
and the one-token form's scanned over the same tokens, against a float64
recurrence on the host (and XLA's running sum of the log decays against
numpy's). Needs a TPU; prints one JSON line a measurement.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HEADS, HEAD_DIM = 32, 128


def slice_inputs(key, T: int):
    """(q, k, v, g, b, state) of one row of ``T`` tokens."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(key, 7)
    shape = (1, T, HEADS, HEAD_DIM)
    q, k, v = (jax.random.normal(x, shape) for x in ks[:3])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * HEAD_DIM ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    rate = jnp.exp(jax.random.uniform(ks[3], (HEADS, 1), minval=-7.0,
                                      maxval=4.6))
    g = -rate * jax.nn.softplus(jax.random.normal(ks[4], shape))
    b = jax.nn.sigmoid(2 * jax.random.normal(ks[5], shape[:3]))
    state = jax.random.normal(ks[6], (1, HEADS, HEAD_DIM, HEAD_DIM))
    return q, k, v, g, b, state


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--lengths", default="512,1024,2048")
    parser.add_argument("--heads", default="1,2,4,8")
    parser.add_argument("--layers", type=int, default=16)
    parser.add_argument("--repeats", type=int, default=5)
    parser.add_argument("--out", default="chiprun_out/kda_sweep.json")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness.kda import chunk_form_cost
    from determined_clone_tpu.ops import kda as ops_kda
    from determined_clone_tpu.telemetry import flops
    from tools.paged_sweep import _best_ms

    if jax.default_backend() != "tpu":
        print("kda_sweep.py times a chip; none is attached", file=sys.stderr)
        return 2
    kind = flops.TPU_DEVICE_KINDS[jax.devices()[0].device_kind]
    config = {"linear_attn_config": {
        "num_heads": HEADS, "head_dim": HEAD_DIM, "kda_layers": [0]}}
    out_rows = []

    def emit(**row):
        out_rows.append(row)
        print(json.dumps(row), flush=True)

    def layers_of(heads):
        def run(q, k, v, g, b, state):
            def body(_, carry):
                o, state = ops_kda._chunked(q + carry[0] * 0, k, v, g, b,
                                            carry[1], ops_kda.CHUNK,
                                            heads=heads)
                return o, state
            return jax.lax.fori_loop(0, args.layers, body,
                                     (jnp.zeros_like(q), state))
        return jax.jit(run)

    q, k, v, g, b, state = slice_inputs(jax.random.PRNGKey(0), 512)
    mask = jnp.ones((1, 512), bool)

    def one_token(state, xs):
        o, state = ops_kda.kda(*(x[:, None] for x in xs), state, mask[:, :1])
        return state, o[:, 0]
    s_step, o_step = jax.jit(lambda *a: jax.lax.scan(
        one_token, a[5], tuple(jnp.moveaxis(x, 1, 0) for x in a[:5])))(
            q, k, v, g, b, state)
    o, s = jax.jit(ops_kda.kda)(q, k, v, g, b, state, mask)
    q64, k64, v64, g64, b64, S = (np.asarray(x, np.float64)[0] for x in (
        q, k, v, g, b, state))
    o64 = np.zeros_like(v64)
    for t in range(512):
        S = np.exp(g64[t])[:, :, None] * S
        u = b64[t][:, None] * (v64[t] - np.einsum("hk,hkv->hv", k64[t], S))
        S = S + k64[t][:, :, None] * u[:, None, :]
        o64[t] = np.einsum("hk,hkv->hv", q64[t], S)
    G_dev = jnp.cumsum(g.reshape(1, 8, 64, -1), axis=2)
    G64 = np.cumsum(np.asarray(g, np.float64).reshape(1, 8, 64, -1), axis=2)
    emit(form="against_float64", tokens=512,
         kernel_out=float(np.abs(np.asarray(o)[0] - o64).max()),
         kernel_state=float(np.abs(np.asarray(s)[0] - S).max()),
         one_token_out=float(np.abs(np.asarray(o_step)[:, 0] - o64).max()),
         one_token_state=float(np.abs(np.asarray(s_step)[0] - S).max()),
         state_max=float(np.abs(S).max()),
         cumsum_max_diff=float(np.abs(np.asarray(G_dev) - G64).max()),
         cumsum_max_rel=float((np.abs(np.asarray(G_dev) - G64)
                               / np.abs(G64)).max()))
    for T in (int(t) for t in args.lengths.split(",")):
        operands = slice_inputs(jax.random.PRNGKey(T), T)
        ops, nbytes = chunk_form_cost(T, config)
        least = max(ops / flops.TPU_PEAK_BF16_FLOPS[kind],
                    nbytes / flops.TPU_HBM_BYTES_PER_S[kind])
        for heads in (int(h) for h in args.heads.split(",")):
            try:
                ms = _best_ms(layers_of(heads), operands, args.repeats)
                emit(tokens=T, heads_a_step=heads,
                     ms_per_layer=round(ms / args.layers, 4),
                     roofline_pct=round(
                         100 * least / (ms / args.layers / 1e3), 2))
            except Exception as e:  # noqa: BLE001 - sizes the chip refuses
                emit(tokens=T, heads_a_step=heads,
                     error=str(e).splitlines()[0][:200])
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out_rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
