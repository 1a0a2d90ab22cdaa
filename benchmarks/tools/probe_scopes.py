#!/usr/bin/env python3
"""Record a small profiler trace of the program's own scopes and spans on
the chip: three steps of ``make_train_step`` over ``gpt.loss_fn`` and two of
``gpt.forward_paged`` at a small width, each inside the spans the trainer
and the engine open (``telemetry.Tracer``, which annotates the trace since
PR 25). The two files it writes are the ones kept beside
``benchmarks/tests/test_scopes.py``:

    probe2.xplane.pb    the trace, slimmed to what the readers read
    probe2.spans.json   the same spans as the tracer recorded them, on
                        time.monotonic, with the harness's sync mark

    python benchmarks/tools/probe_scopes.py <output directory>
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.adapters import gpt as adapter  # noqa: E402
from benchmarks.harness import device, scopes, trace  # noqa: E402

TRAIN_STEPS, DECODE_STEPS = 3, 2


def slim(path: str, kept: str) -> None:
    """Write what the readers read of the trace at ``path`` to ``kept``:
    the device planes' ``XLA Modules`` and ``XLA Ops`` lines with each
    operation's name and scope path, and the host's plane whole. The
    programs' HLO (``/host:metadata``, two thirds of the file) and the
    operations' other statistics (source lines, FLOP and byte counts) are
    left out, so that the file kept with the tests stays small."""
    with open(path, "rb") as f:
        space = scopes._xspace_class().FromString(f.read())
    for plane in list(space.planes):
        device = (plane.name.startswith(trace.DEVICE_PLANE)
                  and plane.name[len(trace.DEVICE_PLANE):].isdigit())
        if not device and plane.name != trace.HOST_PLANE:
            space.planes.remove(plane)
            continue
        if not device:
            continue
        tf_op = {e.key for e in plane.stat_metadata
                 if e.value.name == "tf_op"}
        for line in list(plane.lines):
            if line.name not in (scopes.MODULES_LINE, trace.OPS_LINE):
                plane.lines.remove(line)
        for entry in plane.event_metadata:
            for stat in list(entry.value.stats):
                if stat.metadata_id not in tf_op:
                    entry.value.stats.remove(stat)
    with open(kept, "wb") as f:
        f.write(space.SerializeToString())


def main() -> int:
    import jax
    import jax.numpy as jnp
    import optax

    from determined_clone_tpu.models import gpt
    from determined_clone_tpu.telemetry import Tracer
    from determined_clone_tpu.training.train_step import (
        create_train_state,
        make_train_step,
    )

    out = sys.argv[1]
    os.makedirs(out, exist_ok=True)
    if jax.default_backend() == "tpu":
        device.require_chips(1)
    # wide enough that a train step takes milliseconds: the device's events
    # lead the host's annotations by about a millisecond in a v5e trace
    cfg = gpt.GPTConfig(vocab_size=2048, n_layers=2, d_model=512, n_heads=8,
                        d_ff=2048, max_seq_len=512, remat=True,
                        attention_impl="auto")
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-3))
    state = create_train_state(params, tx, jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (8, 513), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}
    step = make_train_step(
        lambda p, b, rng: gpt.loss_fn(p, cfg, b["tokens"], b["targets"]),
        tx)

    serve_cfg = dataclasses.replace(cfg, remat=False)
    serve_params = jax.tree.map(jnp.copy, params)  # the step donates its own
    rows, blocks, block = 4, 64, 16
    pool = jnp.zeros((cfg.n_layers, blocks, block, cfg.n_heads,
                      cfg.head_dim), cfg.compute_dtype)
    k_pool, v_pool = pool, pool + 0
    fwd = jax.jit(gpt.forward_paged, static_argnums=(1,),
                  donate_argnums=(6, 7))
    tables = jnp.arange(rows * 16, dtype=jnp.int32).reshape(rows, 16)

    def decode(position, k_pool, v_pool):
        return fwd(serve_params, serve_cfg, jnp.ones((rows, 1), jnp.int32),
                   jnp.full((rows, 1), position, jnp.int32),
                   jnp.ones((rows, 1), bool), jnp.zeros((rows,), jnp.int32),
                   k_pool, v_pool, tables)

    # warm up both programs: nothing compiles inside the trace
    state, metrics = step(state, batch)
    logits, k_pool, v_pool = decode(0, k_pool, v_pool)
    jax.block_until_ready((metrics, logits))

    tracer = Tracer(enabled=True, process_name="probe")
    sync_t = time.monotonic()
    tracer.instant("bench_clock_sync")
    window = trace.TraceWindow(os.path.join(out, "probe"))
    window.start()
    for _ in range(TRAIN_STEPS):
        with tracer.span("train_dispatch"):
            state, metrics = step(state, batch)
            jax.block_until_ready(metrics)
        with tracer.span("host_sync"):
            time.sleep(0.001)
    for i in range(DECODE_STEPS):
        with tracer.span("engine_iteration"):
            with tracer.span("serving_decode_step", rows=rows, batch=rows):
                with tracer.span("decode_dispatch"):
                    logits, k_pool, v_pool = decode(1 + i, k_pool, v_pool)
                with tracer.span("decode_readback"):
                    jax.block_until_ready(logits)
            with tracer.span("decode_commit"):
                time.sleep(0.001)
    window.stop()

    (path,) = glob.glob(os.path.join(out, "probe", "plugins", "profile", "*",
                                     "*.xplane.pb"))
    kept = os.path.join(out, "probe2.xplane.pb")
    slim(path, kept)
    spans = adapter.program_spans(tracer, sync_t)
    with open(os.path.join(out, "probe2.spans.json"), "w") as f:
        json.dump({"sync_monotonic": window.sync_monotonic,
                   "spans": [list(s[:3]) for s in spans]}, f)
    print("xplane bytes", os.path.getsize(kept))
    parsed = scopes.load(kept)
    print("host annotations:", sorted({n for n, _, _ in parsed.host
                                       if n in {s[0] for s in spans}}))
    shapes = scopes.pool_shapes({
        "n_layer": cfg.n_layers, "n_head": cfg.n_heads,
        "n_embd": cfg.d_model, "n_positions": cfg.max_seq_len,
        "serving": {"kv_block_size": block, "kv_blocks": blocks,
                    "max_batch": rows}})
    for span in ("train_dispatch", "serving_decode_step"):
        print(span, json.dumps(scopes.reduce_scopes(parsed, span, shapes)))
    print("decode host idle", scopes.decode_host_idle(parsed))
    print(trace.reduce_trace(
        trace.load_xplane(kept), program_spans=[s[:3] for s in spans],
        sync_monotonic=window.sync_monotonic))
    return 0


if __name__ == "__main__":
    sys.exit(main())
