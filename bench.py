"""Headline benchmark: GPT training throughput + MFU on the flagship path.

North-star metric from BASELINE.md: trial throughput in samples/sec/chip with
loss parity for the GPT + mnist baseline configs. The reference publishes no
absolute numbers (BASELINE.json ``published: {}``), so on TPU ``vs_baseline``
is reported against the single-chip parity bar of 0.35 MFU (the
matching-or-beating threshold for a v5e flash path); on the CPU ladder it
stays 1.0 because no baseline exists for that platform.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "detail"}.

A parent that stays off JAX (the chip belongs to one process) starts one
child, which owns the device for the whole run. The child climbs a short
config ladder and prints a complete result JSON line after each rung; the
parent enforces the deadline, keeps the last line, and kills the child's
whole process group on timeout.

Asking for the accelerator and not getting it is an error: there is no CPU
fallback and no retry. ``JAX_PLATFORMS=cpu`` asks for the CPU ladder
explicitly (the tier-1 schema test does). The compile cache is placed by
``utils/compile_cache.py``. ROADMAP item A1 replaces this file.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))

# The single-chip "matching-or-beating" bar: 0.35 MFU on the v5e flash path.
MFU_BASELINE_BAR = 0.35


def _budget(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def loss_ok_for(config_name: str, loss: float, vocab: int) -> bool:
    """Loss gate for a bench rung. With a recorded band for this config
    (tests/data/loss_bands.json, maintained by tests/test_convergence.py)
    the gate catches REGRESSION — a loss outside the band either way means
    the training path changed. Without a band: finite and no worse than
    uniform-over-vocab (+5% headroom) — the catastrophe bound."""
    import math

    if not math.isfinite(loss):
        return False
    try:
        with open(os.path.join(REPO_ROOT, "tests", "data",
                               "loss_bands.json")) as f:
            band = json.load(f).get(config_name)
    except (OSError, ValueError):
        band = None
    if band:
        return band["min"] <= loss <= band["max"]
    return loss < 1.05 * math.log(vocab)


TPU_BUDGET_S = _budget("DCT_BENCH_TPU_BUDGET_S", 300.0)
CPU_BUDGET_S = _budget("DCT_BENCH_CPU_BUDGET_S", 180.0)
# Clock started at main() entry; bounds the control-plane extra.
TOTAL_BUDGET_S = _budget("DCT_BENCH_TOTAL_BUDGET_S", 900.0)


# --------------------------------------------------------------------------
# Child: probe, then the ascending measurement ladder.
# --------------------------------------------------------------------------

def _emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def _run_child() -> None:
    sys.path.insert(0, REPO_ROOT)
    t_start = time.perf_counter()
    deadline = float(os.environ.get("DCT_BENCH_CHILD_DEADLINE", "0")) or None

    def remaining() -> float:
        return (deadline - time.monotonic()) if deadline else 1e9

    import jax
    import jax.numpy as jnp
    import optax

    from determined_clone_tpu.models import gpt, mnist_cnn
    from determined_clone_tpu.training.train_step import (
        capture_compile,
        create_train_state,
        make_train_step,
    )
    from determined_clone_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    device = jax.devices()[0]
    on_tpu = device.platform != "cpu"
    t_init = time.perf_counter() - t_start
    _emit({"probe": device.platform, "init_s": round(t_init, 1)})

    # One tiny jit through the real backend proves it executes, not just
    # enumerates. f32 keeps the expected value exact: (x @ x).sum() with
    # x = 2s is 8*8 * (2*2*8) = 2048.
    x = jnp.full((8, 8), 2.0, jnp.float32)
    jit_ok = float(jax.jit(lambda a: (a @ a).sum())(x)) == 2048.0
    _emit({"probe_jit_ok": jit_ok,
           "probe_s": round(time.perf_counter() - t_start, 1)})
    if not jit_ok:
        # A backend that returns wrong values must not publish numbers.
        sys.exit(3)

    def time_gpt(cfg: gpt.GPTConfig, batch: int, seq: int,
                 timed_steps: int, repeats: int = 1) -> dict:
        from determined_clone_tpu.telemetry.device import device_memory_stats

        params = gpt.init(jax.random.PRNGKey(0), cfg)
        tx = optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1)
        state = create_train_state(params, tx, jax.random.PRNGKey(1))
        tokens = jax.random.randint(
            jax.random.PRNGKey(2), (batch, seq + 1), 0, cfg.vocab_size)

        def loss(p, b, rng):
            return gpt.loss_fn(p, cfg, b[:, :-1], b[:, 1:]), {}

        # explicit lower()/compile() capture (telemetry/xla.py): compile
        # wall time, HLO fingerprint, and cost_analysis FLOPs land in the
        # BENCH json's `xla` section; the measured AOT executable is the
        # one timed below
        step = make_train_step(loss, tx)
        step, compile_rec = capture_compile(step, (state, tokens))
        for _ in range(2):  # two warm executed steps (compile was above)
            state, metrics = step(state, tokens)
        float(metrics["loss"])  # value fetch: waits for the device
        # median-of-repeats: a single short timing window on a shared CPU
        # host swings +/-10% run to run (the r03->r04 "regression" band —
        # ROADMAP item 5); the median of several windows is stable
        durations = []
        for _ in range(max(1, repeats)):
            t0 = time.perf_counter()
            for _ in range(timed_steps):
                state, metrics = step(state, tokens)
            final_loss = float(metrics["loss"])  # fetch = barrier
            durations.append(time.perf_counter() - t0)
        durations.sort()
        dt = durations[len(durations) // 2]
        mem = device_memory_stats()
        return {
            "samples_per_sec": batch * timed_steps / dt,
            "tokens_per_sec": batch * seq * timed_steps / dt,
            "timing_spread": (round(durations[-1] / durations[0], 3)
                              if len(durations) > 1 else None),
            "final_loss": round(final_loss, 4),
            "model_params": gpt.param_count(params),
            "batch": batch,
            "seq_len": seq,
            "compile": compile_rec.as_dict() if compile_rec else None,
            "peak_memory_bytes": (
                mem.get("device_peak_bytes_in_use")
                or mem.get("device_bytes_in_use")),
            "memory_device_count": mem.get("device_count"),
        }

    def time_pipeline(cfg: gpt.GPTConfig, batch: int, seq: int,
                      timed_steps: int, k: int) -> dict:
        """The REAL hot loop: host-side token batches through the async
        DevicePrefetcher + fused k-step dispatch (the trainer's default
        path). Reports the input-pipeline overlap — dataloading_fraction is
        the consumer-visible queue wait over wall time (0 = perfect
        overlap, 1 = host-bound) — plus the telemetry span summary and the
        XLA (re)trace count, so compile churn in the hot loop shows up in
        BENCH history."""
        import numpy as np

        from determined_clone_tpu.telemetry import Telemetry
        from determined_clone_tpu.utils.data import DevicePrefetcher

        # no sync= on wrap_jit: spans time dispatch, the value fetches
        # below stay the only barriers — throughput is unperturbed
        tel = Telemetry(enabled=True)

        params = gpt.init(jax.random.PRNGKey(0), cfg)
        tx = optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1)
        state = create_train_state(params, tx, jax.random.PRNGKey(1))
        host_rng = np.random.RandomState(7)

        def host_batches():
            while True:
                yield host_rng.randint(
                    0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)

        def loss(p, b, rng):
            return gpt.loss_fn(p, cfg, b[:, :-1], b[:, 1:]), {}

        step = tel.wrap_jit("train_dispatch",
                            make_train_step(loss, tx, steps_per_dispatch=k))
        feed = DevicePrefetcher(host_batches(), jax.device_put, depth=2 * k,
                                tracer=tel.tracer, registry=tel.registry)
        try:
            group = [next(feed) for _ in range(k)]
            state, metrics = step(state, *group)  # compile
            group = [next(feed) for _ in range(k)]
            state, metrics = step(state, *group)  # one executed dispatch
            float(metrics["loss"])  # value fetch = real barrier
            feed.take_queue_wait()  # reset: warm-up stall is not steady state
            n_dispatches = max(timed_steps // k, 1)
            t0 = time.perf_counter()
            for _ in range(n_dispatches):
                group = [next(feed) for _ in range(k)]
                state, metrics = step(state, *group)
            float(metrics["loss"])  # fetch = barrier
            dt = time.perf_counter() - t0
            wait = feed.take_queue_wait()
        finally:
            feed.close()
        return {
            "pipeline_samples_per_sec": round(
                batch * k * n_dispatches / dt, 3),
            "dataloading_fraction": round(min(max(wait / dt, 0.0), 1.0), 4),
            "steps_per_dispatch": k,
            "prefetch_depth": 2 * k,
            # >1 means the fused program recompiled mid-run (shape churn)
            "xla_compiles": tel.compile_count(),
            "span_summary": tel.span_summary(),
        }

    def time_mnist(timed_steps: int) -> dict:
        cfg = mnist_cnn.MnistCNNConfig(
            compute_dtype=jnp.bfloat16 if on_tpu else jnp.float32)
        params = mnist_cnn.init(jax.random.PRNGKey(3), cfg)
        tx = optax.adamw(1e-3)
        state = create_train_state(params, tx, jax.random.PRNGKey(4))
        batch = 512 if on_tpu else 64
        data = {
            "x": jax.random.normal(jax.random.PRNGKey(5), (batch, 28, 28, 1)),
            "y": jax.random.randint(jax.random.PRNGKey(6), (batch,), 0, 10),
        }

        def loss(p, b, rng):
            return mnist_cnn.loss_fn(p, cfg, b["x"], b["y"]), {}

        step = make_train_step(loss, tx)
        for _ in range(2):
            state, metrics = step(state, data)
        float(metrics["loss"])  # value fetch = real barrier (see time_gpt)
        t0 = time.perf_counter()
        for _ in range(timed_steps):
            state, metrics = step(state, data)
        float(metrics["loss"])
        dt = time.perf_counter() - t0
        return {"samples_per_sec": round(batch * timed_steps / dt, 1),
                "batch": batch}

    def time_checkpoint_io() -> dict:
        """Checkpoint I/O on the save/restore hot path: 3 saves with ~12%
        churn + 1 restore through the content-addressed store
        (storage/cas.py, 1 MiB chunks, shared_fs backend), against a plain
        shared_fs save of the same payload. Pure host I/O — no devices —
        so it rides in BENCH on every platform."""
        import shutil
        import tempfile

        import numpy as np

        from determined_clone_tpu.storage import (
            CASStorageManager,
            ChunkCache,
            SharedFSStorageManager,
        )

        root = tempfile.mkdtemp(prefix="dct-bench-ckpt-")
        try:
            src = os.path.join(root, "src")
            os.makedirs(src)
            rng = np.random.RandomState(11)
            payload = rng.bytes(8 << 20)
            with open(os.path.join(src, "state.bin"), "wb") as f:
                f.write(payload)
            mb = len(payload) / (1 << 20)

            plain = SharedFSStorageManager(os.path.join(root, "plain"))
            t0 = time.perf_counter()
            plain.upload(src, "ck-plain")
            plain_save_s = time.perf_counter() - t0

            cas = CASStorageManager(
                SharedFSStorageManager(os.path.join(root, "cas-store")),
                cache=ChunkCache(os.path.join(root, "cache")))
            save_s = []
            for i in range(3):
                if i:
                    # churn the first MiB of the payload between saves;
                    # the other 7 chunks dedup against the prior save
                    blob = bytearray(payload)
                    blob[: 1 << 20] = rng.bytes(1 << 20)
                    payload = bytes(blob)
                    with open(os.path.join(src, "state.bin"), "wb") as f:
                        f.write(payload)
                t0 = time.perf_counter()
                cas.upload(src, f"ck-{i}")
                cas.commit(f"ck-{i}")
                save_s.append(round(time.perf_counter() - t0, 4))
            t0 = time.perf_counter()
            cas.download("ck-2", os.path.join(root, "restore"))
            restore_s = time.perf_counter() - t0
            stats = cas.storage_stats()
            sess = stats["session"]
            return {
                "payload_mb": round(mb, 1),
                "plain_save_mb_s": round(mb / max(plain_save_s, 1e-9), 1),
                "cas_save_s": save_s,
                "cas_save_mb_s": round(mb / max(save_s[-1], 1e-9), 1),
                "cas_restore_s": round(restore_s, 4),
                "cas_restore_mb_s": round(mb / max(restore_s, 1e-9), 1),
                "dedup_ratio": stats["dedup_ratio"],
                "bytes_uploaded": sess["bytes_uploaded"],
                "bytes_deduped": sess["bytes_deduped"],
            }
        finally:
            shutil.rmtree(root, ignore_errors=True)

    def time_goodput() -> dict:
        """Wall-clock attribution on a REAL trainer run: core.init +
        Trainer with telemetry enabled, then the GoodputLedger's account
        (telemetry/goodput.py). The gateable outputs: goodput_fraction is
        non-null and the conservation invariant holds — categories sum to
        the ledger's wall-clock (checked against an external perf_counter
        measurement too, within 1%)."""
        import shutil
        import tempfile

        import numpy as np

        from determined_clone_tpu import core as core_mod
        from determined_clone_tpu.config import ExperimentConfig
        from determined_clone_tpu.parallel import MeshSpec, make_mesh
        from determined_clone_tpu.telemetry.goodput import check_conservation
        from determined_clone_tpu.training import (
            JaxTrial,
            Trainer,
            TrialContext,
        )

        class GoodputTrial(JaxTrial):
            n_batches = 24

            def initial_params(self, rng):
                return {"w": jnp.zeros(())}

            def optimizer(self):
                return optax.sgd(0.05)

            def loss(self, params, batch, rng):
                return (params["w"] - jnp.mean(batch)) ** 2, {}

            def training_data(self):
                for i in range(self.n_batches):
                    yield np.full((4, 1), float(i % 7), np.float32)

            def validation_data(self):
                return [np.ones((4, 1), np.float32)]

            @property
            def global_batch_size(self):
                return 4

        root = tempfile.mkdtemp(prefix="dct-bench-goodput-")
        t0 = time.perf_counter()
        try:
            cfg = ExperimentConfig.from_dict({
                "searcher": {"name": "single", "metric": "loss",
                             "max_length": {"batches": 24}},
                "scheduling_unit": 8,
                "min_checkpoint_period": {"batches": 8},
                "checkpoint_storage": {"type": "shared_fs",
                                       "host_path": root},
                "optimizations": {"prefetch_depth": 0},
                "observability": {"enabled": True},
            })
            mesh = make_mesh(MeshSpec(dp=1), jax.devices()[:1])
            with core_mod.init(config=cfg, trial_id=1) as cctx:
                ctx = TrialContext(config=cfg, hparams={}, core=cctx,
                                   mesh=mesh)
                Trainer(GoodputTrial(ctx)).fit()
                snap = cctx.telemetry.goodput.snapshot()
            wall_outside = time.perf_counter() - t0
            cons = check_conservation(snap)
            frac = snap["goodput_fraction"]
            return {
                "goodput_fraction": (round(frac, 4)
                                     if frac is not None else None),
                "wall_s": round(snap["wall_s"], 3),
                "wall_outside_s": round(wall_outside, 3),
                "conservation_ok": bool(cons["ok"]),
                "conservation_error_fraction": round(
                    cons["error_fraction"], 5),
                "categories": {k: round(v, 4)
                               for k, v in snap["categories"].items()
                               if v > 0},
            }
        finally:
            shutil.rmtree(root, ignore_errors=True)

    def time_serving() -> dict:
        """Latency-vs-load on the continuous-batching serving engine
        (serving/engine.py, docs/serving.md), now as a CONTROLLED A/B:

        - **baseline** engine: chunked prefill only (the workload's long
          prompts need it), prefix cache and speculative decoding OFF.
          Its numbers feed the original schema fields — load_points,
          static replay, continuous_over_static, serving_mfu.
        - **optimized** engine: same params, same request set, same
          rates, with COW prefix sharing + draft-model speculative
          decoding enabled. ``optimized_over_baseline`` is the raw-speed
          headline: tokens/sec ratio at the load-bound top rate.

        The target model is identity-extended (models/gpt.py): a 2-layer
        core plus zero-projection residual blocks, so the 16-layer
        target's greedy stream is bit-identical to the core's while
        every call pays 16 layers of weight traffic — decode is
        memory/launch-bound exactly like production serving. The draft
        is the sliced 2-layer core, i.e. a perfectly-distilled draft
        (acceptance exactly 1.0); BENCH reads the measured rate from the
        engine, not the construction. The workload is "one system
        prompt, many tails": a 32-token shared prefix and 3-token tails,
        with 4 exact-duplicate prompts so the COW fork path runs in the
        measured window, not just in tests.

        Serving MFU comes from the analytic KV-cached generation FLOPs
        (telemetry/flops.py gpt_generation_flops), not the training
        formula — decode attention is linear in context, and pretending
        otherwise would flatter the number ~P/2-fold. The optimized
        lane's MFU counts only FLOPs it actually ran (``prefill_from``
        skips the shared-prefix blocks), so prefix sharing lowers it
        while raising tokens/sec — useful work per second is the point,
        not utilization."""
        import numpy as np

        from determined_clone_tpu.serving import (
            BucketSpec,
            InferenceEngine,
            KVCacheConfig,
        )
        from determined_clone_tpu.telemetry import flops as flops_mod

        core_cfg = gpt_cfg(2, 256, 4, 80, "mha", vocab=256, remat=False)
        core = gpt.init(jax.random.PRNGKey(21), core_cfg)
        params, cfg = gpt.extend_with_identity_layers(core, core_cfg, 14)
        draft_params, draft_cfg = gpt.slice_prefix_layers(params, cfg, 2)
        rng = np.random.RandomState(9)
        # Shared 32-token system prefix + per-request tails, and a WIDE
        # generation-length spread: the spread is what run-to-completion
        # batching pays for — every static group decodes until its
        # longest member (32 here) finishes, so short rows burn masked
        # steps, while continuous retires them immediately and refills
        # the slot. Requests 8..11 repeat tails 0..3 verbatim, so their
        # prefix match reaches into the partial tail block and forces a
        # COW fork. The top rate must make the point load-bound (arrival
        # span shorter than processing), or both policies just measure
        # the arrival clock and the comparison is meaningless.
        system = rng.randint(1, cfg.vocab_size, 32).tolist()
        reqs = []
        for i in range(12):
            max_new = (2, 4, 8, 32)[i % 4]
            reqs.append((system + [40 + (i % 8), 2, 3], max_new))
        rates = (4.0, 32.0, 256.0)
        chunk = 16

        def measure(engine, rate: float) -> tuple:
            t0 = time.monotonic()
            handles = []
            for i, (prompt, max_new) in enumerate(reqs):
                target = t0 + i / rate
                delay = target - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                handles.append(engine.submit_with_backoff(prompt, max_new))
            results = [h.result(timeout=120.0) for h in handles]
            wall = time.monotonic() - t0
            toks = sum(len(r.tokens) for r in results)
            lats = [r.total_s for r in results]
            return results, wall, {
                "offered_rps": rate,
                "tokens_per_sec": round(toks / max(wall, 1e-9), 1),
                "p50_total_s": round(float(np.percentile(lats, 50)), 4),
                "p99_total_s": round(float(np.percentile(lats, 99)), 4),
                "completed": len(results),
                "wall_s": round(wall, 3),
            }

        def sweep(engine) -> tuple:
            # precompile the FULL program ladder (chunk buckets, and for
            # the optimized engine the draft ladder + k-token verify +
            # COW copy) so every measured point times execution, not
            # XLA. A warm burst is not enough: paced arrivals trickle
            # into the running batch one or two at a time, hitting
            # small batch-bucket shapes a burst never compiles —
            # leaving those cold once stalled the top load point behind
            # a mid-measurement compile ~10x the real work
            engine.warmup()
            points = []
            top_results: list = []
            top_wall = 1.0
            for rate in rates:
                results, wall, point = measure(engine, rate)
                points.append(point)
                top_results, top_wall = results, wall
            return points, top_results, top_wall

        cache = KVCacheConfig(num_blocks=64, block_size=8)
        peak, peak_label = flops_mod.peak_flops_estimate(device.platform)

        base = InferenceEngine(
            params, cfg, buckets=BucketSpec.build(4, 16), cache=cache,
            max_queue_depth=64, chunk_prefill_len=chunk)
        try:
            points, top_results, top_wall = sweep(base)
            # tracing observer cost at top load: the SAME warm engine,
            # per-request event recording flipped on (attach_tracer is an
            # atomic attribute swap), re-driven at the top rate. Paired
            # back-to-back runs; a second pair retries a noisy first
            # reading (single-digit-% run noise on a shared CPU would
            # otherwise dominate the per-event dict cost being measured)
            from determined_clone_tpu.telemetry import Tracer

            tracing_overhead = None
            traced_tps = None
            # the sweep just finished with an untraced top-rate run on
            # this same warm engine, so it doubles as the first pair's
            # baseline; only a noisy first reading pays for a fresh pair
            untraced_pt = points[-1]
            for _ in range(3):
                if untraced_pt is None:
                    _, _, untraced_pt = measure(base, rates[-1])
                base.attach_tracer(Tracer(
                    enabled=True, max_events=65_536,
                    process_name="bench_serving"))
                _, _, traced_pt = measure(base, rates[-1])
                base.attach_tracer(None)
                u = untraced_pt["tokens_per_sec"]
                t = traced_pt["tokens_per_sec"]
                est = (u - t) / max(u, 1e-9)
                if tracing_overhead is None or est < tracing_overhead:
                    tracing_overhead = round(est, 4)
                    traced_tps = t
                if tracing_overhead <= 0.02:
                    break
                untraced_pt = None
            arrivals = [i / rates[-1] for i in range(len(reqs))]
            t0 = time.monotonic()
            static_res = base.run_static(reqs, arrivals=arrivals,
                                         timeout=120.0)
            static_wall = time.monotonic() - t0
            static_toks = sum(len(r.tokens) for r in static_res)
            static_lats = [r.total_s for r in static_res]
            static_tps = static_toks / max(static_wall, 1e-9)
            static_point = {
                "offered_rps": rates[-1],
                "tokens_per_sec": round(static_tps, 1),
                "p50_total_s": round(
                    float(np.percentile(static_lats, 50)), 4),
                "p99_total_s": round(
                    float(np.percentile(static_lats, 99)), 4),
                "wall_s": round(static_wall, 3),
            }
            gen_flops = sum(
                flops_mod.gpt_generation_flops(cfg, r.prompt_len,
                                               len(r.tokens))
                for r in top_results)
            base_stats = base.stats()
        finally:
            base.close()

        opt = InferenceEngine(
            params, cfg, buckets=BucketSpec.build(4, 16), cache=cache,
            max_queue_depth=64, chunk_prefill_len=chunk,
            prefix_cache=True, speculative_k=4,
            draft_params=draft_params, draft_cfg=draft_cfg)
        try:
            opt_points, opt_top, opt_wall = sweep(opt)
            # only the target FLOPs the engine actually executed: shared
            # prefix blocks were never re-prefilled (prefill_from), and
            # accepted spec tokens cost the same verify FLOPs a plain
            # decode would have
            opt_flops = sum(
                flops_mod.gpt_generation_flops(
                    cfg, r.prompt_len, len(r.tokens),
                    prefill_from=r.prefix_hit_blocks * cache.block_size)
                for r in opt_top)
            opt_stats = opt.stats()
        finally:
            opt.close()

        # SLO verdict for this round (telemetry/slo.py): the measured
        # top-load latency distribution replayed over every burn-rate
        # window on a simulated clock — hourly ticks back through the 3d
        # window, so all four windows see the same slow fraction and the
        # verdict reflects what this round measured, not wall history.
        # The latency objective is relative to measured capability (4x
        # the top-load p50, floored) — an absolute threshold would grade
        # the host, not the change under test.
        from determined_clone_tpu.telemetry import SLOEngine

        slo_base_t = 1_000_000.0
        thr = max(0.5, 4.0 * points[-1]["p50_total_s"])
        slo = SLOEngine(latency_threshold_s=thr,
                        clock=lambda: slo_base_t)
        slow_n = sum(1 for r in top_results if r.total_s > thr)
        fast_n = len(top_results) - slow_n
        for tick in range(72):
            t = slo_base_t - tick * 3600.0
            if fast_n:
                slo.record_request(latency_s=thr * 0.5, n=fast_n, t=t)
            if slow_n:
                slo.record_request(latency_s=thr * 2.0, n=slow_n, t=t)
        slo_ev = slo.evaluate(now=slo_base_t)

        hit, miss = opt_stats.prefix_hit_blocks, opt_stats.prefix_miss_blocks
        return {
            "model": {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
                      "vocab": cfg.vocab_size,
                      "params": gpt.param_count(params),
                      "draft_layers": draft_cfg.n_layers,
                      "draft_params": gpt.param_count(draft_params)},
            "requests": len(reqs),
            "load_points": points,
            "static": static_point,
            "continuous_over_static": round(
                points[-1]["tokens_per_sec"] / max(static_tps, 1e-9), 3),
            "serving_mfu": round(
                flops_mod.mfu(gen_flops / max(top_wall, 1e-9), peak), 8),
            "mfu_peak_assumed": f"{peak_label}:{peak:.0f}",
            "programs_compiled": base_stats.programs_compiled,
            "program_budget": base_stats.program_budget,
            "tracing_overhead": tracing_overhead,
            "traced_tokens_per_sec": traced_tps,
            "slo": {
                "verdict": slo_ev["verdict"],
                "latency_threshold_s": round(thr, 4),
                "burning_fast": any(
                    o["burning_fast"]
                    for o in slo_ev["objectives"].values()),
                "latency_burn_5m": slo_ev["objectives"]["latency"][
                    "windows"]["5m"]["burn_rate"],
            },
            "optimized": {
                "prefix_cache": True,
                "speculative_k": 4,
                "chunk_prefill_len": chunk,
                "load_points": opt_points,
                "acceptance_rate": opt_stats.spec_acceptance_rate,
                "prefix_hit_blocks": hit,
                "prefix_miss_blocks": miss,
                "prefix_hit_rate": (round(hit / (hit + miss), 4)
                                    if hit + miss else None),
                "serving_mfu": round(
                    flops_mod.mfu(opt_flops / max(opt_wall, 1e-9), peak),
                    8),
                "programs_compiled": opt_stats.programs_compiled,
                "program_budget": opt_stats.program_budget,
            },
            "optimized_over_baseline": round(
                opt_points[-1]["tokens_per_sec"]
                / max(points[-1]["tokens_per_sec"], 1e-9), 3),
        }

    def time_serving_fleet() -> dict:
        """Throughput scaling of the replica fleet (serving/fleet.py,
        docs/serving.md): the SAME burst of requests goes through the
        least-loaded router at 1, 2 and 4 replicas. Replicas share one
        host core here, so raw compute would not scale; each engine
        paces iterations with a simulated device-step floor instead,
        making a replica's ceiling ~batch/floor tokens/sec — exactly
        the regime the fleet targets, where the device step dominates
        and replicas multiply capacity. All replicas share one jitted
        forward, so only the fleet's first warmup compiles. After the
        ladder, a blue-green rollout runs mid-burst at the widest
        point; new params are the old ones x3 (every random tiny-GPT
        init emits the same degenerate greedy stream, so scaling the
        weights is what provably changes the output). The bar: zero
        failed requests, and every response bit-identical to the old-
        or new-version reference — drains serialize each replica's
        stream around its swap, so no output may mix versions."""
        import numpy as np

        from determined_clone_tpu.serving import (
            BucketSpec,
            KVCacheConfig,
            ServingFleet,
        )

        cfg = gpt_cfg(2, 32, 4, 48, "mha", vocab=97, remat=False)
        params = gpt.init(jax.random.PRNGKey(0), cfg)
        floor_s = 0.02
        n_req, max_new = 96, 8
        prompt = (1, 2, 3)

        fleet = ServingFleet(
            params, cfg, name="bench", buckets=BucketSpec.build(4, 16),
            cache=KVCacheConfig(num_blocks=24, block_size=8),
            max_queue_depth=2 * n_req, iteration_floor_s=floor_s)

        def burst(count: int) -> tuple:
            t0 = time.monotonic()
            handles = [fleet.submit(list(prompt), max_new, timeout=120.0)
                       for _ in range(count)]
            results, errors = [], 0
            for h in handles:
                try:
                    results.append(h.result(timeout=120.0))
                except Exception:  # noqa: BLE001 - counted, not raised
                    errors += 1
            return results, errors, time.monotonic() - t0

        try:
            points = []
            for n in (1, 2, 4):
                fleet.scale_to(n)
                results, errors, wall = burst(n_req)
                toks = sum(len(r.tokens) for r in results)
                lats = [r.total_s for r in results] or [0.0]
                points.append({
                    "replicas": n,
                    "tokens_per_sec": round(toks / max(wall, 1e-9), 1),
                    "p50_total_s": round(float(np.percentile(lats, 50)), 4),
                    "p99_total_s": round(float(np.percentile(lats, 99)), 4),
                    "completed": len(results),
                    "failed": errors,
                    "wall_s": round(wall, 3),
                })
            tps = [p["tokens_per_sec"] for p in points]

            # blue-green rollout mid-burst at the widest point
            old_ref = fleet.submit(list(prompt), max_new,
                                   timeout=60.0).result(60.0).tokens
            new_params = jax.tree_util.tree_map(lambda x: x * 3.0, params)
            box: dict = {}

            def do_rollout() -> None:
                box["report"] = fleet.rollout(new_params)

            roller = threading.Thread(target=do_rollout,
                                      name="bench-rollout", daemon=True)
            t0 = time.monotonic()
            handles = []
            for i in range(n_req):
                handles.append(fleet.submit(list(prompt), max_new,
                                            timeout=120.0))
                if i == n_req // 4:
                    roller.start()
                # paced so the burst spans the whole rollout window
                time.sleep(floor_s / 4)
            rollout_results, rollout_errors = [], 0
            for h in handles:
                try:
                    rollout_results.append(h.result(timeout=120.0))
                except Exception:  # noqa: BLE001
                    rollout_errors += 1
            roller.join(180.0)
            rollout_wall = time.monotonic() - t0
            new_ref = fleet.submit(list(prompt), max_new,
                                   timeout=60.0).result(60.0).tokens

            old_phase = sum(1 for r in rollout_results
                            if r.tokens == old_ref)
            new_phase = sum(1 for r in rollout_results
                            if r.tokens == new_ref)
            report = box.get("report")
            stats = fleet.stats()
            return {
                "model": {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
                          "vocab": cfg.vocab_size},
                "requests_per_point": n_req,
                "tokens_per_request": max_new,
                "iteration_floor_s": floor_s,
                "points": points,
                "speedup_2": round(tps[1] / max(tps[0], 1e-9), 3),
                "speedup_4": round(tps[2] / max(tps[0], 1e-9), 3),
                "monotonic": tps[0] < tps[1] < tps[2],
                "rollout": {
                    "replicas": 4,
                    "requests": n_req,
                    "failed": rollout_errors,
                    "parity_ok": (old_ref != new_ref
                                  and old_phase + new_phase
                                  == len(rollout_results)),
                    "old_version_responses": old_phase,
                    "new_version_responses": new_phase,
                    "wall_s": round(rollout_wall, 3),
                    "rollout_duration_s": (round(report.duration_s, 3)
                                           if report else None),
                },
                "rejected_total": stats.rejected,
            }
        finally:
            fleet.close()

    def time_recovery() -> dict:
        """Goodput + p99 through a fault storm, before/after
        self-healing (serving/supervisor.py, docs/serving.md
        "Self-healing"): the same paced burst runs three times on a
        2-replica fleet — clean; with one replica killed mid-burst and
        NO supervisor (front-door requeue keeps every accepted request
        alive, but the fleet limps on at half capacity); and with the
        kill plus a FleetSupervisor that replaces the corpse
        mid-burst. The bar the advisory gate reads: zero lost accepted
        requests in every leg, zero leaked KV blocks, MTTR within
        budget, and the supervised leg's throughput back near the
        clean leg's."""
        import numpy as np

        from determined_clone_tpu import faults
        from determined_clone_tpu.serving import (
            BucketSpec,
            KVCacheConfig,
            ServingFleet,
        )

        cfg = gpt_cfg(2, 32, 4, 48, "mha", vocab=97, remat=False)
        params = gpt.init(jax.random.PRNGKey(0), cfg)
        floor_s = 0.02
        n_req, max_new = 48, 8
        prompt = [1, 2, 3]

        def run_leg(name: str, *, kill: bool, supervise: bool) -> dict:
            fleet = ServingFleet(
                params, cfg, name=name, buckets=BucketSpec.build(4, 16),
                cache=KVCacheConfig(num_blocks=24, block_size=8),
                max_queue_depth=2 * n_req, iteration_floor_s=floor_s,
                warmup=False, tracing=False)
            plan = None
            try:
                fleet.scale_up(2)
                if supervise:
                    fleet.start_supervisor(interval_s=0.05,
                                           stale_after_s=2.0)
                if kill:
                    # the victim dies a few scheduler passes into the
                    # burst — mid-decode, with requests on board
                    plan = faults.activate(faults.plan_from_dict({
                        "seed": 0,
                        "rules": [{"point": f"engine.step.{name}-1",
                                   "action": "error", "nth": 8,
                                   "times": 1}]}))
                lats: list = []
                failed = [0]
                lock = threading.Lock()

                def worker(i: int) -> None:
                    t0 = time.monotonic()
                    try:
                        fleet.handle_request(list(prompt), max_new,
                                             request_id=f"{name}-r{i}",
                                             timeout=120.0)
                        dt = time.monotonic() - t0
                        with lock:
                            lats.append(dt)
                    except Exception:  # noqa: BLE001 - counted, not raised
                        with lock:
                            failed[0] += 1

                threads = [threading.Thread(target=worker, args=(i,),
                                            name=f"bench-rec-{i}",
                                            daemon=True)
                           for i in range(n_req)]
                t0 = time.monotonic()
                for t in threads:
                    t.start()
                    time.sleep(floor_s / 8)  # burst spans the kill window
                for t in threads:
                    t.join(180.0)
                wall = time.monotonic() - t0
                if supervise and kill:
                    deadline = time.monotonic() + 15.0
                    while (not fleet.incidents()
                           and time.monotonic() < deadline):
                        time.sleep(0.05)
                incidents = fleet.incidents()
                live = 0
                leaked = sum(int(i.get("leaked_blocks") or 0)
                             for i in incidents)
                for rep in fleet.replicas():
                    lv = rep.engine.liveness()
                    if lv["thread_alive"] and lv["fatal"] is None:
                        live += 1
                        rep.engine.wait_idle(30.0)
                        leaked += rep.engine.kv_outstanding()
                toks = len(lats) * max_new
                return {
                    "completed": len(lats),
                    "lost": n_req - len(lats) - failed[0],
                    "failed": failed[0],
                    "open_ledger_entries": len(
                        fleet.ledger.open_requests()),
                    "tokens_per_sec": round(toks / max(wall, 1e-9), 1),
                    "p50_s": round(float(np.percentile(lats or [0.0],
                                                       50)), 4),
                    "p99_s": round(float(np.percentile(lats or [0.0],
                                                       99)), 4),
                    "wall_s": round(wall, 3),
                    "live_replicas": live,
                    "leaked_blocks": leaked,
                    "replacements": len(incidents),
                    "mttr_s": round(max(
                        (float(i.get("recovery_s", 0.0))
                         for i in incidents), default=0.0), 4),
                }
            finally:
                faults.deactivate(plan)
                fleet.close()

        clean = run_leg("rclean", kill=False, supervise=False)
        unsup = run_leg("rsolo", kill=True, supervise=False)
        healed = run_leg("rsup", kill=True, supervise=True)
        return {
            "model": {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
                      "vocab": cfg.vocab_size},
            "requests": n_req,
            "tokens_per_request": max_new,
            "iteration_floor_s": floor_s,
            "mttr_budget_s": 30.0,
            "clean": clean,
            "unsupervised": unsup,
            "supervised": healed,
            "recovered_throughput_fraction": round(
                healed["tokens_per_sec"]
                / max(clean["tokens_per_sec"], 1e-9), 3),
        }

    def time_exec_cache() -> dict:
        """Persistent executable cache A/B (storage/exec_cache.py,
        docs/checkpoint_storage.md): bring up a one-replica fleet twice
        against the SAME on-disk cache. Leg A (cold) compiles the full
        warmup ladder and publishes each executable to ``cas/exec/``;
        ``jax.clear_caches()`` then empties the in-memory jit cache so
        leg B (warm) can only be fast by deserializing from the store.
        The bar the gate reads: every warm program is a cache hit with
        zero fallback compiles, ``compile_time_saved_s`` is non-null,
        and the warm replica start beats the cold one."""
        import shutil
        import tempfile

        from determined_clone_tpu.serving import (
            BucketSpec,
            KVCacheConfig,
            ServingFleet,
        )
        from determined_clone_tpu.storage import exec_cache as exec_mod
        from determined_clone_tpu.storage.base import SharedFSStorageManager

        cfg = gpt_cfg(2, 32, 4, 48, "mha", vocab=97, remat=False)
        params = gpt.init(jax.random.PRNGKey(0), cfg)
        cache_dir = tempfile.mkdtemp(prefix="bench-exec-cache-")

        def leg(tokens_ref: list) -> tuple:
            cache = exec_mod.ExecutableCache(
                SharedFSStorageManager(cache_dir))
            fleet = ServingFleet(
                params, cfg, name="exec-ab",
                buckets=BucketSpec.build(4, 16),
                cache=KVCacheConfig(num_blocks=24, block_size=8),
                exec_cache=cache)
            try:
                t0 = time.monotonic()
                fleet.scale_up(1)
                start_s = (fleet.scale_up_latencies_s or
                           [time.monotonic() - t0])[0]
                tokens = fleet.submit([1, 2, 3], 8,
                                      timeout=120.0).result(120.0).tokens
                tokens_ref.append(list(tokens))
                return start_s, fleet.exec_cache_summary() or {}
            finally:
                fleet.close()

        try:
            tokens_ab: list = []
            cold_s, cold = leg(tokens_ab)
            # drop the in-memory jit cache: leg B must go through the
            # persistent store or pay the compile again
            jax.clear_caches()
            warm_s, warm = leg(tokens_ab)
            warm_hits = warm.get("exec_cache_hits", 0)
            warm_misses = warm.get("exec_cache_misses", 0)
            return {
                "programs": warm.get("programs"),
                "cold_replica_start_s": round(cold_s, 3),
                "warm_replica_start_s": round(warm_s, 3),
                "speedup": round(cold_s / max(warm_s, 1e-9), 2),
                "cold_hits": cold.get("exec_cache_hits", 0),
                "cold_misses": cold.get("exec_cache_misses", 0),
                "exec_cache_hits": warm_hits,
                "exec_cache_misses": warm_misses,
                "warm_hit_rate": round(
                    warm_hits / max(warm_hits + warm_misses, 1), 3),
                "fallback_compiles": warm.get("fallback_compiles", 0),
                "compile_time_saved_s": warm.get("compile_time_saved_s"),
                "warm_compile_seconds": warm.get("compile_seconds"),
                "tokens_match": tokens_ab[0] == tokens_ab[1],
            }
        finally:
            shutil.rmtree(cache_dir, ignore_errors=True)

    def time_kv_hierarchy() -> dict:
        """Fleet-wide KV memory hierarchy A/B (serving/kv_store.py,
        docs/serving.md "KV memory hierarchy"): the same seeded Zipf
        burst — shared system-prefix heads over a prompt-template pool —
        against a 4-replica fleet twice. Leg A is the per-replica
        prefix-cache baseline; leg B adds the host/CAS KVBlockStore,
        prefix-affinity routing, and a mid-burst replica restart. The
        gate's advisory bars: the tiered leg's fleet-wide prefix hit
        rate is no lower than the baseline's, p99 doesn't regress, and
        the restarted replica warms the shared prefix from the tier
        instead of re-prefilling it (``kv_miss_blocks == 0`` on the
        replacement is the receipt)."""
        from tools.loadgen import run_zipf_load

        kw = dict(requests=64, replicas=4, templates=12, skew=1.1,
                  seed=0, tokens_per_request=8, shared_blocks=1,
                  iteration_floor_s=0.0, budget_s=240.0)
        base = run_zipf_load(kv_store=False, **kw)
        tiered = run_zipf_load(kv_store=True, restart_at=0.5, **kw)
        if "error" in base or "error" in tiered:
            return {"error": base.get("error") or tiered.get("error")}
        restart = tiered.get("restart") or {}
        return {
            "requests": kw["requests"],
            "replicas": kw["replicas"],
            "zipf_skew": kw["skew"],
            "baseline_prefix_hit_rate": base.get("prefix_hit_rate"),
            "tiered_prefix_hit_rate": tiered.get("prefix_hit_rate"),
            "kv_tier_hit_rate": tiered.get("kv_tier_hit_rate"),
            "kv_host_hit_blocks": tiered.get("kv_host_hit_blocks"),
            "kv_cas_hit_blocks": tiered.get("kv_cas_hit_blocks"),
            "kv_promoted_blocks": tiered.get("kv_promoted_blocks"),
            "kv_spilled_blocks": tiered.get("kv_spilled_blocks"),
            "baseline_p99_s": (base.get("request_total_s")
                               or {}).get("p99"),
            "tiered_p99_s": (tiered.get("request_total_s")
                             or {}).get("p99"),
            "baseline_errors": base.get("errors"),
            "tiered_errors": tiered.get("errors"),
            # the restarted replica's first-contact counters: promoted
            # from the tier vs re-prefilled cold. warm == promoted >= 1;
            # misses here can be never-seen Zipf template bodies, so the
            # strict zero-miss pin lives in the kv_warm_failover chaos
            # scenario where every chain key is the shared block
            "restart": restart,
            "restart_warm": bool(restart
                                 and restart.get("kv_promoted_blocks",
                                                 0) >= 1),
            "host_tier": (tiered.get("kv_stats") or {}).get("entries"),
            "duration_s": round(base.get("duration_s", 0.0)
                                + tiered.get("duration_s", 0.0), 3),
        }

    def time_multichip(device_counts=(8, 16)) -> dict:
        """Measured multichip scaling lane (docs/parallelism.md): one
        ``parallel/scaling_bench.py`` subprocess per simulated mesh size —
        device count is fixed at backend init, so each size needs its own
        process; they run concurrently because the virtual devices
        timeshare the host either way. Each child steers itself to a
        forced-device-count CPU mesh before backend init and prints one
        MULTICHIP schema artifact as its last JSON line."""
        from determined_clone_tpu.telemetry.mesh import validate_multichip

        deadline = time.monotonic() + max(60.0, min(remaining() - 15.0,
                                                    300.0))
        env = dict(os.environ)
        # the children steer themselves to a forced-device-count CPU mesh;
        # this lane never runs where the parent holds a chip (see caller)
        env.pop("JAX_PLATFORMS", None)
        env.pop("XLA_FLAGS", None)
        procs = {}
        for n in device_counts:
            procs[str(n)] = subprocess.Popen(
                [sys.executable, "-m",
                 "determined_clone_tpu.parallel.scaling_bench",
                 "--devices", str(n), "--steps", "2", "--warmup", "1",
                 "--json"],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                text=True, cwd=REPO_ROOT, env=env)
        runs = {}
        for key, proc in procs.items():
            try:
                out, _ = proc.communicate(
                    timeout=max(10.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.communicate()
                runs[key] = {"error": "timeout"}
                continue
            artifact = None
            for line in (out or "").splitlines():
                line = line.strip()
                if line.startswith("{"):
                    try:
                        artifact = json.loads(line)
                    except ValueError:
                        continue
            if proc.returncode != 0 or not isinstance(artifact, dict):
                runs[key] = {"error": f"rc={proc.returncode}, "
                                      f"no artifact line"}
                continue
            problems = validate_multichip(artifact)
            if problems:
                artifact["schema_errors"] = problems[:5]
            runs[key] = artifact
        return {"runs": runs}

    def time_tsdb() -> dict:
        """Scrape+store overhead of the embedded time-series layer
        (telemetry/tsdb.py): a synthetic aggregator shaped like a busy
        cluster — 8 trials' worth of counter/gauge families plus 4
        serving replicas — is scraped repeatedly into a TSDB with the
        stock SLO burn rules evaluating each tick. The number the gate
        reads is duty_fraction: scrape+evaluate wall time over the 5 s
        scrape period, advisory-bounded at 2% so the loop can never
        crowd the master it observes."""
        from determined_clone_tpu.telemetry.aggregate import (
            ClusterMetricsAggregator,
        )
        from determined_clone_tpu.telemetry.metrics import MetricsRegistry
        from determined_clone_tpu.telemetry.rules import (
            RuleEngine,
            stock_slo_rules,
        )
        from determined_clone_tpu.telemetry.tsdb import TimeSeriesDB

        sim = {"t": 1_000_000.0}

        def clock() -> float:
            return sim["t"]

        agg = ClusterMetricsAggregator(clock=clock)
        tsdb = TimeSeriesDB(clock=clock)
        engine = RuleEngine(stock_slo_rules(), clock=clock)
        registry = MetricsRegistry()

        def feed(tick: int) -> None:
            for r in range(4):
                agg.ingest_prometheus_text(
                    f"serving_replica_r{r}",
                    "# TYPE serving_queue_depth gauge\n"
                    f"serving_queue_depth {tick % 7}\n"
                    "# TYPE serving_tokens_per_sec gauge\n"
                    f"serving_tokens_per_sec {90 + r}\n"
                    "# TYPE serving_tokens_total counter\n"
                    f"serving_tokens_total {1000 * r + 50 * tick}\n"
                    "# TYPE serving_requests_completed_total counter\n"
                    f"serving_requests_completed_total {10 * tick}\n")
            for n in range(8):
                lines = [f"# TYPE bench_worker_gauge_{g} gauge\n"
                         f"bench_worker_gauge_{g} {g + tick}\n"
                         for g in range(8)]
                lines += [f"# TYPE bench_worker_steps_{c}_total counter\n"
                          f"bench_worker_steps_{c}_total {c + 3 * tick}\n"
                          for c in range(4)]
                agg.ingest_prometheus_text(f"bench_worker_{n}",
                                           "".join(lines))

        ticks = 60
        feed(0)
        t0 = time.perf_counter()
        for _ in range(ticks):
            agg.dump()
        dump_s = (time.perf_counter() - t0) / ticks
        scrape_s = 0.0
        for tick in range(1, ticks + 1):
            feed(tick)
            sim["t"] += 5.0
            t0 = time.perf_counter()
            tsdb.scrape(agg)
            engine.evaluate(tsdb)
            engine.publish(registry)
            scrape_s += time.perf_counter() - t0
        scrape_s /= ticks
        stats = tsdb.stats()
        period_s = 5.0
        return {
            "series": stats["series"],
            "samples_per_scrape": round(
                stats["samples_stored_total"] / max(1,
                                                    stats["scrapes_total"])),
            "dump_ms": round(dump_s * 1e3, 3),
            "scrape_ms": round(scrape_s * 1e3, 3),
            "scrape_period_s": period_s,
            # the fraction of the scrape period the loop spends working;
            # the gate's advisory bar is 2%
            "duty_fraction": round(scrape_s / period_s, 6),
            "bytes_estimate": stats["bytes_estimate"],
            "memory_budget_bytes": stats["memory_budget_bytes"],
            "within_budget": stats["within_budget"],
        }

    def gpt_cfg(n_layers: int, d_model: int, n_heads: int, seq: int,
                attention_impl: str, vocab: int = 50304,
                remat: bool = True) -> gpt.GPTConfig:
        return gpt.GPTConfig(
            vocab_size=vocab, n_layers=n_layers, d_model=d_model,
            n_heads=n_heads, d_ff=4 * d_model, max_seq_len=seq,
            remat=remat, attention_impl=attention_impl)

    if on_tpu:
        # Ascending ladder: bank a small number fast, then climb. Each rung
        # emits a full result line; the parent keeps the last one. min_s is
        # the floor of remaining budget needed to even start the rung
        # (compile dominates; the persistent cache shrinks warm rounds).
        ladder = [
            {"name": "gpt-2L", "layers": 2, "d": 256, "heads": 4,
             "seq": 512, "batch": 8, "steps": 10, "min_s": 25.0},
            {"name": "gpt-4L", "layers": 4, "d": 512, "heads": 8,
             "seq": 1024, "batch": 8, "steps": 10, "min_s": 40.0},
            {"name": "gpt2-small", "layers": 12, "d": 768, "heads": 12,
             "seq": 1024, "batch": 8, "steps": 10, "min_s": 60.0},
        ]
    else:
        # steps/repeats sized so the timed window is long enough to beat
        # scheduler noise: the old 2-step single window swung the CPU
        # throughput +/-10% run to run (the r03->r04 band, ROADMAP item 5)
        ladder = [
            {"name": "gpt-tiny-cpu", "layers": 2, "d": 128, "heads": 4,
             "seq": 128, "batch": 4, "steps": 4, "repeats": 3,
             "min_s": 0.0, "vocab": 512},
            # the non-toy CPU tier (ROADMAP item 5): big enough that a
            # step is compute-bound rather than dispatch-overhead-bound,
            # small enough to fit the tier-1 timeout when budget allows
            # (min_s gates it; the banked gpt-tiny-cpu line survives
            # regardless)
            {"name": "gpt-small-cpu", "layers": 4, "d": 256, "heads": 8,
             "seq": 256, "batch": 4, "steps": 4, "repeats": 3,
             "min_s": 60.0, "vocab": 2048},
        ]

    from determined_clone_tpu.telemetry import flops as flops_mod

    # published peak of the device kind JAX reports (the one table,
    # telemetry/flops.py), or the labelled CPU stand-in
    mfu_peak, peak_label = flops_mod.peak_flops_estimate()
    if mfu_peak is None:
        raise SystemExit(f"bench: no published peak for this device "
                         f"({peak_label}); add it to telemetry/flops.py")
    mfu_peak_label = f"{peak_label}:{mfu_peak:.0f}"

    mnist = None
    pipeline = None
    ckpt_io = None
    flash_over_mha = None
    mha_sps = None
    mha_rung = None
    goodput_section = None
    serving_section = None
    serving_fleet_section = None
    exec_cache_section = None
    multichip_section = None
    tsdb_section = None
    recovery_section = None
    kv_hierarchy_section = None
    if not on_tpu:
        # cheap on CPU, and computing it before the ladder means the very
        # first banked result line already carries a non-null
        # goodput_fraction (the bench-gate contract); on TPU it runs as a
        # post-bank extra instead so it can never cost the rung result
        try:
            goodput_section = time_goodput()
        except Exception as exc:  # noqa: BLE001
            goodput_section = {"error": repr(exc)[:200]}
        # same placement logic for the serving lane: the first banked
        # line already carries non-null tokens/sec + p50/p99 at every
        # offered load (the bench-gate serving contract)
        try:
            serving_section = time_serving()
        except Exception as exc:  # noqa: BLE001
            serving_section = {"error": repr(exc)[:200]}
        # fleet scaling ladder + mid-burst rollout: pre-ladder for the
        # same reason — the first banked line carries the replica-count
        # scaling numbers the bench gate's advisory fleet check reads
        try:
            serving_fleet_section = time_serving_fleet()
        except Exception as exc:  # noqa: BLE001
            serving_fleet_section = {"error": repr(exc)[:200]}
        # cold/warm replica-start A/B through the persistent executable
        # cache — pre-ladder so the first banked line already answers
        # "did the restart leg's compile cost collapse" (ROADMAP item 4)
        try:
            exec_cache_section = time_exec_cache()
        except Exception as exc:  # noqa: BLE001
            exec_cache_section = {"error": repr(exc)[:200]}
        # host-only and cheap (~1 s): the scrape/store duty cycle of the
        # time-series layer, pre-ladder so the first banked line has it
        try:
            tsdb_section = time_tsdb()
        except Exception as exc:  # noqa: BLE001
            tsdb_section = {"error": repr(exc)[:200]}
        # self-healing fault storm: goodput/p99 clean vs killed vs
        # supervised — the advisory recovery gate reads lost requests,
        # leaked blocks, and MTTR off this section
        try:
            recovery_section = time_recovery()
        except Exception as exc:  # noqa: BLE001
            recovery_section = {"error": repr(exc)[:200]}
        # KV memory hierarchy Zipf A/B + warm-failover restart leg —
        # the advisory kv gate reads hit rates, p99, and the restarted
        # replica's promoted/miss counters off this section
        try:
            kv_hierarchy_section = time_kv_hierarchy()
        except Exception as exc:  # noqa: BLE001
            kv_hierarchy_section = {"error": repr(exc)[:200]}
    for i, rung in enumerate(ladder):
        if remaining() < rung["min_s"]:
            _emit({"skipped_rung": rung["name"],
                   "remaining_s": round(remaining(), 1)})
            break
        vocab = rung.get("vocab", 50304)
        cfg_flash = gpt_cfg(rung["layers"], rung["d"], rung["heads"],
                            rung["seq"], "flash", vocab=vocab,
                            remat=on_tpu)
        flash = time_gpt(cfg_flash, rung["batch"], rung["seq"],
                         rung["steps"], repeats=rung.get("repeats", 1))

        n_params = flash["model_params"]
        # Analytic FLOPs (attention + MLP + embeddings, telemetry/flops.py)
        # against the peak resolved above; mfu_peak_assumed says what the
        # denominator was.
        step_flops = flops_mod.gpt_train_step_flops(
            cfg_flash, rung["batch"], rung["seq"])
        flops_per_sec = (step_flops.total * flash["samples_per_sec"]
                         / max(1, flash["batch"]))
        mfu = flops_mod.mfu(flops_per_sec, mfu_peak)
        # Loss gate: the recorded band (regression) where one exists for
        # this config, the uniform-entropy catastrophe bound otherwise.
        loss_ok = loss_ok_for(rung["name"], flash["final_loss"], vocab)

        # XLA-level section: what the COMPILED program cost (cost_analysis
        # FLOPs -> measured MFU, vs the analytic `mfu` above), what the
        # compile itself cost (ROADMAP item 4 needs this to prove
        # compile_time_saved), and the per-program fingerprint that lets
        # future rounds prove the program did/didn't change (item 5).
        comp = flash.get("compile") or {}
        measured_flops = comp.get("flops")
        measured_fps = (measured_flops * flash["samples_per_sec"]
                        / max(1, flash["batch"])
                        if measured_flops else None)
        xla_section = {
            "compile_time_s": (
                round(comp["lower_seconds"] + comp["compile_seconds"], 4)
                if comp else None),
            "fingerprint": (comp.get("fingerprint") or "")[:16] or None,
            "program_flops": measured_flops,
            "program_bytes_accessed": comp.get("bytes_accessed"),
            "measured_flops_per_sec": (round(measured_fps, 1)
                                       if measured_fps else None),
            "measured_mfu": (round(measured_fps / mfu_peak, 6)
                             if measured_fps else None),
            "peak_memory_bytes": flash.get("peak_memory_bytes"),
            "memory_device_count": flash.get("memory_device_count"),
            "timing_spread": flash.get("timing_spread"),
        }

        def result_line() -> dict:
            return {
                "metric": "gpt_train_throughput",
                "value": round(flash["samples_per_sec"], 3),
                "unit": "samples/sec/chip",
                # the MFU bar is a TPU bar; a CPU estimate-denominated MFU
                # would misleadingly score ~0 against it
                "vs_baseline": (round(mfu / MFU_BASELINE_BAR, 3)
                                if on_tpu else 1.0),
                "detail": {
                    "platform": device.platform,
                    "config": rung["name"],
                    "attention_impl": "flash",
                    "model_params": n_params,
                    "batch": flash["batch"],
                    "seq_len": flash["seq_len"],
                    "tokens_per_sec": round(flash["tokens_per_sec"], 1),
                    "mfu": round(mfu, 6),
                    "mfu_peak_assumed": mfu_peak_label,
                    "xla": xla_section,
                    "flops_per_sec": round(flops_per_sec, 1),
                    "flops_per_step": round(step_flops.total, 1),
                    "final_loss": flash["final_loss"],
                    "loss_ok": loss_ok,
                    "mha_samples_per_sec": mha_sps,
                    "flash_over_mha": flash_over_mha,
                    "mha_config": mha_rung,  # rung the delta was measured on
                    "mnist_cnn": mnist,
                    # input-pipeline overlap (prefetch + fused dispatch):
                    # tracked across rounds so regressions in the trainer's
                    # default hot-loop path are visible in BENCH history
                    "dataloading_fraction": (pipeline or {}).get(
                        "dataloading_fraction"),
                    "steps_per_dispatch": (pipeline or {}).get(
                        "steps_per_dispatch"),
                    "pipeline": pipeline,
                    # checkpoint save/restore wall time + effective MB/s +
                    # dedup ratio through the content-addressed store
                    "checkpoint_io": ckpt_io,
                    # wall-clock attribution of a real trainer mini-run
                    # (telemetry/goodput.py): fraction + conservation check
                    "goodput": goodput_section,
                    # continuous-batching serving: tokens/sec + p50/p99 at
                    # several offered loads, vs the static run-to-completion
                    # baseline on the same programs (docs/serving.md)
                    "serving": serving_section,
                    # replica-fleet scaling: aggregate tokens/sec + p99 at
                    # 1/2/4 replicas under the same burst, plus a mid-burst
                    # blue-green rollout (zero failures, version parity)
                    "serving_fleet": serving_fleet_section,
                    # persistent executable cache: cold vs warm replica
                    # start on the same on-disk cas/exec/ store —
                    # compile_time_saved_s is the tentpole's receipt
                    "exec_cache": exec_cache_section,
                    # measured multichip scaling (parallel/scaling_bench):
                    # per-axis efficiency, measured-vs-analytic MFU, and
                    # collective structure on 8/16-device simulated meshes
                    "multichip": multichip_section,
                    # time-series layer duty cycle: scrape+store+rule
                    # evaluation wall time over the 5 s scrape period
                    "tsdb": tsdb_section,
                    # self-healing under a fault storm: clean vs
                    # replica-killed vs supervisor-healed burst (lost
                    # requests / leaked blocks / MTTR / p99)
                    "recovery": recovery_section,
                    # KV memory hierarchy: Zipf A/B hit rates + p99 and
                    # the mid-burst restart leg warmed from the tier
                    "kv_hierarchy": kv_hierarchy_section,
                    "init_s": round(t_init, 1),
                },
            }

        # Bank the flash number IMMEDIATELY: if the budget expires during
        # the mha/mnist extras below, the parent still has this rung.
        _emit(result_line())

        # The mha delta and mnist numbers are cheap on the first rung; on
        # later rungs only re-measure mha if budget clearly allows.
        if i == 0 or remaining() > 2 * rung["min_s"]:
            import dataclasses
            cfg_mha = dataclasses.replace(cfg_flash, attention_impl="mha")
            mha = time_gpt(cfg_mha, rung["batch"], rung["seq"],
                           rung["steps"])
            mha_sps = round(mha["samples_per_sec"], 3)
            flash_over_mha = round(
                flash["samples_per_sec"] / mha["samples_per_sec"], 3)
            mha_rung = rung["name"]
        if mnist is None and (i == 0 or remaining() > 30):
            mnist = time_mnist(20 if on_tpu else 3)
        if pipeline is None and (not on_tpu or remaining() > 45):
            # the prefetch + fused-dispatch hot loop on this rung's config;
            # never let the extra compile sink the banked rung result
            try:
                pipeline = time_pipeline(
                    cfg_flash, rung["batch"], rung["seq"],
                    timed_steps=8 if not on_tpu else rung["steps"], k=4)
            except Exception as exc:  # noqa: BLE001
                pipeline = {"error": repr(exc)[:200]}
        if ckpt_io is None and (not on_tpu or remaining() > 20):
            # host-only I/O; cheap, but never let it sink the banked rung
            try:
                ckpt_io = time_checkpoint_io()
            except Exception as exc:  # noqa: BLE001
                ckpt_io = {"error": repr(exc)[:200]}
        if goodput_section is None and remaining() > 30:
            # TPU lane: the goodput mini-run is a post-bank extra
            try:
                goodput_section = time_goodput()
            except Exception as exc:  # noqa: BLE001
                goodput_section = {"error": repr(exc)[:200]}
        if serving_section is None and remaining() > 45:
            # TPU lane: serving rides post-bank too (its compiles are
            # tiny, but the banked rung number always comes first)
            try:
                serving_section = time_serving()
            except Exception as exc:  # noqa: BLE001
                serving_section = {"error": repr(exc)[:200]}
        if serving_fleet_section is None and remaining() > 60:
            # TPU lane: the fleet ladder shares the serving programs'
            # compile cache, but budget it like a full extra anyway
            try:
                serving_fleet_section = time_serving_fleet()
            except Exception as exc:  # noqa: BLE001
                serving_fleet_section = {"error": repr(exc)[:200]}
        if exec_cache_section is None and remaining() > 45:
            # TPU lane: the cold leg pays the ladder compile once; the
            # warm leg is mostly deserialize, so the pair fits the slot
            try:
                exec_cache_section = time_exec_cache()
            except Exception as exc:  # noqa: BLE001
                exec_cache_section = {"error": repr(exc)[:200]}
        if tsdb_section is None and remaining() > 10:
            # TPU lane: host-only, ~1 s; rides in any leftover budget
            try:
                tsdb_section = time_tsdb()
            except Exception as exc:  # noqa: BLE001
                tsdb_section = {"error": repr(exc)[:200]}
        if recovery_section is None and remaining() > 60:
            # TPU lane: shares the serving programs' compile cache; the
            # three bursts are paced by the iteration floor, not compute
            try:
                recovery_section = time_recovery()
            except Exception as exc:  # noqa: BLE001
                recovery_section = {"error": repr(exc)[:200]}
        if kv_hierarchy_section is None and remaining() > 60:
            # TPU lane: two Zipf legs against an already-warm compile
            # cache; the restart leg reuses the fleet programs too
            try:
                kv_hierarchy_section = time_kv_hierarchy()
            except Exception as exc:  # noqa: BLE001
                kv_hierarchy_section = {"error": repr(exc)[:200]}
        if multichip_section is None and not on_tpu and remaining() > 100:
            # CPU lane only: the scaling-bench children are processes of
            # their own, and a child of the process that holds the chip
            # must never reach for a device. Absence is an
            # OPTIONAL_SECTION note in the gate, not a failure
            try:
                multichip_section = time_multichip()
            except Exception as exc:  # noqa: BLE001
                multichip_section = {"error": repr(exc)[:200]}

        # Re-emit enriched with the extras; the parent keeps the last line.
        _emit(result_line())


# --------------------------------------------------------------------------
# Parent: one bounded attempt; a result line and exit 0, or exit 1.
# --------------------------------------------------------------------------

def _attach_control_plane(obj: dict, t_round0: float) -> None:
    """Attach the control-plane section: a synthetic scheduler load run
    against the real C++ master (tools/loadgen.py — simulated agents +
    no-op trials), reporting submits/sec admitted, decisions/sec, p50/p99
    submit→running latency and peak queue depth. Host-only (binary +
    sqlite + HTTP), so it rides in BENCH on every platform; a missing
    build degrades to an error note, never a crash."""
    trials = int(_budget("DCT_BENCH_CP_TRIALS", 1000))
    if trials <= 0:
        return
    left = TOTAL_BUDGET_S - (time.monotonic() - t_round0)
    cp_budget = min(_budget("DCT_BENCH_CP_BUDGET_S", 120.0),
                    max(left, 45.0))
    detail = obj.setdefault("detail", {})
    try:
        sys.path.insert(0, REPO_ROOT)
        from tools.loadgen import run_load

        detail["control_plane"] = run_load(trials=trials,
                                           budget_s=cp_budget)
    except Exception as exc:  # noqa: BLE001 - bench must print its line
        detail["control_plane"] = {"error": repr(exc)[:200]}


def _attempt(env: dict, budget: float) -> tuple:
    """Run the child under ``budget`` seconds; return (result, error).

    The child streams JSON lines; the last dict with a "metric" key wins.
    The child runs in its own session and the whole process group is
    killed on timeout, so nothing it started can hold the pipes open.
    """
    import signal

    env = dict(env)
    env["DCT_BENCH_CHILD_DEADLINE"] = str(time.monotonic() + budget)
    try:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--child"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, start_new_session=True,
        )
    except Exception as exc:  # noqa: BLE001 - must never crash the parent
        return None, f"spawn failed: {exc!r}"

    lines: list[dict] = []
    stderr_tail: list[str] = []

    def _reader() -> None:
        try:
            for line in proc.stdout:
                try:
                    obj = json.loads(line)
                except ValueError:
                    continue
                if isinstance(obj, dict):
                    lines.append(obj)
        except Exception:  # noqa: BLE001 - pipe may die with the child
            pass

    def _stderr_reader() -> None:
        # Drain continuously: a chatty child (JAX warnings, tracebacks)
        # would otherwise block on a full 64 KB pipe mid-ladder.
        try:
            for line in proc.stderr:
                stderr_tail.append(line)
                del stderr_tail[:-50]
        except Exception:  # noqa: BLE001
            pass

    reader = threading.Thread(target=_reader, daemon=True,
                              name="bench-stdout-reader")
    reader.start()
    err_reader = threading.Thread(target=_stderr_reader, daemon=True,
                                  name="bench-stderr-reader")
    err_reader.start()
    t0 = time.monotonic()
    timed_out = None
    while proc.poll() is None:
        if time.monotonic() - t0 > budget:
            timed_out = f"timeout after {budget:.0f}s"
            break
        time.sleep(0.5)
    if timed_out:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except Exception:  # noqa: BLE001
            proc.kill()
    reader.join(timeout=10)
    err_reader.join(timeout=10)  # bounded: orphaned pipe holders are
    stderr = "".join(stderr_tail)  # abandoned, the threads are daemons
    try:
        proc.wait(timeout=10)
    except Exception:  # noqa: BLE001
        pass

    results = [o for o in lines if "metric" in o]
    if results:
        best = results[-1]  # last completed rung = largest model measured
        if timed_out:
            best.setdefault("detail", {})["budget_note"] = timed_out
        best.setdefault("detail", {})["rungs_completed"] = len(
            {o.get("detail", {}).get("config") for o in results})
        return best, None
    if timed_out:
        return None, timed_out
    if proc.returncode != 0:
        return None, f"rc={proc.returncode}: {stderr.strip()[-400:]}"
    return None, "child produced no JSON line"


def main() -> int:
    t_round0 = time.monotonic()
    env = dict(os.environ)
    cpu_asked = env.get("JAX_PLATFORMS", "") == "cpu"
    obj, err = _attempt(env, CPU_BUDGET_S if cpu_asked else TPU_BUDGET_S)
    if obj is None:
        print(f"bench: no result: {err}", file=sys.stderr)
        return 1
    platform = (obj.get("detail") or {}).get("platform", "")
    if platform == "cpu" and not cpu_asked:
        # JAX came up on the CPU although nobody asked for it: a CPU
        # timing must never stand in for the accelerator's
        print("bench: asked for the accelerator, JAX ran on the CPU "
              "(set JAX_PLATFORMS=cpu to run the CPU ladder on purpose)",
              file=sys.stderr)
        return 1
    _attach_control_plane(obj, t_round0)
    print(json.dumps(obj))
    return 0


if __name__ == "__main__":
    if "--child" in sys.argv:
        _run_child()
    else:
        sys.exit(main())
