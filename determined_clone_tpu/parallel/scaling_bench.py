"""Measured multichip scaling lane: MULTICHIP promoted from dryrun to data.

The dryrun (``__graft_entry__.dryrun_multichip``) proves the sharded
train step *runs* on an ``--xla_force_host_platform_device_count`` mesh;
this module measures it. For each parallelism axis (dp / fsdp / tp) it
builds an all-devices-on-that-axis mesh, AOT-captures the train step
(telemetry/xla.py — fingerprint, cost analysis, and the post-SPMD
collective accounting of telemetry/collectives.py), runs a few timed
steps, and reports:

- **scaling efficiency** per axis: ``thr_N / (N * thr_1)`` against a
  single-device baseline measured in the same process. dp/fsdp scale
  weakly (global batch = per-device batch x N), tp strongly (fixed
  batch) — the uniform formula makes ideal scaling 1.0 in both regimes;
- **measured vs analytic MFU**: ``cost_analysis()`` FLOPs of the
  partitioned per-device module vs the flops.py formula, both over the
  same measured step rate;
- **collective structure**: op/byte counts per (kind, axis) and the
  structure fingerprint tools/bench_gate.py watches for drift;
- **per-device peak bytes** (live-buffer residency, telemetry/device.py)
  and a cross-device straggler summary (telemetry/mesh.py) over the
  timed steps.

The numbers are simulation numbers — virtual devices timeshare one host,
so absolute efficiency is pessimistic — but they are *stable* on a given
machine, which is all a regression gate needs: a sharding change that
halves dp efficiency on the simulated mesh will do worse on real ICI.

Device count is fixed at backend init, so ``bench.py`` runs this as a
subprocess per mesh size: ``python -m
determined_clone_tpu.parallel.scaling_bench --devices N --json``.
Emits one MULTICHIP_SCHEMA_VERSION artifact (telemetry/mesh.py) per run.
"""
from __future__ import annotations

import json
import time
from typing import Any, Dict, List, Optional, Sequence

DEFAULT_AXES = ("dp", "fsdp", "tp")
PER_DEVICE_BATCH = 4
SEQ_LEN = 64


def _bench_config(gpt_mod: Any) -> Any:
    """Tiny-but-shardable GPT: n_heads/d_ff divisible by every axis size
    this lane runs (tp up to 16), big enough to emit real collectives."""
    return gpt_mod.GPTConfig(
        vocab_size=256, n_layers=2, d_model=64, n_heads=16, d_ff=256,
        max_seq_len=SEQ_LEN, remat=True,
    )


def _measure_mesh(mesh: Any, batch_size: int, *, steps: int,
                  warmup: int, registry: Optional[Any] = None
                  ) -> Dict[str, Any]:
    """Build + AOT-capture + time the sharded train step on one mesh.

    Returns throughput, per-step seconds, the compile record's collective
    summary / fingerprint / comm fraction, measured + analytic MFU
    inputs, and per-device completion durations for the straggler view.
    """
    import jax
    import optax
    from jax.sharding import NamedSharding

    from determined_clone_tpu.models import gpt
    from determined_clone_tpu.parallel.sharding import shard_put
    from determined_clone_tpu.telemetry import flops as flops_mod
    from determined_clone_tpu.telemetry.mesh import (
        MeshStragglerDetector,
        per_device_completion_seconds,
    )
    from determined_clone_tpu.training.train_step import (
        capture_compile,
        create_train_state,
        make_train_step,
        state_shardings,
    )

    cfg = _bench_config(gpt)
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    tx = optax.adamw(1e-3, weight_decay=0.01)
    state = create_train_state(params, tx, jax.random.PRNGKey(1))
    sharding = state_shardings(state, mesh, gpt.GPT_SHARDING_RULES)
    state = shard_put(state, sharding)
    batch_sharding = NamedSharding(mesh, gpt.TOKENS_SPEC)
    tokens = jax.random.randint(
        jax.random.PRNGKey(2), (batch_size, SEQ_LEN), 0, cfg.vocab_size)
    tokens = shard_put(tokens, batch_sharding)

    def loss_fn(p, batch, rng):
        return gpt.loss_fn(p, cfg, batch[:, :-1], batch[:, 1:],
                           mesh=mesh), {}

    step = make_train_step(
        loss_fn, tx, mesh=mesh, state_sharding=sharding,
        batch_sharding=batch_sharding)
    step, record = capture_compile(
        step, (state, tokens), program="scaling_bench",
        registry=registry, mesh=mesh)

    detector = MeshStragglerDetector(registry)
    for _ in range(max(0, warmup)):
        state, metrics = step(state, tokens)
        jax.block_until_ready(metrics)
    t_start = time.perf_counter()
    step_seconds: List[float] = []
    for _ in range(max(1, steps)):
        t0 = time.perf_counter()
        state, metrics = step(state, tokens)
        durations = per_device_completion_seconds(metrics, t0)
        jax.block_until_ready(metrics)
        step_seconds.append(time.perf_counter() - t0)
        if durations:
            detector.observe(durations)
    elapsed = time.perf_counter() - t_start

    n = mesh.devices.size
    sps = len(step_seconds) / elapsed if elapsed > 0 else 0.0
    platform = mesh.devices.flat[0].platform
    peak, peak_label = flops_mod.peak_flops_estimate(platform)
    analytic = flops_mod.gpt_train_step_flops(cfg, batch_size, SEQ_LEN - 1)
    mfu_analytic = flops_mod.mfu(analytic.total * sps, peak, n)
    mfu_measured = None
    if record is not None and record.flops:
        # cost_analysis flops describe the per-device partitioned module:
        # total program flops/exec = flops * n, over n devices of peak
        mfu_measured = flops_mod.mfu(record.flops * n * sps, peak, n)
    from determined_clone_tpu.telemetry.device import (
        live_buffer_bytes_by_device,
    )

    # captured while state/tokens are still live — per-device residency
    # of the sharded train state on THIS mesh
    live_bytes = {dev: b for dev, b in
                  live_buffer_bytes_by_device().items()}
    out: Dict[str, Any] = {
        "mesh_shape": {k: int(v) for k, v in dict(mesh.shape).items()},
        "per_device_live_bytes": dict(sorted(live_bytes.items())),
        "batch_size": int(batch_size),
        "steps_timed": len(step_seconds),
        "step_seconds_mean": elapsed / max(1, len(step_seconds)),
        "throughput_samples_per_sec": batch_size * sps,
        "mfu_analytic": mfu_analytic,
        "mfu_measured": mfu_measured,
        "peak_flops_provenance": peak_label,
        "straggler": detector.summary(),
    }
    if record is not None:
        out["program_fingerprint"] = record.fingerprint[:16]
        out["compile_seconds"] = (record.lower_seconds
                                  + record.compile_seconds)
        if record.collectives is not None:
            out["collectives"] = record.collectives.as_dict()
        if record.comm_fraction is not None:
            out["comm_compute_fraction"] = record.comm_fraction
    return out


def run_scaling_bench(n_devices: int, *,
                      axes: Sequence[str] = DEFAULT_AXES,
                      steps: int = 3, warmup: int = 1,
                      registry: Optional[Any] = None) -> Dict[str, Any]:
    """Measure per-axis scaling on an ``n_devices`` mesh (already forced
    via ``--xla_force_host_platform_device_count`` / host steering).

    Returns one MULTICHIP schema_version-1 artifact
    (``telemetry.mesh.validate_multichip`` is the contract).
    """
    import jax

    from determined_clone_tpu.parallel.mesh import MeshSpec, make_mesh
    from determined_clone_tpu.telemetry.mesh import MULTICHIP_SCHEMA_VERSION

    devices = jax.devices()[:n_devices]
    if len(devices) < n_devices:
        raise RuntimeError(
            f"need {n_devices} devices, have {len(devices)}; run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_devices}")

    baseline_mesh = make_mesh(MeshSpec(), devices[:1])
    baseline = _measure_mesh(baseline_mesh, PER_DEVICE_BATCH,
                             steps=steps, warmup=warmup)
    thr1 = baseline["throughput_samples_per_sec"]

    peaks: Dict[str, float] = {}
    meshes: Dict[str, Dict[str, Any]] = {}
    for axis in axes:
        # MeshSpec defaults dp to the -1 wildcard; pin it so the measured
        # axis is the only one absorbing the devices
        spec_kwargs = {"dp": 1, axis: n_devices}
        mesh = make_mesh(MeshSpec(**spec_kwargs), devices)
        # dp/fsdp scale weakly (batch grows with the mesh); tp strongly
        # (model dims shard, batch fixed) — efficiency thr_N/(N*thr_1)
        # targets 1.0 in both regimes
        batch = (PER_DEVICE_BATCH * n_devices if axis in ("dp", "fsdp")
                 else PER_DEVICE_BATCH)
        run = _measure_mesh(mesh, batch, steps=steps, warmup=warmup,
                            registry=registry)
        thr_n = run["throughput_samples_per_sec"]
        run["scaling_efficiency"] = (
            thr_n / (n_devices * thr1) if thr1 > 0 else None)
        meshes[axis] = run
        for dev, b in run.get("per_device_live_bytes", {}).items():
            peaks[dev] = max(peaks.get(dev, 0.0), b)

    return {
        "schema_version": MULTICHIP_SCHEMA_VERSION,
        "n_devices": int(n_devices),
        "platform": devices[0].platform,
        "baseline": baseline,
        "meshes": meshes,
        "per_device_peak_bytes": dict(sorted(peaks.items())),
    }


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        description="measured multichip scaling lane (simulated mesh)")
    parser.add_argument("--devices", type=int, default=8)
    parser.add_argument("--axes", default=",".join(DEFAULT_AXES),
                        help="comma-separated mesh axes to measure")
    parser.add_argument("--steps", type=int, default=3)
    parser.add_argument("--warmup", type=int, default=1)
    parser.add_argument("--json", action="store_true",
                        help="emit the artifact as one JSON line")
    args = parser.parse_args(argv)

    # steer before any backend init: device count is fixed at first use
    from determined_clone_tpu.utils.host_steering import steer_to_host_cpu

    steer_to_host_cpu(args.devices)
    result = run_scaling_bench(
        args.devices,
        axes=[a.strip() for a in args.axes.split(",") if a.strip()],
        steps=args.steps, warmup=args.warmup)
    if args.json:
        print(json.dumps(result))
    else:
        print(json.dumps(result, indent=2, sort_keys=True))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
