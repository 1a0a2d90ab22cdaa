#!/bin/bash
# Run the test suite on a virtual 8-device CPU mesh (JAX_PLATFORMS=cpu).
# The chip is reached through `python chip_smoke.py`, never from here.
#
# `./run_tests.sh --tier1` runs the tier-1 gate subset (everything not
# marked slow) — the same selection ROADMAP.md's verify command uses, and
# the set the prefetch/fused-dispatch tests (tests/test_prefetch_fused.py)
# ride in.
#
# `./run_tests.sh --observability` runs just the telemetry + profiler
# surface (docs/observability.md): the telemetry core, profiler/tensorboard
# shipping, the observability config round-trip, the XLA/device lane +
# flight recorder + goodput ledger, and the static
# checks. The goodput suite skips cleanly under DCT_TELEMETRY_DISABLED=1.
#
# `./run_tests.sh --lint` runs the dctlint static-analysis suite over the
# tier-1 lint set (docs/static_analysis.md) — the same run
# tests/test_static_checks.py gates in CI.
#
# `./run_tests.sh --chaos` runs the fault-tolerance + flight-recorder +
# goodput-ledger + fleet self-healing suites (docs/fault_tolerance.md)
# with no marker filter, so the slow kill -9 subprocess tests (including
# the restart-leg ledger merge) and the full chaos-conductor scenario
# catalog (tools/chaosfleet.py) run too — the tier-1 lane skips them via
# `-m "not slow"`.
#
# `./run_tests.sh --storage` runs the checkpoint-storage surface
# (docs/checkpoint_storage.md): backends, the content-addressed store +
# transfer pool, and the storage-facing fault-tolerance paths.
#
# `./run_tests.sh --control-plane` runs the control-plane observability
# surface (docs/observability.md): scheduler lifecycle telemetry,
# exposition conformance, trace stitching with the master lane, the
# job-queue counter checks and the synthetic load harness. Every test in
# the lane skips cleanly when the C++ master build is unavailable.
#
# `./run_tests.sh --serving` runs the online-inference surface
# (docs/serving.md): the continuous-batching engine, paged-KV parity and
# compile discipline, the raw-speed features (COW prefix sharing,
# speculative decoding, chunked prefill), the HTTP surface, the
# KV-cached decode FLOPs accounting, and the batch-inference
# dropped-example counter.
#
# `./run_tests.sh --fleet` runs the serving-fleet surface (docs/serving.md
# "Replica fleets"): the least-loaded router + 429 failover, the drain
# protocol and drain-protected scale-down, blue-green rollout parity, the
# queue-driven autoscaler, the fleet HTTP/CLI surface and the aggregator
# rollup — plus the single-engine suite the fleet builds on, and the
# KV memory hierarchy (host/CAS tier, prefix-affinity routing). The master
# integration tests skip cleanly when the C++ build is unavailable.
#
# `./run_tests.sh --multichip` runs the mesh-observability surface
# (docs/parallelism.md) on the simulated 8-device mesh: collective
# accounting, straggler detection, per-device lanes, plus the
# sharding/mesh suites the lane builds on.
# The live-mesh tests skip cleanly when device forcing is unavailable
# (they check len(jax.devices()) themselves).
if [ "$1" = "--lint" ]; then
    shift
    exec env JAX_PLATFORMS=cpu \
        python -m tools.dctlint determined_clone_tpu tools "$@"
elif [ "$1" = "--tier1" ]; then
    shift
    set -- tests/ -m "not slow" "$@"
elif [ "$1" = "--chaos" ]; then
    shift
    set -- tests/test_fault_tolerance.py tests/test_flight_recorder.py \
        tests/test_goodput.py tests/test_self_healing.py "$@"
elif [ "$1" = "--storage" ]; then
    shift
    set -- tests/test_storage_backends.py tests/test_cas_store.py \
        tests/test_fault_tolerance.py -m "not slow" "$@"
elif [ "$1" = "--control-plane" ]; then
    shift
    set -- tests/test_control_plane.py tests/test_load_smoke.py \
        tests/test_job_queue.py \
        -m "not slow" "$@"
elif [ "$1" = "--serving" ]; then
    shift
    set -- tests/test_serving.py tests/test_serving_speed.py \
        tests/test_batch_inference.py \
        -m "not slow" "$@"
elif [ "$1" = "--fleet" ]; then
    shift
    set -- tests/test_serving_fleet.py tests/test_serving.py \
        tests/test_self_healing.py tests/test_kv_store.py \
        -m "not slow" "$@"
elif [ "$1" = "--multichip" ]; then
    shift
    set -- tests/test_mesh_observability.py tests/test_mesh_sharding.py \
        tests/test_xla_telemetry.py tests/test_device_telemetry.py \
        -m "not slow" "$@"
elif [ "$1" = "--observability" ]; then
    shift
    set -- tests/test_telemetry.py tests/test_profiler_tensorboard.py \
        tests/test_observability_config.py tests/test_observability_plane.py \
        tests/test_xla_telemetry.py tests/test_device_telemetry.py \
        tests/test_flight_recorder.py tests/test_goodput.py \
        tests/test_request_tracing.py tests/test_slo.py \
        tests/test_tsdb_rules.py \
        tests/test_static_checks.py \
        -m "not slow" "$@"
fi
exec env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}" \
    python -m pytest "${@:-tests/}" -q
