"""core.Context and core.init() — the Core API entry point.

Equivalent of the reference's core.init/Context
(harness/determined/core/_context.py:183-320): bundles distributed, train,
checkpoint, preempt and searcher contexts. Off-cluster (no master) every
component gets a local fallback, so the same trial code runs managed and
unmanaged — the reference's Dummy-context design, kept.
"""
from __future__ import annotations

import contextlib
import os
import tempfile
from typing import Any, Iterator, Optional

from determined_clone_tpu.config.experiment import (
    CheckpointStorageConfig,
    ExperimentConfig,
)
from determined_clone_tpu.core._checkpoint import (
    CheckpointContext,
    LocalCheckpointRegistry,
)
from determined_clone_tpu.core._distributed import DistributedContext
from determined_clone_tpu.core._preempt import (
    FilePreemptionSource,
    NeverPreempt,
    PreemptContext,
    PreemptionSource,
)
from determined_clone_tpu.core._searcher import (
    LocalSearcherSource,
    SearcherContext,
    SearcherOperationSource,
)
from determined_clone_tpu.core._train import (
    LocalMetricsBackend,
    MetricsBackend,
    TrainContext,
)
from determined_clone_tpu.storage import base as storage_base
from determined_clone_tpu.utils import retry as retry_util


class Context:
    def __init__(self, *, distributed: DistributedContext, train: TrainContext,
                 checkpoint: CheckpointContext, preempt: PreemptContext,
                 searcher: SearcherContext,
                 info: Optional[Any] = None) -> None:
        self.distributed = distributed
        self.train = train
        self.checkpoint = checkpoint
        self.preempt = preempt
        self.searcher = searcher
        self.info = info
        # observability, wired by the exec layer on managed runs (None in
        # local/unmanaged mode): ProfilerAgent / TensorboardManager /
        # telemetry.Telemetry (the `observability:` config block)
        self.profiler: Optional[Any] = None
        self.tensorboard: Optional[Any] = None
        self.telemetry: Optional[Any] = None

    def close(self) -> None:
        self.preempt.close()
        self.distributed.close()

    def __enter__(self) -> "Context":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


@contextlib.contextmanager
def init(
    *,
    config: Optional[ExperimentConfig] = None,
    distributed: Optional[DistributedContext] = None,
    storage_path: Optional[str] = None,
    metrics_backend: Optional[MetricsBackend] = None,
    preemption_source: Optional[PreemptionSource] = None,
    searcher_source: Optional[SearcherOperationSource] = None,
    checkpoint_registry: Optional[Any] = None,
    trial_id: Optional[int] = None,
) -> Iterator[Context]:
    """Build a Context. With no arguments this is fully local: single rank,
    tmpdir checkpoint storage, JSONL metrics — the unmanaged mode."""
    config = config or ExperimentConfig.from_dict({})
    dist = distributed or DistributedContext.single()

    # telemetry first: the preempt watcher, fault plan and retry layer all
    # want its registry (telemetry_from_config returns None when off)
    from determined_clone_tpu.telemetry import telemetry_from_config

    telemetry = telemetry_from_config(config)
    registry_arg = telemetry.registry if telemetry is not None else None
    if (telemetry is not None and telemetry.goodput is not None
            and trial_id is not None):
        # the goodput journal file is named by trial id, so identity must
        # land before the ledger's first durable write (first publish)
        telemetry.goodput.set_identity(trial_id=trial_id)

    # fault plan: a config `faults:` block wins; otherwise DCT_FAULT_PLAN.
    # Config plans are cached by payload so counters survive restart legs;
    # env plans are process-global and never deactivated here.
    from determined_clone_tpu import faults as faults_mod

    fault_plan = None
    if (config.faults is not None and config.faults.enabled
            and config.faults.rules):
        fault_plan = faults_mod.activate_from_config(
            {"seed": config.faults.seed, "rules": config.faults.rules},
            registry=registry_arg)
    elif faults_mod.active_plan() is None:
        faults_mod.install_from_env()

    cleanup_dir: Optional[tempfile.TemporaryDirectory] = None
    if config.checkpoint_storage is not None:
        storage = storage_base.build(config.checkpoint_storage)
        # the cas wrapper keeps its paths on the inner backend block
        path_cfg = config.checkpoint_storage
        if path_cfg.type == "cas" and path_cfg.inner is not None:
            path_cfg = path_cfg.inner
        registry_base = (
            path_cfg.host_path or path_cfg.container_path or "."
        )
    else:
        if storage_path is None:
            cleanup_dir = tempfile.TemporaryDirectory(prefix="dct-ckpt-")
            storage_path = cleanup_dir.name
        storage = storage_base.build(
            CheckpointStorageConfig(type="shared_fs", host_path=storage_path)
        )
        registry_base = storage_path

    if telemetry is not None and hasattr(storage, "set_telemetry"):
        storage.set_telemetry(telemetry.registry, telemetry.tracer)

    registry = checkpoint_registry or LocalCheckpointRegistry(
        os.path.join(registry_base, "checkpoints.jsonl")
    )
    checkpoint = CheckpointContext(dist, storage, registry, trial_id=trial_id)

    backend = metrics_backend or LocalMetricsBackend()
    train = TrainContext(
        backend,
        is_chief=dist.is_chief,
        metric=config.searcher.metric,
        smaller_is_better=config.searcher.smaller_is_better,
    )

    source = preemption_source
    if source is None:
        flag = os.environ.get("DCT_PREEMPT_FILE")
        source = FilePreemptionSource(flag) if flag else NeverPreempt()
    preempt = PreemptContext(dist, source, registry=registry_arg).start()

    if searcher_source is None:
        searcher_source = LocalSearcherSource(config.searcher.max_length)
    searcher = SearcherContext(searcher_source, is_chief=dist.is_chief)

    ctx = Context(distributed=dist, train=train, checkpoint=checkpoint,
                  preempt=preempt, searcher=searcher)

    # local/unmanaged runs still get telemetry when the config asks for it
    # (managed runs: exec/trial.py wires this plus profiler shipping)
    ctx.telemetry = telemetry
    retry_util.set_registry(registry_arg)
    try:
        yield ctx
    finally:
        try:
            if ctx.telemetry is not None and ctx.telemetry.trace_path:
                ctx.telemetry.export_chrome_trace()
        finally:
            if ctx.telemetry is not None:
                # flush+fsync the live flight segment on clean shutdown
                # (a crash skips this — the recorder's line-buffered
                # writes are already on disk, which is its whole point)
                ctx.telemetry.close()
            if fault_plan is not None:
                faults_mod.deactivate(fault_plan)
            retry_util.set_registry(None)
            ctx.close()
            if cleanup_dir is not None:
                cleanup_dir.cleanup()
