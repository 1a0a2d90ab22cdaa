"""In-task trial entrypoint — what the agent execs for a trial leg.

≈ the reference's in-container chain (entrypoint.sh → prep_container →
determined.exec.harness, SURVEY.md §3.1-3.2), collapsed: ClusterInfo from
DCT_* env (≈ _info.py:23), master rendezvous (≈ prep_container.py:203),
jax.distributed init for multi-host gangs, master-backed Core API contexts,
then Trainer.fit on the user's JaxTrial class.

Usage (by the agent): python -m determined_clone_tpu.exec.trial module:Class
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import os
import socket
import sys
import time
from typing import Any, Dict, Optional

from determined_clone_tpu import faults  # import-light (stdlib only)


@dataclasses.dataclass
class ClusterInfo:
    """≈ det.get_cluster_info() (harness/determined/_info.py:23-137)."""

    master_host: str
    master_port: int
    allocation_id: str
    trial_id: int
    experiment_id: int
    rank: int
    world_size: int
    slots: int
    n_slices: int
    hparams: Dict[str, Any]
    target_units: int
    latest_checkpoint: Optional[str]
    experiment_config: Dict[str, Any]

    @staticmethod
    def from_env() -> "ClusterInfo":
        def need(name: str) -> str:
            v = os.environ.get(name)
            if v is None:
                raise RuntimeError(f"missing required env var {name}")
            return v

        return ClusterInfo(
            master_host=os.environ.get("DCT_MASTER_HOST", "127.0.0.1"),
            master_port=int(os.environ.get("DCT_MASTER_PORT", "8080")),
            allocation_id=need("DCT_ALLOCATION_ID"),
            trial_id=int(need("DCT_TRIAL_ID")),
            experiment_id=int(os.environ.get("DCT_EXPERIMENT_ID", "0")),
            rank=int(os.environ.get("DCT_RANK", "0")),
            world_size=int(os.environ.get("DCT_WORLD_SIZE", "1")),
            slots=int(os.environ.get("DCT_SLOTS", "1")),
            n_slices=int(os.environ.get("DCT_N_SLICES", "1")),
            hparams=json.loads(os.environ.get("DCT_HPARAMS", "{}")),
            target_units=int(os.environ.get("DCT_TARGET_UNITS", "0")),
            latest_checkpoint=os.environ.get("DCT_LATEST_CHECKPOINT") or None,
            experiment_config=json.loads(
                os.environ.get("DCT_EXPERIMENT_CONFIG", "{}")),
        )


def resolve_entrypoint(entrypoint: str):
    """'pkg.module:Attr' → a JaxTrial subclass or a Core API function
    ``fn(core_context, cluster_info)``. The model-def directory (cwd) is on
    sys.path, like the reference's context-dir download + import."""
    if ":" not in entrypoint:
        raise RuntimeError(
            f"entrypoint {entrypoint!r} must look like 'module:TrialClass' "
            f"or 'module:core_api_function'"
        )
    module_name, class_name = entrypoint.split(":", 1)
    if "" == module_name:
        raise RuntimeError("entrypoint module is empty")
    sys.path.insert(0, os.getcwd())
    module = importlib.import_module(module_name)
    return getattr(module, class_name)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("0.0.0.0", 0))
        return s.getsockname()[1]


def do_rendezvous(session, info: ClusterInfo, addr: str) -> dict:
    """Register our address; poll until the whole gang is present
    (≈ task/rendezvous.go:94-187). Returns the full rendezvous payload:
    rank-ordered ``members`` (member[0] carries the jax coordinator +
    control-plane ports) plus, for multislice gangs, ``n_slices`` and the
    per-rank ``slice_ids`` the scheduler assigned."""
    deadline = time.monotonic() + 300
    while True:
        faults.point("trial.rendezvous")
        resp = session.post(
            f"/api/v1/allocations/{info.allocation_id}/rendezvous",
            {"rank": info.rank, "address": addr},
            retryable=True,  # idempotent re-registration
        )
        if resp.get("ready"):
            return resp
        if time.monotonic() > deadline:
            raise RuntimeError(
                f"rendezvous timed out: {len(resp.get('members', []))}/"
                f"{resp.get('world_size')} members present"
            )
        time.sleep(0.5)


def build_multislice_mesh(info: ClusterInfo, rdv: dict):
    """The hybrid ICI×DCN mesh for a master-scheduled slice-group gang.

    The rendezvous payload is the source of truth for the slice layout
    (scheduler.cc's n_slices branch put one whole slice on each agent;
    routes.cc's rendezvous response carries the per-rank slice_ids). The
    mesh hparam splits into {"ici": {per-slice axes}, "dcn": {cross-slice
    axes}}; dcn defaults to pure data parallelism over the slices.
    """
    import math

    from determined_clone_tpu.parallel.mesh import (
        MeshSpec,
        make_multislice_mesh,
    )

    n_slices = int(rdv.get("n_slices", info.n_slices))
    slice_ids = list(rdv.get("slice_ids") or [])
    if slice_ids:
        # make_multislice_mesh assumes slice-major device enumeration and
        # process order == rank order: each slice's ranks must be one
        # contiguous ascending run of equal size
        if slice_ids != sorted(slice_ids):
            raise RuntimeError(
                f"rendezvous slice_ids are not slice-major: {slice_ids}")
        counts = [slice_ids.count(s) for s in range(n_slices)]
        if len(set(counts)) > 1:
            raise RuntimeError(f"uneven slice groups: {counts}")

    mesh_hp = info.hparams.get("mesh") or {}
    unknown = set(mesh_hp) - {"ici", "dcn"}
    if unknown:
        # a flat single-slice spec ({"dp": 8, "tp": 2}) here would be
        # silently dropped — reject loudly instead
        raise RuntimeError(
            f"multislice experiments take mesh: {{ici: ..., dcn: ...}}; "
            f"got flat axes {sorted(unknown)}")
    ici = MeshSpec.from_dict(mesh_hp.get("ici") or {})
    dcn = MeshSpec.from_dict(mesh_hp.get("dcn") or {"dp": n_slices})
    dcn_total = math.prod(dcn.axis_sizes())
    if dcn_total != n_slices:
        raise RuntimeError(
            f"mesh.dcn axes {dcn.to_dict()} multiply to {dcn_total} but the "
            f"allocation has {n_slices} slices — ICI axes would span the "
            f"DCN boundary")
    return make_multislice_mesh(ici, dcn)


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    if not argv:
        print("usage: python -m determined_clone_tpu.exec.trial module:Class",
              file=sys.stderr)
        return 2

    from determined_clone_tpu import core
    from determined_clone_tpu.api.client import MasterSession
    from determined_clone_tpu.config.experiment import ExperimentConfig
    from determined_clone_tpu.config.length import Length, Unit
    from determined_clone_tpu.core._master_backed import (
        MasterCheckpointRegistry,
        MasterMetricsBackend,
        MasterPreemptionSource,
        MasterSearcherSource,
    )
    from determined_clone_tpu.training import JaxTrial, Trainer, TrialContext
    from determined_clone_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    # a restart leg (or the next trial of the same shape) reuses compiles
    configure_compile_cache()

    # Chaos runs ship their plan through the environment; a no-op when
    # DCT_FAULT_PLAN is unset.
    faults.install_from_env()

    info = ClusterInfo.from_env()
    faults.point("trial.startup")
    session = MasterSession(info.master_host, info.master_port)
    config = ExperimentConfig.from_dict(info.experiment_config)
    trial_cls = resolve_entrypoint(argv[0])

    # Ports are chosen ephemerally and advertised via rendezvous so that
    # concurrent gangs sharing a host never collide. member[0] format:
    # "host:jax_port:ctrl_port".
    chief_transport = None
    if info.world_size > 1 and info.rank == 0:
        from determined_clone_tpu.core._distributed import _ChiefTransport

        chief_transport = _ChiefTransport(0, info.world_size)
        addr = f"{socket.gethostname()}:{_free_port()}:{chief_transport.port}"
    else:
        addr = f"{socket.gethostname()}:0:0"

    rdv = do_rendezvous(session, info, addr)
    members = list(rdv.get("members", []))
    if info.world_size > 1:
        # multi-host gang: rank 0's host is the XLA coordinator
        # (SURVEY.md §2.8 plane 1: jax.distributed over ICI/DCN)
        import jax

        chief_host, jax_port, ctrl_port = members[0].rsplit(":", 2)
        jax.distributed.initialize(
            coordinator_address=f"{chief_host}:{jax_port}",
            num_processes=info.world_size,
            process_id=info.rank,
        )
        if info.rank == 0:
            dist = core.DistributedContext(
                rank=0, size=info.world_size, transport=chief_transport,
            )
        else:
            dist = core.DistributedContext.from_tcp(
                chief_host, int(ctrl_port), info.rank, info.world_size
            )
    else:
        dist = core.DistributedContext.single()

    # searcher targets arrive in max_length units; wrap for the trainer
    unit = (config.searcher.max_length.unit
            if config.searcher.max_length is not None else Unit.BATCHES)

    class UnitWrappingSource(MasterSearcherSource):
        def operations(self, is_chief):
            for op in super().operations(is_chief):
                op.length = Length(unit, int(op.length))
                yield op

    exit_code = 0
    with core.init(
        config=config,
        distributed=dist,
        metrics_backend=MasterMetricsBackend(session, info.trial_id),
        preemption_source=MasterPreemptionSource(session, info.allocation_id),
        searcher_source=UnitWrappingSource(session, info.trial_id),
        checkpoint_registry=MasterCheckpointRegistry(session, info.trial_id),
        trial_id=info.trial_id,
    ) as cctx:
        # SIGTERM -> graceful preemption (≈ exec/launch.py:18-27's SLURM
        # SIGTERM semantics): the agent belt-and-braces a SIGTERM alongside
        # the preempt flag; without this handler python's default action
        # would kill the trial mid-step instead of letting it checkpoint
        import signal as signal_mod

        signal_mod.signal(signal_mod.SIGTERM,
                          lambda signum, frame: cctx.preempt.signal())

        # observability: telemetry (opt-in via `observability` config,
        # already built by core.init), profiler (opt-in via `profiling`
        # config) + tensorboard event shipping (chief only, needs a
        # storage backend). The telemetry registry feeds the profiler's
        # drop counters; spans/metrics ship over the profiler channel.
        from determined_clone_tpu import profiler as profiler_mod

        tel = cctx.telemetry
        if tel is not None and not tel.trace_path:
            tel.trace_path = os.path.abspath(
                f"trace-trial-{info.trial_id}.json")
        if tel is not None:
            # trace stitching: DCT_TRACE_ID (set by the submitter) was
            # already picked up by telemetry_from_config; the lane name
            # makes this process a distinct row in the stitched trace
            tel.set_identity(process_name=f"trial-{info.trial_id}")
        prof = profiler_mod.from_config(
            session, info.trial_id, info.experiment_config,
            registry=tel.registry if tel is not None else None)
        cctx.profiler = prof if prof.enabled else None
        prof.start()

        tbm = None
        storage_raw = info.experiment_config.get("checkpoint_storage")
        if dist.is_chief and storage_raw:
            from determined_clone_tpu.tensorboard import TensorboardManager

            try:
                tbm = TensorboardManager.from_config(
                    storage_raw, info.experiment_id, info.trial_id,
                    os.path.abspath(f"tb-events-trial-{info.trial_id}"),
                    rank=info.rank,
                ).start()
            except Exception as e:  # noqa: BLE001 - observability is best-effort
                print(f"[trial] tensorboard disabled: {e}", flush=True)
        cctx.tensorboard = tbm

        # trial construction INSIDE the try: a raising user __init__ must
        # still stop the profiler/tb threads and report the failure cleanly
        try:
            if isinstance(trial_cls, type):
                # a class that does NOT subclass JaxTrial is a config error,
                # not a Core API script — constructing it would "complete"
                # without training a step
                if not issubclass(trial_cls, JaxTrial):
                    raise RuntimeError(
                        f"entrypoint class {trial_cls.__name__!r} must "
                        f"subclass JaxTrial (or be a plain function for "
                        f"the Core API)")
                # multislice gang: build the hybrid ICI×DCN mesh from the
                # rendezvous slice assignments (Core API entrypoints drive
                # their own device layout, so only the Trainer path pays
                # for this)
                multislice_mesh = (build_multislice_mesh(info, rdv)
                                   if info.n_slices > 1 else None)
                tctx = TrialContext(config=config, hparams=info.hparams,
                                    core=cctx, mesh=multislice_mesh)
                trial = trial_cls(tctx)
                trainer = Trainer(trial)
                result = trainer.fit(latest_checkpoint=info.latest_checkpoint)
            elif not callable(trial_cls):
                raise RuntimeError(
                    f"entrypoint {trial_cls!r} is neither a JaxTrial "
                    f"subclass nor a callable")
            else:
                # Core API script entrypoint: a plain function driving the
                # Context itself (searcher ops, metrics, checkpoints) — the
                # reference's `entrypoint: python3 train.py` + core.init()
                # pattern (examples/hf_trainer_api; docs Core API tutorial).
                # Called with the live Context and ClusterInfo so the script
                # needs no env-var spelunking.
                result = trial_cls(cctx, info)
            print(f"[trial] leg finished: {result}", flush=True)
        except Exception as e:  # noqa: BLE001 - report, then fail the task
            print(f"[trial] FAILED: {type(e).__name__}: {e}", flush=True)
            exit_code = 1
        finally:
            if tel is not None:
                # final metric snapshot rides the profiler buffer that
                # prof.stop() flushes; the Chrome trace lands next to the
                # model def (core.init also exports, this logs the path)
                tel.publish(cctx.profiler)
                try:
                    path = tel.export_chrome_trace()
                    print(f"[trial] telemetry trace written: {path}",
                          flush=True)
                except OSError as e:
                    print(f"[trial] trace export failed: {e}", flush=True)
            prof.stop()
            if tbm is not None:
                tbm.close()
    return exit_code


if __name__ == "__main__":
    sys.exit(main())
