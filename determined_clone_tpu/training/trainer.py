"""Trainer — the training loop (≈ _PyTorchTrialController + Trainer.fit,
harness/determined/pytorch/_pytorch_trial.py:183,631 and _trainer.py:83).

Loop shape mirrors the reference's searcher-driven boundaries
(_train_with_boundaries :695): train in scheduling_unit chunks, report
training metrics per chunk, validate/checkpoint on period boundaries,
cooperate with preemption — but each batch is one jitted XLA program and
metrics stay on device until a boundary (no per-batch host syncs).

Hot-loop performance (config ``optimizations:`` block, docs/
training_loop_performance.md):

- **Async device prefetch** (``prefetch_depth``, default 2): a background
  thread pulls host batches and applies the sharded ``device_put`` into a
  bounded queue, so input transfer overlaps device compute instead of
  blocking every dispatch. Depth 0 restores the synchronous path.
- **Fused multi-step dispatch** (``steps_per_dispatch=k``): k batches are
  ``lax.scan``ned through the step body inside one jitted program — one
  Python dispatch per k optimizer steps, metrics summed device-side.
  Chunk/target remainders smaller than k fall back to the k=1 program, so
  batch order and the rng chain match the unfused loop exactly.
"""
from __future__ import annotations

import logging
import os
import time
from typing import Any, Dict, Iterator, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding

from determined_clone_tpu import faults
from determined_clone_tpu.config.length import Length
from determined_clone_tpu.core._checkpoint import CheckpointCorruptError
from determined_clone_tpu.core._serialization import load_pytree, save_pytree
from determined_clone_tpu.telemetry import flops as flops_mod
from determined_clone_tpu.telemetry.device import DeviceMemoryMonitor
from determined_clone_tpu.telemetry.spans import null_span
from determined_clone_tpu.telemetry.xla import StepTimeAnomalyDetector
from determined_clone_tpu.training.metrics import MetricAccumulator
from determined_clone_tpu.training.train_step import (
    TrainState,
    capture_compile,
    create_train_state,
    make_eval_step,
    make_train_step,
    param_count,
    state_shardings,
)
from determined_clone_tpu.training.trial import JaxTrial
from determined_clone_tpu.utils.data import make_device_feeder

CKPT_STATE_DIR = "state"

logger = logging.getLogger(__name__)


def _skip_batches(it: Iterator[Any], n: int) -> int:
    """Fast-forward ``n`` batches of ``it``; returns how many were skipped
    (< n once exhausted). Iterators exposing ``skip_batches`` (e.g.
    ``utils.data.BatchIterator``) skip by index arithmetic; anything else
    falls back to materialize-and-discard."""
    if n <= 0:
        return 0
    fast = getattr(it, "skip_batches", None)
    if fast is not None:
        return int(fast(n))
    skipped = 0
    while skipped < n:
        try:
            next(it)
        except StopIteration:
            break
        skipped += 1
    return skipped


class Trainer:
    def __init__(self, trial: JaxTrial) -> None:
        self.trial = trial
        self.context = trial.context
        self.config = trial.context.config
        self.core = trial.context.core
        self.mesh = trial.context.mesh

    # -- length resolution --------------------------------------------------

    def _to_batches(self, length: Optional[Any], default: int = 0) -> int:
        if length is None:
            return default
        if isinstance(length, int):
            return length
        if isinstance(length, Length):
            return length.to_batches(
                self.trial.global_batch_size, self.config.records_per_epoch
            )
        raise TypeError(f"cannot resolve training length {length!r}")

    # -- checkpoint save/restore -------------------------------------------

    def _save(self, state: TrainState, batches_trained: int,
              reason: str, metric=None) -> str:
        """Every host writes its addressable shard files; sharded upload
        merges the manifests (multi-host pjit state is never fully
        addressable on one host). ``metric`` (the searcher metric at save
        time) feeds the master's save_trial_best GC policy."""
        faults.point("training.checkpoint_save")
        dist = self.core.distributed
        ck = self.core.checkpoint
        sharded = dist.size > 1
        metadata = {
            "steps_completed": batches_trained,
            "reason": reason,
            "global_batch_size": self.trial.global_batch_size,
        }
        if metric is not None:
            metadata["validation_metric"] = float(metric)
        with self._span("checkpoint_save", reason=reason):
            with ck.store_path(
                metadata=metadata,
                shard=sharded,
            ) as (path, holder):
                save_pytree(f"{path}/{CKPT_STATE_DIR}", state,
                            host_id=dist.rank)
        return holder.get("storage_id", "")

    def _restore(self, storage_id: str, like: TrainState,
                 shardings: TrainState) -> tuple:
        """Restore with fallback: a checkpoint refused by commit-protocol
        validation (crash mid-upload, torn write) falls back through the
        registry's committed checkpoints, newest first. The registry only
        holds committed ones, so the first candidate that validates is the
        newest safe state."""
        ck = self.core.checkpoint
        candidates = [storage_id] + [
            sid for sid in ck.committed_checkpoints() if sid != storage_id]
        first_err: Optional[CheckpointCorruptError] = None
        for sid in candidates:
            try:
                return self._restore_one(sid, like, shardings)
            except CheckpointCorruptError as e:
                if first_err is None:
                    first_err = e
                logger.warning(
                    "checkpoint %s refused (%s); falling back to the "
                    "previous committed checkpoint", sid, e.reason)
                tel = self._telemetry
                if tel is not None:
                    tel.registry.counter(
                        "checkpoint_restore_fallbacks",
                        "restores that fell back past an uncommitted/"
                        "corrupt checkpoint").inc()
        raise first_err if first_err is not None else RuntimeError(
            f"no restorable checkpoint for {storage_id}")

    def _restore_one(self, storage_id: str, like: TrainState,
                     shardings: TrainState) -> tuple:
        ck = self.core.checkpoint
        with self._span("checkpoint_restore"):
            with ck.restore_path(storage_id) as path:
                state = load_pytree(f"{path}/{CKPT_STATE_DIR}", like,
                                    shardings=shardings)
                mpath = f"{path}/metadata.json"
                meta: dict = {}
                if os.path.exists(mpath):
                    with open(mpath) as f:
                        import json

                        meta = json.load(f)
        return state, int(meta.get("steps_completed", 0))

    @property
    def _telemetry(self):
        return getattr(self.core, "telemetry", None)

    @staticmethod
    def _resolve_step_flops(trial: JaxTrial, state: TrainState
                            ) -> Tuple[float, str]:
        """(FLOPs per optimizer step, source label). Prefers the trial's
        analytic count; falls back to 6*N_params*tokens. A trial hook that
        raises downgrades to the fallback — FLOPs accounting must never
        fail training."""
        try:
            f = trial.train_step_flops()
        except Exception:  # noqa: BLE001 - observability is best-effort
            f = None
        if f is not None:
            return float(getattr(f, "total", f)), "analytic"
        n_params = param_count(state.params)
        try:
            tokens_per_sample = int(trial.tokens_per_sample() or 1)
        except Exception:  # noqa: BLE001 - observability is best-effort
            tokens_per_sample = 1
        tokens = trial.global_batch_size * max(1, tokens_per_sample)
        return flops_mod.dense_train_flops_per_token(n_params) * tokens, \
            "dense_6n"

    @property
    def _span(self):
        """The tracer's span factory, or the shared no-op when telemetry is
        off — boundary-only call sites (save/restore/sync), never per batch."""
        tel = self._telemetry
        return tel.tracer.span if tel is not None else null_span

    # -- the loop -----------------------------------------------------------

    def fit(self, latest_checkpoint: Optional[str] = None) -> Dict[str, Any]:
        try:
            return self._fit_inner(latest_checkpoint)
        except BaseException:
            # join local uploader threads so the crash doesn't kill them
            # mid-upload — WITHOUT collectives (other ranks may be mid-loop
            # or dead; a collective here would hang or corrupt their
            # exchanges). Nothing is published; the error stays primary.
            try:
                self.core.checkpoint.abort_async()
            except Exception:
                pass
            raise

    def _fit_inner(self, latest_checkpoint: Optional[str] = None
                   ) -> Dict[str, Any]:
        trial, config = self.trial, self.config
        dist = self.core.distributed
        mesh = self.mesh

        rng = jax.random.PRNGKey(config.experiment_seed)
        init_rng, state_rng = jax.random.split(rng)
        # eval keys branch off the same seeded chain via fold_in (not a
        # 3-way split) so init/state keys — and restored runs — are
        # unchanged from earlier versions
        eval_rng = jax.random.fold_in(rng, 1)
        params = trial.initial_params(init_rng)
        tx = trial.optimizer()
        state = create_train_state(params, tx, state_rng)
        shardings = state_shardings(state, mesh, trial.sharding_rules())

        data_iter = iter(trial.training_data())
        try:
            first_batch = next(data_iter)
        except StopIteration:
            raise RuntimeError("training_data() yielded no batches") from None
        batch_sharding = jax.tree.map(
            lambda spec: NamedSharding(mesh, spec),
            trial.batch_spec(first_batch),
        )

        batches_trained = 0
        if latest_checkpoint:
            state, batches_trained = self._restore(latest_checkpoint, state,
                                                   shardings)
        else:
            state = jax.device_put(state, shardings)

        opt = config.optimizations
        k = max(1, int(opt.steps_per_dispatch))
        prefetch_depth = max(0, int(opt.prefetch_depth))

        train_step = make_train_step(
            trial.loss, tx, mesh=mesh, state_sharding=shardings,
            batch_sharding=batch_sharding,
            apply_statistics=trial.apply_statistics,
        )
        # k batches through one jitted lax.scan program; remainders smaller
        # than k use the single-step program above, so batch order and the
        # rng chain are identical to the unfused loop
        fused_step = None
        if k > 1:
            fused_step = make_train_step(
                trial.loss, tx, mesh=mesh, state_sharding=shardings,
                batch_sharding=batch_sharding, steps_per_dispatch=k,
                apply_statistics=trial.apply_statistics,
            )
        eval_step = make_eval_step(
            trial.eval_metrics, state_sharding=shardings,
            batch_sharding=batch_sharding, rng=eval_rng,
        )

        # telemetry (observability: block; None when disabled — the hot loop
        # below then runs the *unwrapped* callables and feeder, so the
        # disabled path adds nothing per step). The sync makes each
        # train_dispatch span cover device completion, not just enqueue.
        tel = self._telemetry
        span = tel.tracer.span if tel is not None else null_span
        anomaly = None
        memmon = None
        if tel is not None:
            # explicit lower()/compile() capture (telemetry/xla.py): the
            # compile that runs is the compile that was measured, and the
            # program fingerprint + cost_analysis FLOPs land in the
            # registry before the first step dispatches
            train_step, _ = capture_compile(
                train_step, (state, first_batch),
                program="train_step",
                registry=tel.registry, tracer=tel.tracer)
            if fused_step is not None:
                fused_step, _ = capture_compile(
                    fused_step, (state,) + (first_batch,) * k,
                    program=f"train_step_fused_k{k}",
                    registry=tel.registry, tracer=tel.tracer)
            # rolling median/MAD straggler detection over steady-state
            # dispatch durations (compiles are excluded by wrap_jit)
            anomaly = StepTimeAnomalyDetector(
                tel.registry, tracer=tel.tracer,
                window=tel.anomaly_window,
                threshold=tel.anomaly_threshold,
                min_samples=tel.anomaly_min_samples)
            memmon = DeviceMemoryMonitor(tel.registry)
            train_step = tel.wrap_jit("train_dispatch", train_step,
                                      sync=jax.block_until_ready,
                                      observe=anomaly.observe)
            if fused_step is not None:
                fused_step = tel.wrap_jit("train_dispatch", fused_step,
                                          sync=jax.block_until_ready,
                                          observe=anomaly.observe)
            eval_step = tel.wrap_jit("eval_dispatch", eval_step,
                                     sync=jax.block_until_ready)

        # analytic FLOPs/MFU accounting (telemetry/flops.py) — resolved
        # once here, reported per chunk. Only when telemetry is on: the
        # disabled hot loop must stay byte-identical.
        step_flops = 0.0
        flops_source = peak_label = ""
        peak_total = 0.0
        if tel is not None:
            step_flops, flops_source = self._resolve_step_flops(trial, state)
            n_devices = (int(mesh.devices.size) if mesh is not None
                         else jax.device_count())
            peak, peak_label = flops_mod.peak_flops_estimate()
            if peak is None:
                # an accelerator that is not in the peak table: FLOP/s are
                # still reported, MFU is not (never a guessed denominator)
                logger.warning(
                    "no published peak for this device (%s): MFU will not "
                    "be reported", peak_label)
            peak_total = (peak or 0.0) * max(1, n_devices)

        sched_unit = config.scheduling_unit
        val_period = self._to_batches(config.min_validation_period, 0)
        ckpt_period = self._to_batches(config.min_checkpoint_period, 0)
        policy = config.checkpoint_policy
        smaller = config.searcher.smaller_is_better
        searcher_metric = config.searcher.metric

        # skip already-trained batches on restore so data order lines up;
        # index-capable iterators (BatchIterator.skip_batches) fast-forward
        # by arithmetic instead of materializing every replayed batch
        restored = batches_trained > 0
        if restored:
            # spanned so the goodput ledger books replay as restore badput,
            # not unattributed time (the restore itself is already spanned
            # as checkpoint_restore in _restore_one)
            with self._span("restore_replay", batches=batches_trained - 1):
                to_skip = batches_trained - 1  # first_batch discarded below
                while to_skip > 0:
                    skipped = _skip_batches(data_iter, to_skip)
                    to_skip -= skipped
                    if to_skip > 0:
                        # epoch exhausted mid-replay: roll into the next one
                        data_iter = iter(trial.training_data())
                        if skipped == 0:
                            # the previous epoch was already drained, so a
                            # zero-progress round means the fresh epoch must
                            # move — probe one batch to rule out an empty
                            # dataset (would otherwise loop forever)
                            if _skip_batches(data_iter, 1) == 0:
                                raise RuntimeError(
                                    "training_data() yielded no batches "
                                    "while replaying restored progress")
                            to_skip -= 1

        def batches() -> Iterator[Any]:
            if not restored:
                yield first_batch
            yield from data_iter
            while True:  # repeat dataset
                yield from iter(trial.training_data())

        batch_gen = batches()

        one_process = jax.process_count() == 1

        def to_device(batch: Any) -> Any:
            if one_process:
                return jax.device_put(batch, batch_sharding)
            # across processes device_put first checks, with an all-gather,
            # that every process passed the same value. Issued from the
            # prefetch thread, that collective and the step's are launched
            # in an order that differs between processes, and the gang
            # deadlocks. Every process holds the whole batch: each places
            # the shards its own devices hold and asks no other.
            def place(x: Any, sharding: NamedSharding) -> jax.Array:
                x = np.asarray(x)
                return jax.make_array_from_callback(
                    x.shape, sharding, x.__getitem__)

            return jax.tree.map(place, batch, batch_sharding)

        # async device prefetch: a producer thread overlaps host input +
        # device_put with XLA compute (depth 0 = the old synchronous path);
        # fused dispatch consumes k batches at once, so scale the buffer
        feed = make_device_feeder(
            batch_gen, to_device,
            depth=prefetch_depth * k if prefetch_depth else 0,
            name="train-prefetch",
            tracer=tel.tracer if tel is not None else None,
            registry=tel.registry if tel is not None else None,
        )
        if tel is not None:
            feed = tel.wrap_feeder(feed)

        acc = MetricAccumulator()
        last_val: Dict[str, float] = {}
        best_val: Optional[float] = None
        last_val_at = batches_trained
        last_ckpt_at = batches_trained
        preempted = False
        result: Dict[str, Any] = {}

        # optional observability wired by the exec layer (None in
        # local/unmanaged runs): profiler (≈ ProfilerAgent) + tensorboard
        profiler = self.core.profiler
        tb = self.core.tensorboard

        # truncated validation must be visible: dropped remainder batches
        # are counted (examples, not batches), surfaced once per fit in the
        # log and continuously in a telemetry gauge
        eval_dropped = {"examples": 0, "warned": False}

        def validate() -> Dict[str, float]:
            vdata = trial.validation_data()
            if vdata is None:
                return {}

            def full_batches() -> Iterator[Any]:
                # drop the shape-mismatched remainder batch (the
                # drop_remainder contract): a second batch shape would mean
                # a second eval_step compile every validation — eval stays
                # a single compiled program
                first_shapes = None
                for vb in vdata:
                    shapes = tuple(
                        np.shape(leaf) for leaf in jax.tree.leaves(vb))
                    if first_shapes is None:
                        first_shapes = shapes
                    elif shapes != first_shapes:
                        leaves = jax.tree.leaves(vb)
                        n = int(np.shape(leaves[0])[0]) if (
                            leaves and np.ndim(leaves[0])) else 1
                        eval_dropped["examples"] += n
                        continue
                    yield vb

            with span("validate"):
                vacc = MetricAccumulator()
                vfeed = make_device_feeder(
                    full_batches(), to_device,
                    depth=prefetch_depth, name="eval-prefetch",
                    tracer=tel.tracer if tel is not None else None)
                try:
                    for vbatch in vfeed:
                        vacc.add(eval_step(state, vbatch))
                finally:
                    vfeed.close()
                metrics = vacc.result() if len(vacc) else {}
            if eval_dropped["examples"]:
                if not eval_dropped["warned"]:
                    eval_dropped["warned"] = True
                    logger.warning(
                        "validation dropped %d examples in shape-mismatched "
                        "remainder batches (drop_remainder contract); pad "
                        "or size the eval set to a batch multiple for full "
                        "coverage", eval_dropped["examples"])
                if tel is not None:
                    tel.registry.gauge(
                        "eval_examples_dropped",
                        "eval examples lost to shape-mismatched remainder "
                        "batches this fit").set(eval_dropped["examples"])
            if metrics:
                self.core.train.report_validation_metrics(batches_trained, metrics)
                if tb is not None:
                    tb.add_scalars("validation", metrics, batches_trained)
            return metrics

        # the prefetcher must join on EVERY exit — normal completion,
        # preemption, or a mid-chunk exception (no leaked producer
        # threads, no deadlock on a dead consumer)
        try:
            for op in self.core.searcher.operations():
                if op.length is None:
                    raise RuntimeError(
                        "searcher.max_length is not set: the searcher operation "
                        "has no training target. Set searcher.max_length in the "
                        "experiment config (e.g. {'batches': 1000}) or provide a "
                        "searcher_source."
                    )
                target = self._to_batches(op.length, 0)
                while batches_trained < target and not preempted:
                    chunk_end = min(
                        target,
                        (batches_trained // sched_unit + 1) * sched_unit,
                    )
                    t0 = time.perf_counter()
                    n0 = batches_trained
                    while batches_trained < chunk_end:
                        # one pair per dispatch (fused counts as one); a
                        # None check each when no plan is active
                        faults.point("training.pre_step")
                        if (fused_step is not None
                                and chunk_end - batches_trained >= k):
                            # k prefetched device batches → ONE dispatch
                            group = [next(feed) for _ in range(k)]
                            state, metrics = fused_step(state, *group)
                            acc.add(metrics, count=k)
                            batches_trained += k
                        else:
                            state, metrics = train_step(state, next(feed))
                            acc.add(metrics)
                            batches_trained += 1
                        faults.point("training.post_step")
                    # ---- reporting boundary (one host sync per chunk) ----
                    with span("host_sync"):
                        train_metrics = acc.result()
                    dt = time.perf_counter() - t0
                    # queue-wait is the consumer-visible input stall (the
                    # overlap residue); host-time is the producer's true input
                    # cost even when hidden under compute
                    t_wait = feed.take_queue_wait()
                    t_host = feed.take_host_time()
                    train_metrics["batches_per_second"] = (batches_trained - n0) / dt
                    train_metrics["samples_per_second"] = (
                        (batches_trained - n0) * trial.global_batch_size / dt
                    )
                    if tel is not None and step_flops:
                        # FLOPs throughput + MFU against the published
                        # peak of the device kind the runtime reports; the
                        # provenance label travels with the number, and an
                        # unknown kind publishes no MFU at all
                        # (docs/observability.md)
                        fps = step_flops * train_metrics["batches_per_second"]
                        mfu_val = flops_mod.mfu(fps, peak_total)
                        train_metrics["flops_per_sec"] = fps
                        reg = tel.registry
                        reg.gauge("samples_per_sec",
                                  "training throughput").set(
                            train_metrics["samples_per_second"])
                        reg.gauge("flops_per_sec",
                                  "analytic model FLOPs per second").set(fps)
                        if mfu_val is not None:
                            train_metrics["mfu"] = mfu_val
                            reg.gauge("mfu",
                                      "model FLOPs utilization vs peak "
                                      "(provenance: mfu_peak_info labels)"
                                      ).set(mfu_val)
                            reg.gauge("mfu_peak_flops",
                                      "peak FLOPs the MFU denominator uses "
                                      "(all participating devices)").set(
                                peak_total)
                        reg.gauge(
                            "mfu_peak_info",
                            "constant 1; labels carry the peak provenance "
                            "and FLOPs-count source",
                            labels={"assumed": peak_label,
                                    "flops_source": flops_source}).set(1)
                    if memmon is not None:
                        # per-device gauges + the between-boundary peak
                        # watermark (profiler's sampler thread feeds the
                        # same monitor path at 1 Hz when profiling is on)
                        memmon.sample()
                        tel.registry.gauge(
                            "device_memory_peak_bytes",
                            "peak summed device bytes_in_use since the "
                            "previous chunk boundary").set(memmon.take_peak())
                    # the span carries the report: a trace reader finds the
                    # loss's own metrics (an expert layer's counts) here
                    with span("training_report") as report:
                        report.set(**train_metrics)
                        self.core.train.report_training_metrics(
                            batches_trained, train_metrics)
                    if profiler is not None:
                        # chunk-level split of the hot loop: input stall vs the
                        # rest (dispatch + device compute up to the acc sync)
                        profiler.record_batch_timing(
                            batches_trained, dataloading_s=t_host,
                            compute_s=max(dt - t_wait, 0.0),
                            queue_wait_s=t_wait, steps_per_dispatch=k,
                            prefetch_depth=prefetch_depth)
                    if tel is not None:
                        # batched telemetry shipping rides the chunk
                        # boundary (and the profiler's flush thread)
                        tel.publish(profiler, batches_trained)
                    if tb is not None:
                        tb.add_scalars("training", train_metrics, batches_trained)
                    op.report_progress(batches_trained)

                    if val_period and batches_trained - last_val_at >= val_period:
                        last_val = validate()
                        last_val_at = batches_trained
                        if searcher_metric in last_val:
                            v = last_val[searcher_metric]
                            is_best = best_val is None or (
                                v < best_val if smaller else v > best_val
                            )
                            if is_best:
                                best_val = v
                                if policy == "best":
                                    self._save(state, batches_trained, "best",
                                               metric=v)
                                    last_ckpt_at = batches_trained

                    # a metric only describes the saved weights when validation
                    # ran at THIS batch count — a stale value would misattribute
                    # quality to drifted weights (and mislead best-checkpoint GC)
                    def fresh_metric():
                        if last_val_at == batches_trained:
                            return last_val.get(searcher_metric)
                        return None

                    if ckpt_period and batches_trained - last_ckpt_at >= ckpt_period:
                        if policy != "none":
                            self._save(state, batches_trained, "periodic",
                                       metric=fresh_metric())
                        last_ckpt_at = batches_trained

                    if self.core.preempt.should_preempt():
                        preempted = True

                if preempted:
                    self._save(state, batches_trained, "preemption",
                               metric=fresh_metric())
                    self.core.train.report_early_exit("preempted")
                    break

                # op complete: ensure a fresh validation at the boundary
                final_val = validate()
                if final_val:
                    last_val = final_val
                    last_val_at = batches_trained
                    if searcher_metric in final_val:
                        v = final_val[searcher_metric]
                        if best_val is None or (v < best_val if smaller else v > best_val):
                            best_val = v
                op.complete(last_val.get(searcher_metric, float("nan")))
        finally:
            feed.close()

        if not preempted and policy != "none" and batches_trained > last_ckpt_at:
            metric = (last_val.get(searcher_metric)
                      if last_val_at == batches_trained else None)
            self._save(state, batches_trained, "final", metric=metric)

        # drain any in-flight async checkpoint uploads before the process
        # can exit — the flush-then-exit rule (SURVEY §7 hard parts); a
        # preempted run must not lose the checkpoint it just handed off
        self.core.checkpoint.wait_async()

        result.update(
            batches_trained=batches_trained,
            last_validation=last_val,
            best_validation=best_val,
            preempted=preempted,
        )
        self._final_state = state
        return result
