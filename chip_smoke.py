#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py             # one TPU chip: device, train, serve
    python chip_smoke.py --chips 4   # four chips: sharded training only

One process (the chip belongs to one process). It drives the two hot paths
through the entry points a user calls, at GPT-2-small width with seeded
random weights, and checks what comes out:

- **device**: JAX must report a TPU. No CPU fallback, no retry.
- **train**: ``examples/gpt_fsdp/fsdp.yaml`` (only the chip-count fields,
  the lengths and the observability block overridden) ->
  ``LocalExperimentRunner`` -> ``Trainer``, then one more leg that can only
  run by restoring the checkpoint the first leg wrote.
- **serve**: ``InferenceEngine.from_serving_config`` -> ``warmup`` ->
  ``ServingHTTPServer`` -> ``generate_over_http``, every token checked
  against the uncached ``gpt.apply`` on the same weights.
- **--chips 4** runs only sharded training (``mesh: {fsdp: 4}``) and the
  one-device run it is compared with.

One JSON object per phase goes to stdout. Any failure is a non-zero exit
and no ``"ok": true``; on success the last line is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Times printed here are smoke figures of one run, not benchmark results.
"""
from __future__ import annotations

import argparse
import copy
import functools
import glob
import importlib.util
import json
import math
import os
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))
EXAMPLE_DIR = os.path.join(REPO_ROOT, "examples", "gpt_fsdp")

# Engine vs uncached reference: tokens must be the reference's argmax, or
# (a bf16 tie broken the other way) trail its top logit by at most this.
LOGIT_TIE_TOL = 0.05
# One-device vs fsdp=4 training: per-step loss, relative.
SHARDED_LOSS_RTOL = 1e-2


def emit(obj: Dict[str, Any]) -> None:
    print(json.dumps(obj), flush=True)


@functools.cache
def gpt_trial_class():
    """``examples/gpt_fsdp/model_def.py:GPTTrial``, loaded by path: every
    example calls its module ``model_def``, so the name cannot be trusted
    in a process that has imported another one (the test suite has)."""
    spec = importlib.util.spec_from_file_location(
        "gpt_fsdp_model_def", os.path.join(EXAMPLE_DIR, "model_def.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.GPTTrial


class CompileCounter:
    """Counts XLA compile requests and persistent-cache hits through JAX's
    own monitoring events: requests - hits = cold compiles."""

    REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
    HITS = "/jax/compilation_cache/cache_hits"
    BACKEND_S = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax.monitoring

        self.requests = self.hits = 0
        self.backend_s = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)

    def _on_event(self, name: str, **kw: Any) -> None:
        if name == self.REQUESTS:
            self.requests += 1
        elif name == self.HITS:
            self.hits += 1

    def _on_secs(self, name: str, secs: float, **kw: Any) -> None:
        if name == self.BACKEND_S:
            self.backend_s += secs

    def snapshot(self) -> Dict[str, Any]:
        return {"compile_requests": self.requests,
                "persistent_cache_hits": self.hits,
                "cold_compiles": self.requests - self.hits,
                "backend_compile_s": round(self.backend_s, 2)}

    def since(self, before: Dict[str, Any]) -> Dict[str, Any]:
        now = self.snapshot()
        return {k: round(now[k] - before[k], 2) for k in now}


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def device_phase(n_chips: int) -> Dict[str, Any]:
    import jax
    import jaxlib

    from determined_clone_tpu.telemetry import flops
    from determined_clone_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    cache_dir = configure_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU (JAX reports platform {dev.platform!r}); "
            f"this script does not run on anything else")
    if len(devices) != n_chips:
        raise SystemExit(
            f"chip_smoke: this run is for {n_chips} chip(s), JAX reports "
            f"{len(devices)} (--chips 4 is the four-chip run)")
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 - reporting only
        libtpu = None
    peak, label = flops.peak_flops_estimate()
    info = {
        "phase": "device", "platform": dev.platform,
        "kind": dev.device_kind, "count": len(devices),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": libtpu, "compile_cache_dir": cache_dir,
        "compile_cache_from_env": bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        # what the C++ agent's chip detection can see (agent.cc)
        "dev_nodes": sorted(glob.glob("/dev/accel*")
                            + glob.glob("/dev/vfio/*")),
        "peak_label": label, "peak_bf16_flops": peak,
        "peak_hbm_bytes_per_s": flops.TPU_HBM_BYTES_PER_S.get(
            flops.TPU_DEVICE_KINDS.get(dev.device_kind, "")),
    }
    if peak is None:
        raise SystemExit(
            f"chip_smoke: device kind {dev.device_kind!r} is not in the "
            f"peak table (telemetry/flops.py)")
    emit(info)
    return info


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def experiment_config(n_chips: int, *, widths: Optional[Dict[str, Any]],
                      max_batches: int, **top: Any):
    """fsdp.yaml with the chip count, the run length and the observability
    block overridden — widths, vocab, sequence length and global batch stay
    the YAML's (``widths`` is for the tiny CPU rehearsal in the tests)."""
    from determined_clone_tpu.config.experiment import ExperimentConfig

    base = ExperimentConfig.from_yaml(os.path.join(EXAMPLE_DIR, "fsdp.yaml"))
    raw = copy.deepcopy(base.raw)
    raw["resources"]["slots_per_trial"] = n_chips
    raw["hyperparameters"]["mesh"] = {"fsdp": n_chips}
    raw["hyperparameters"].update(widths or {})
    raw["searcher"]["max_length"] = {"batches": max_batches}
    # on, so that the trainer's compile capture and MFU gauges run too
    raw["observability"] = {"enabled": True, "ship_spans": True}
    raw.update(top)
    return ExperimentConfig.from_dict(raw)


def compile_train_step(config: Any, hparams: Dict[str, Any], mesh: Any):
    """The trial's train step, built as ``Trainer._fit_inner`` builds it and
    compiled from shapes alone; returns the ``jax.stages.Compiled``."""
    import jax
    from jax.sharding import NamedSharding

    from determined_clone_tpu.training import TrialContext
    from determined_clone_tpu.training.train_step import (
        create_train_state,
        make_train_step,
        state_shardings,
    )

    trial = gpt_trial_class()(TrialContext(config=config, hparams=hparams,
                                           core=None, mesh=mesh))
    tx = trial.optimizer()
    state = jax.eval_shape(
        lambda k: create_train_state(trial.initial_params(k), tx, k),
        jax.random.PRNGKey(config.experiment_seed))
    shardings = state_shardings(state, mesh, trial.sharding_rules())
    batch = next(iter(trial.training_data()))
    batch_sharding = jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                                  trial.batch_spec(batch))
    step = make_train_step(trial.loss, tx, mesh=mesh,
                           state_sharding=shardings,
                           batch_sharding=batch_sharding)

    def shaped(x: Any, s: Any) -> Any:
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s)

    return step.lower(jax.tree.map(shaped, state, shardings),
                      jax.tree.map(shaped, batch, batch_sharding)).compile()


def _read_metrics(path: str, group: str) -> List[Dict[str, Any]]:
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return [r for r in rows if r["group"] == group]


def _require_finite_and_falling(losses: List[float], what: str) -> None:
    if not losses or not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"{what}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{what}: loss did not fall: {losses}")


def train_phase(workdir: str, *, widths: Optional[Dict[str, Any]] = None,
                units: int = 3) -> Dict[str, Any]:
    import jax

    from determined_clone_tpu.api.inprocess import InProcessMaster
    from determined_clone_tpu.core import LocalCheckpointRegistry
    from determined_clone_tpu.experiment.runner import LocalExperimentRunner
    from determined_clone_tpu.models import gpt
    from determined_clone_tpu.searcher import ValidateAfter
    from determined_clone_tpu.searcher.methods import SingleSearch
    from determined_clone_tpu.telemetry import flops
    from determined_clone_tpu.telemetry.metrics import parse_prometheus_text

    on_tpu = jax.devices()[0].platform == "tpu"
    unit = experiment_config(1, widths=widths,
                             max_batches=1).scheduling_unit
    first_leg = units * unit
    # one validation and one periodic checkpoint fall inside the first leg
    period = {"batches": (units - 1) * unit}
    config = experiment_config(
        1, widths=widths, max_batches=first_leg,
        min_validation_period=period, min_checkpoint_period=period)

    class ResumeOnce(SingleSearch):
        """The YAML's single searcher, plus one more step after the trial
        has validated at max_length: the runner can only deliver it by
        restoring the checkpoint the first leg saved (the pause/resume
        every adaptive searcher relies on)."""

        def on_validation_completed(self, request_id, metric, units_done):
            if units_done == first_leg:
                return [ValidateAfter(request_id, first_leg + 1)]
            return super().on_validation_completed(request_id, metric,
                                                   units_done)

    master = InProcessMaster()
    runner = LocalExperimentRunner(
        config, gpt_trial_class(), storage_path=workdir, master=master,
        method=ResumeOnce(config.searcher, config.hyperparameters,
                          seed=config.experiment_seed))
    t0 = time.monotonic()
    result = runner.run()
    wall_s = time.monotonic() - t0

    (rec,) = result.trials.values()
    # the runner turns a trial's exception into a restart; none is allowed
    if rec.state != "completed" or rec.restarts or not result.shutdown:
        raise AssertionError(f"trial did not complete cleanly: {rec}")

    training = _read_metrics(rec.metrics_path, "training")
    validation = _read_metrics(rec.metrics_path, "validation")
    steps = [r["steps_completed"] for r in training]
    losses = [r["metrics"]["loss"] for r in training]
    expect_steps = [unit * (i + 1) for i in range(units)] + [first_leg + 1]
    if steps != expect_steps:
        raise AssertionError(f"reports at {steps}, expected {expect_steps}")
    _require_finite_and_falling(losses, "train")
    if not all(math.isfinite(r["metrics"]["loss"]) for r in validation):
        raise AssertionError(f"non-finite validation loss: {validation}")

    # the second leg really started from the saved weights: its one step
    # sits below the first report, where a re-initialised model could not
    spans = master.spans(trial_id=rec.request_id)
    restores = [s for s in spans if s["name"] == "checkpoint_restore"]
    saves = [s for s in spans if s["name"] == "checkpoint_save"]
    if len(restores) != 1 or losses[-1] >= losses[0]:
        raise AssertionError(
            f"resume leg: {len(restores)} restores, losses {losses}")
    ckpts = [r for r in LocalCheckpointRegistry(
        os.path.join(workdir, "checkpoints.jsonl")).list()
        if r.get("trial_id") == rec.request_id]
    if not any(r["metadata"]["steps_completed"] == first_leg for r in ckpts):
        raise AssertionError(f"no checkpoint at batch {first_leg}: {ckpts}")

    # the compile the trainer captured (None -> no explicit span, no gauge)
    samples = parse_prometheus_text(master.metrics_text())["samples"]
    captured = [s for s in spans if s["name"] == "xla_compile"
                and (s.get("args") or {}).get("explicit")
                and s["args"].get("program") == "train_step"]
    compile_gauge = [v for name, _, v in samples
                     if name == "xla_compile_seconds"]
    if len(captured) != 2 or not compile_gauge:
        raise AssertionError(
            f"compile record missing: {len(captured)} captures (one per "
            f"leg expected), gauge {compile_gauge}")

    # MFU: published against the peak of THIS device kind
    (peak_labels,) = [labels for name, labels, _ in samples
                      if name == "mfu_peak_info"]
    _, expect_label = flops.peak_flops_estimate()
    if peak_labels["assumed"] != expect_label \
            or peak_labels["flops_source"] != "analytic":
        raise AssertionError(
            f"MFU peak label {peak_labels}, expected {expect_label!r}")
    mfu = [r["metrics"].get("mfu") for r in training]
    if not all(v is not None and 0 < v < 1 for v in mfu):
        raise AssertionError(f"MFU out of range: {mfu}")

    # which attention the step took, read from the program itself
    from determined_clone_tpu.parallel.mesh import single_device_mesh

    hp = rec.hparams
    cfg = gpt.GPTConfig(attention_impl=str(hp["attention_impl"]))
    impl = gpt.resolved_attention_impl(cfg)
    compiled = compile_train_step(config, hp, single_device_mesh())
    has_kernel = "tpu_custom_call" in compiled.as_text()
    if on_tpu and (impl != "flash" or not has_kernel):
        raise AssertionError(
            f"on TPU the step must run the Pallas flash kernel: "
            f"resolved_attention_impl={impl!r}, custom call in the compiled "
            f"step: {has_kernel}")

    mem = jax.devices()[0].memory_stats() or {}
    steady = training[units - 1]["metrics"]
    out = {
        "phase": "train", "config": "examples/gpt_fsdp/fsdp.yaml",
        "widths": {k: hp[k] for k in (
            "n_layers", "d_model", "n_heads", "d_ff", "vocab_size",
            "seq_len", "global_batch_size")},
        "mesh": hp["mesh"], "attention_impl": impl,
        "pallas_custom_call_in_step": has_kernel,
        "steps": steps[-1], "reports_at": steps,
        "loss": [round(v, 4) for v in losses],
        "first_loss": round(losses[0], 4), "last_loss": round(losses[-1], 4),
        "validation_loss": [round(r["metrics"]["loss"], 4)
                            for r in validation],
        "checkpoints_saved": len(saves), "checkpoint_restored": True,
        # the second leg compiles the same step again: the persistent
        # cache is what makes it short
        "compile_s_by_leg": [round(s["dur_us"] / 1e6, 2) for s in captured],
        "smoke_seconds_per_step": round(
            1.0 / steady["batches_per_second"], 4),
        "smoke_mfu": round(steady["mfu"], 4),
        "mfu_peak_label": peak_labels["assumed"],
        "peak_hbm_bytes": mem.get("peak_bytes_in_use"),
        "wall_s": round(wall_s, 1),
    }
    emit(out)
    return out


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

# (prompt length, new tokens); the first four are sent at once so that
# continuous batching has something to batch
SERVE_REQUESTS = ((3, 12), (17, 12), (64, 16), (100, 8), (5, 8), (33, 12))
SERVE_CONCURRENT = 4


def serve_phase(model_cfg: Any, *, seed: int = 0) -> Dict[str, Any]:
    """The calls ``dct serve --selftest`` makes (cli.cmd_serve), with
    ``model_cfg`` and seeded random weights in place of its tiny preset."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from determined_clone_tpu.config.experiment import ServingConfig
    from determined_clone_tpu.models import gpt
    from determined_clone_tpu.serving import InferenceEngine
    from determined_clone_tpu.serving.http import (
        ServingHTTPServer,
        generate_over_http,
    )

    params = gpt.init(jax.random.PRNGKey(seed), model_cfg)
    rng = np.random.RandomState(seed)
    requests = [(rng.randint(0, model_cfg.vocab_size, n).tolist(), new)
                for n, new in SERVE_REQUESTS]
    replies: List[Optional[Dict[str, Any]]] = [None] * len(requests)
    errors: List[Exception] = []

    engine = InferenceEngine.from_serving_config(params, model_cfg,
                                                 ServingConfig())
    with engine:
        t0 = time.monotonic()
        # a jit's cache is its function's, shared by every engine of the
        # process: what this engine compiled is the growth
        before = engine.programs_compiled()
        programs = engine.warmup() - before
        warmup_s = time.monotonic() - t0
        with ServingHTTPServer(engine, host="127.0.0.1", port=0) as server:
            def ask(i: int) -> None:
                prompt, new = requests[i]
                try:
                    replies[i] = generate_over_http(server.url, prompt,
                                                    max_new_tokens=new)
                except Exception as e:  # noqa: BLE001 - re-raised below
                    errors.append(e)

            t0 = time.monotonic()
            threads = [threading.Thread(target=ask, args=(i,))
                       for i in range(SERVE_CONCURRENT)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for i in range(SERVE_CONCURRENT, len(requests)):
                ask(i)
            serve_s = time.monotonic() - t0
        stats = engine.stats()
        compiled_after = engine.programs_compiled() - before
    # the engine is closed: every KV block must be back in the pool
    engine.assert_kv_balanced(0)
    leaked = engine.kv_outstanding()
    if errors:
        raise errors[0]
    if leaked or engine.stats().free_blocks != engine.cache.num_blocks:
        raise AssertionError(f"{leaked} KV blocks leaked")
    if compiled_after != programs or programs > engine.program_budget():
        raise AssertionError(
            f"requests compiled past the warm-up: {programs} -> "
            f"{compiled_after} (budget {engine.program_budget()})")
    if stats.peak_active < 2:
        raise AssertionError(
            f"continuous batching never batched: peak_active="
            f"{stats.peak_active}")

    # Reference: the plain uncached forward on the same weights, teacher-
    # forced on each reply. If every token is its argmax the reply IS the
    # greedy decode gpt.apply would produce (induction over positions);
    # padding on the right cannot reach a causal position on the left.
    pad_to = 128 * math.ceil(max(len(p) + n for p, n in requests) / 128)
    full_logits = jax.jit(lambda p, toks: gpt.apply(p, model_cfg, toks))
    near_ties = []
    n_tokens = 0
    for i, ((prompt, new), reply) in enumerate(zip(requests, replies)):
        tokens = reply["tokens"]
        if len(tokens) != new or reply["finish_reason"] != "length":
            raise AssertionError(f"request {i}: bad reply {reply}")
        seq = prompt + tokens
        padded = jnp.asarray([seq + [0] * (pad_to - len(seq))], jnp.int32)
        logits = np.asarray(full_logits(params, padded)[0])
        if not np.isfinite(logits[:len(seq)]).all():
            raise AssertionError(f"request {i}: non-finite reference logits")
        for j, tok in enumerate(tokens):
            row = logits[len(prompt) + j - 1]
            n_tokens += 1
            if int(row.argmax()) == tok:
                continue
            near_ties.append({"request": i, "position": j, "engine": tok,
                              "reference": int(row.argmax()),
                              "gap": round(float(row.max() - row[tok]), 5)})
    if any(t["gap"] > LOGIT_TIE_TOL for t in near_ties):
        raise AssertionError(
            f"engine tokens trail the reference's top logit by more than "
            f"{LOGIT_TIE_TOL}: {near_ties}")
    out = {
        "phase": "serve",
        "widths": {"n_layers": model_cfg.n_layers,
                   "d_model": model_cfg.d_model,
                   "n_heads": model_cfg.n_heads, "d_ff": model_cfg.d_ff,
                   "vocab_size": model_cfg.vocab_size,
                   "max_seq_len": model_cfg.max_seq_len},
        "programs_compiled_in_warmup": programs,
        "program_budget": engine.program_budget(),
        "smoke_warmup_s": round(warmup_s, 1),
        "requests": len(requests), "concurrent": SERVE_CONCURRENT,
        "prompt_lens": [len(p) for p, _ in requests],
        "tokens": n_tokens, "peak_active": stats.peak_active,
        "smoke_serve_s": round(serve_s, 2),
        "tokens_match_reference": not near_ties,
        # a bf16 tie broken the other way, accepted within LOGIT_TIE_TOL
        "near_ties_accepted": near_ties, "logit_tie_tol": LOGIT_TIE_TOL,
        "leaked_kv_blocks": leaked,
    }
    emit(out)
    return out


# ---------------------------------------------------------------------------
# --chips 4: sharded training against one device
# ---------------------------------------------------------------------------

def _fit(n_chips: int, workdir: str, widths: Optional[Dict[str, Any]],
         steps: int):
    """One Trainer.fit of the trial on the first ``n_chips`` devices, the
    mesh built by the library from slots_per_trial and the mesh hparam.
    Returns the per-step losses and where the state lives."""
    import random

    import jax

    from determined_clone_tpu import core
    from determined_clone_tpu.telemetry import collectives
    from determined_clone_tpu.training import Trainer, TrialContext
    from determined_clone_tpu.training.train_step import state_shardings

    # one report per step, so that losses compare step by step
    config = experiment_config(n_chips, widths=widths, max_batches=steps,
                               scheduling_unit=1)
    hparams = config.hyperparameters.sample(
        random.Random(config.experiment_seed))
    backend = core.LocalMetricsBackend()  # keeps the records in memory
    with core.init(config=config, storage_path=workdir,
                   metrics_backend=backend, trial_id=n_chips) as cctx:
        tctx = TrialContext(config=config, hparams=hparams, core=cctx)
        mesh = tctx.mesh
        if mesh.devices.size != n_chips:
            raise AssertionError(
                f"asked for {n_chips} chips, mesh has {mesh.devices.size}")
        trial = gpt_trial_class()(tctx)
        trainer = Trainer(trial)
        trainer.fit()
        state = trainer._final_state
        specs = state_shardings(state, mesh, trial.sharding_rules())
        want = set(mesh.devices.flat)
        sharded = misplaced = 0
        held = dict.fromkeys(mesh.devices.flat, 0)
        for leaf, sh in zip(jax.tree.leaves(state), jax.tree.leaves(specs)):
            for shard in leaf.addressable_shards:
                held[shard.device] += shard.data.nbytes
            if any(axis is not None for axis in sh.spec):
                sharded += 1
                piece = leaf.sharding.shard_shape(leaf.shape)
                if leaf.sharding.device_set != want \
                        or math.prod(piece) * n_chips != leaf.size:
                    misplaced += 1
        # None where the backend keeps no statistics (the CPU rehearsal)
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in mesh.devices.flat]
        compiled = compile_train_step(config, hparams, mesh)
        text = compiled.as_text()
        coll = collectives.parse_hlo_collectives(text, mesh=mesh)
    return {
        "chips": n_chips, "mesh": {k: v for k, v in mesh.shape.items()
                                   if v > 1} or {"fsdp": 1},
        "loss": [round(r["metrics"]["loss"], 5) for r in backend.records
                 if r["group"] == "training"],
        "leaves_the_rules_shard": sharded, "leaves_misplaced": misplaced,
        "state_bytes_per_device": list(held.values()),
        "bytes_in_use_per_device": in_use,
        "pallas_custom_call_in_step": "tpu_custom_call" in text,
        "collectives": {kind: coll.count(kind, "fsdp")
                        for kind in collectives.COLLECTIVE_KINDS
                        if coll.count(kind, "fsdp")},
    }


def sharded_phase(workdir: str, *, n_chips: int = 4,
                  widths: Optional[Dict[str, Any]] = None,
                  steps: int = 8) -> Dict[str, Any]:
    import jax

    on_tpu = jax.devices()[0].platform == "tpu"
    one = _fit(1, workdir, widths, steps)
    many = _fit(n_chips, workdir, widths, steps)
    for fit in (one, many):
        if len(fit["loss"]) != steps:
            raise AssertionError(f"{len(fit['loss'])} reports for {steps} "
                                 f"steps on {fit['chips']} chip(s)")
        _require_finite_and_falling(fit["loss"], f"{fit['chips']} chip(s)")
    worst = max(abs(a - b) / abs(a)
                for a, b in zip(one["loss"], many["loss"]))
    if worst > SHARDED_LOSS_RTOL:
        raise AssertionError(
            f"per-step losses diverge by {worst:.4f} relative "
            f"(> {SHARDED_LOSS_RTOL}): {one['loss']} vs {many['loss']}")
    if not many["leaves_the_rules_shard"] or many["leaves_misplaced"]:
        raise AssertionError(f"state is not spread over the mesh: {many}")
    for key in ("state_bytes_per_device", "bytes_in_use_per_device"):
        if not all(b is None or b > 0 for b in many[key]) \
                or (on_tpu and None in many[key]):
            raise AssertionError(f"a device holds nothing: {many[key]}")
    c = many["collectives"]
    # the gradient reduction and the parameter gather FSDP implies
    if not c.get("all-gather") or not (c.get("reduce-scatter")
                                       or c.get("all-reduce")):
        raise AssertionError(f"no FSDP collectives in the step: {c}")
    if on_tpu and not (one["pallas_custom_call_in_step"]
                       and many["pallas_custom_call_in_step"]):
        raise AssertionError("the flash kernel is missing from a step")
    out = {"phase": "sharded_train", "steps": steps,
           "max_relative_loss_gap": round(worst, 6),
           "loss_rtol": SHARDED_LOSS_RTOL, "one_device": one,
           "sharded": many}
    emit(out)
    return out


# ---------------------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only sharded training (fsdp=4) and "
                             "its one-device comparison")
    parser.add_argument("--seed", type=int, default=0,
                        help="seed of the serving weights and prompts")
    args = parser.parse_args(argv)

    t0 = time.monotonic()
    device = device_phase(args.chips)
    compiles = CompileCounter()

    from determined_clone_tpu.models import gpt

    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as workdir:
        if args.chips == 1:
            before = compiles.snapshot()
            train_phase(workdir)
            emit({"phase": "train_compiles", **compiles.since(before)})
            before = compiles.snapshot()
            # GPT-2-small: GPTConfig's defaults at the YAML's sequence length
            serve_phase(gpt.GPTConfig(max_seq_len=1024), seed=args.seed)
            emit({"phase": "serve_compiles", **compiles.since(before)})
        else:
            sharded_phase(workdir, n_chips=args.chips)
    emit({"phase": "total", "wall_s": round(time.monotonic() - t0, 1),
          **compiles.snapshot()})
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
