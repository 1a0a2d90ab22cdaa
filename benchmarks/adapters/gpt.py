"""Adapter ``gpt``: how a configuration file of the GPT family becomes the
system under test — a trial for ``training/trainer.py`` and an engine for
``serving/engine.py`` — and where its seeded weights come from.

This is the only file of the benchmark that imports the program. A new
model family arrives as another file in this directory, named by the
``adapter`` key of its configuration file.

The configuration file keeps its source's key names (``n_layer``,
``n_embd``, ``n_head``, ``n_inner``, ``n_positions``, ``vocab_size``); the
vocabulary the program is built with is ``assumed.padded_vocab_size``.
"""
from __future__ import annotations

import functools
import importlib.util
import os
from typing import Any, Dict, Iterable, Optional

import jax
import jax.numpy as jnp

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
EXAMPLE = os.path.join(REPO_ROOT, "examples", "gpt_fsdp", "model_def.py")

REFERENCE = "gpt2"  # benchmarks/reference/gpt2.py


def dims(config: Dict[str, Any]) -> Dict[str, int]:
    """The sizes both the program and the reference are built with."""
    return {
        "vocab": int(config["assumed"]["padded_vocab_size"]),
        "layers": int(config["n_layer"]),
        "d_model": int(config["n_embd"]),
        "heads": int(config["n_head"]),
        "d_ff": int(config["n_inner"]),
        "positions": int(config["n_positions"]),
    }


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (seeds above 2**31
    included)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _weights(key: jax.Array, d: Dict[str, int]) -> Dict[str, Any]:
    """GPT-2's published initialisation: normal(0, 0.02) matrices, the two
    projections into the residual stream scaled by 1/sqrt(2 L), LayerNorm
    at (1, 0), biases at 0. float32, the type the program trains and
    serves its parameters in."""
    V, L, D, F = d["vocab"], d["layers"], d["d_model"], d["d_ff"]
    k = jax.random.split(key, 5)
    resid = 0.02 / (2 * L) ** 0.5
    f32 = jnp.float32

    def normal(key, shape, std=0.02):
        return std * jax.random.normal(key, shape, f32)

    def ln():
        return {"scale": jnp.ones((L, D), f32), "bias": jnp.zeros((L, D), f32)}

    return {
        "embed": {"table": normal(k[0], (V, D))},
        "blocks": {
            "ln1": ln(),
            "attn_qkv": {"kernel": normal(k[1], (L, D, 3 * D)),
                         "bias": jnp.zeros((L, 3 * D), f32)},
            "attn_out": {"kernel": normal(k[2], (L, D, D), resid),
                         "bias": jnp.zeros((L, D), f32)},
            "ln2": ln(),
            "mlp_up": {"kernel": normal(k[3], (L, D, F)),
                       "bias": jnp.zeros((L, F), f32)},
            "mlp_down": {"kernel": normal(k[4], (L, F, D), resid),
                         "bias": jnp.zeros((L, D), f32)},
        },
        "final_norm": {"scale": jnp.ones((D,), f32),
                       "bias": jnp.zeros((D,), f32)},
    }


def weight_shardings(config: Dict[str, Any], mesh: Any) -> Any:
    """Where the program's rules put each parameter on ``mesh``."""
    from determined_clone_tpu.models import gpt

    shapes = jax.eval_shape(
        lambda: _weights(jax.random.PRNGKey(0), dims(config)))
    return gpt.GPT_SHARDING_RULES.shardings_for(shapes, mesh)


def make_weights(config: Dict[str, Any], seed: int,
                 shardings: Any = None) -> Dict[str, Any]:
    """The model's weights from the seed, on the device, in one jitted call
    (already laid out as ``shardings`` says where a mesh spans chips)."""
    d = dims(config)
    make = jax.jit(functools.partial(_weights, d=d), out_shardings=shardings)
    return make(seed_key(seed))


def model_config(config: Dict[str, Any], *, remat: bool) -> Any:
    from determined_clone_tpu.models import gpt

    d = dims(config)
    return gpt.GPTConfig(
        vocab_size=d["vocab"], n_layers=d["layers"], d_model=d["d_model"],
        n_heads=d["heads"], d_ff=d["d_ff"], max_seq_len=d["positions"],
        remat=remat, attention_impl="auto")


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

@functools.cache
def _example_trial() -> Any:
    """``examples/gpt_fsdp/model_def.py:GPTTrial``, loaded by path (every
    example calls its module ``model_def``)."""
    spec = importlib.util.spec_from_file_location("bench_gpt_fsdp_model_def",
                                                  EXAMPLE)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.GPTTrial


def hyperparameters(config: Dict[str, Any], seq_len: int,
                    mesh: Dict[str, int]) -> Dict[str, Any]:
    d, t = dims(config), config["training"]
    return {
        "global_batch_size": int(t["global_batch_size"]),
        "vocab_size": d["vocab"], "n_layers": d["layers"],
        "d_model": d["d_model"], "n_heads": d["heads"], "d_ff": d["d_ff"],
        "seq_len": int(seq_len), "remat": bool(t["remat"]),
        "attention_impl": "auto", "mesh": dict(mesh),
    }


def trial_class(config: Dict[str, Any], seed: int,
                batches: Iterable[Any]) -> Any:
    """The example's trial with the benchmark's weights, data and optimizer
    constants in place of its own: everything the Trainer compiles and runs
    (``loss``, ``sharding_rules``, the mesh) stays the example's."""
    import optax

    opt = config["training"]["optimizer"]

    class BenchTrial(_example_trial()):
        def initial_params(self, rng):
            mesh = self.context.mesh
            sh = weight_shardings(config, mesh) if mesh.size > 1 else None
            return make_weights(config, seed, sh)

        def optimizer(self):
            return optax.chain(
                optax.clip_by_global_norm(float(opt["clip_global_norm"])),
                optax.adamw(float(opt["lr"]), b1=float(opt["b1"]),
                            b2=float(opt["b2"]), eps=float(opt["eps"]),
                            weight_decay=float(opt["weight_decay"])))

        def training_data(self):
            return batches

        def validation_data(self):
            return None

    return BenchTrial


def _trainer_state() -> Any:
    """The live ``TrainState`` of the ``Trainer._fit_inner`` that is calling
    us (through a report). The trainer hands its state to no hook, so the
    benchmark reads the local of that frame; PERF.md lists the hook a later
    PR should add in its place."""
    import sys

    from determined_clone_tpu.training.train_step import TrainState

    frame = sys._getframe(1)
    while frame is not None:
        if frame.f_code.co_name == "_fit_inner":
            state = frame.f_locals.get("state")
            if isinstance(state, TrainState):
                return state
        frame = frame.f_back
    raise RuntimeError("no Trainer._fit_inner with a `state` on the stack")


def run_training(config: Dict[str, Any], *, seed: int, chips: int,
                 mesh: Dict[str, int], seq_len: int, scheduling_unit: int,
                 prefetch_depth: int, batches: Iterable[Any], hooks: Any,
                 observe: bool, workdir: str) -> Dict[str, Any]:
    """One ``Trainer.fit`` of the configuration, built as
    ``LocalExperimentRunner._run_to`` builds it (``core.init`` ->
    ``TrialContext`` -> trial -> ``Trainer``), with the harness in the seats
    of the searcher and of the metrics backend:

    - ``hooks.next_target(steps_done)`` gives the next cumulative length to
      train to, or None to end the fit (no preemption: that would save);
    - ``hooks.on_boundary(steps, metrics, get_state)`` is called at every
      training report; ``get_state()`` is the trainer's live state.

    The runner itself is bypassed: it trains to a length fixed ahead, needs
    a validation metric and saves on the way out. No validation, no
    checkpoint (``checkpoint_policy: none``).
    Returns the program's spans (``observe`` only) on ``time.monotonic``.
    """
    import random
    import time

    from determined_clone_tpu import core
    from determined_clone_tpu.config.experiment import ExperimentConfig
    from determined_clone_tpu.core._searcher import (
        SearcherOperation,
        SearcherOperationSource,
    )
    from determined_clone_tpu.training import Trainer, TrialContext

    exp = ExperimentConfig.from_dict({
        "name": "benchmark",
        "entrypoint": "model_def:GPTTrial",
        "hyperparameters": hyperparameters(config, seq_len, mesh),
        "searcher": {"name": "single", "metric": "loss",
                     "smaller_is_better": True,
                     "max_length": {"batches": 10 ** 9}},
        "resources": {"slots_per_trial": chips},
        "scheduling_unit": int(scheduling_unit),
        "optimizations": {"prefetch_depth": int(prefetch_depth)},
        "checkpoint_policy": "none",
        "max_restarts": 0,
        "reproducibility": {"experiment_seed": seed % (2 ** 31 - 1)},
        "observability": {"enabled": bool(observe)},
    })

    class Ops(SearcherOperationSource):
        def operations(self, is_chief: bool):
            done = 0
            while True:
                target = hooks.next_target(done)
                if target is None:
                    return
                yield SearcherOperation(target, is_chief=is_chief)
                done = target

    class Reports(core.MetricsBackend):
        def report(self, group: str, steps_completed: int,
                   metrics: Dict[str, Any]) -> None:
            if group == "training":
                hooks.on_boundary(steps_completed, metrics, _trainer_state)

    hparams = exp.hyperparameters.sample(random.Random(exp.experiment_seed))
    with core.init(config=exp, storage_path=workdir,
                   metrics_backend=Reports(), searcher_source=Ops(),
                   trial_id=1) as cctx:
        sync_t = None
        if cctx.telemetry is not None:
            sync_t = time.monotonic()
            cctx.telemetry.tracer.instant("bench_clock_sync")
        tctx = TrialContext(config=exp, hparams=hparams, core=cctx)
        if tctx.mesh.devices.size != chips:
            raise RuntimeError(f"mesh has {tctx.mesh.devices.size} devices, "
                               f"the cell asks for {chips}")
        trial = trial_class(config, seed, batches)(tctx)
        Trainer(trial).fit()
        spans = (program_spans(cctx.telemetry.tracer, sync_t)
                 if cctx.telemetry is not None else [])
    return {"spans": spans}


def program_spans(tracer: Any, sync_t: float) -> list:
    """The tracer's records as (name, start, seconds, args) on
    ``time.monotonic``, placed by the ``bench_clock_sync`` instant that was
    recorded at ``sync_t``."""
    events = tracer.events()
    (mark,) = [e for e in events if e["name"] == "bench_clock_sync"]
    shift = sync_t - mark["ts_us"] / 1e6
    return [(e["name"], e["ts_us"] / 1e6 + shift, e["dur_us"] / 1e6,
             e.get("args") or {})
            for e in events if e.get("ph") != "i"]


def adam_first_moment(opt_state: Any) -> Any:
    """The Adam ``mu`` tree inside an optax chain's state."""
    for node in jax.tree.leaves(opt_state,
                                is_leaf=lambda n: hasattr(n, "mu")):
        if hasattr(node, "mu"):
            return node.mu
    raise RuntimeError("no Adam state in the optimizer's state")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def build_engine(config: Dict[str, Any], params: Any,
                 telemetry: Optional[Any]) -> Any:
    """``InferenceEngine`` with default features (no prefix cache, chunked
    prefill or speculation) at the configuration's serving sizes."""
    from determined_clone_tpu.config.experiment import ServingConfig
    from determined_clone_tpu.serving import InferenceEngine

    s = config["serving"]
    scfg = ServingConfig(
        max_batch=int(s["max_batch"]),
        max_prefill_len=int(s["max_prefill_len"]),
        kv_block_size=int(s["kv_block_size"]),
        kv_blocks=int(s["kv_blocks"]),
        max_queue_depth=int(s["max_queue_depth"]))
    scfg.validate()
    return InferenceEngine.from_serving_config(
        params, model_config(config, remat=False), scfg, telemetry=telemetry)


def new_telemetry() -> Any:
    from determined_clone_tpu.telemetry import Telemetry

    return Telemetry(enabled=True, max_events=2_000_000,
                     process_name="bench")
