"""Adapter ``glm4_moe_lite``: how a configuration file of the GLM-4.7-Flash
family becomes the system under test — a trial for ``training/trainer.py``
over ``models/glm_moe_lite.py`` — and where its seeded weights come from.
Training only: the family has no serving path (ROADMAP B-M).

The configuration file keeps its source's key names (``hidden_size``,
``q_lora_rank``, ``kv_lora_rank``, ``moe_intermediate_size``, ...).
``n_routed_experts`` is the experts held here; ``published_n_routed_experts``
(the router's width) and ``first_expert`` sit beside it, and what the source
does not state (``init_std``, ``embedding_std``, ``selection_bias_std``,
``bias_update_rate``, ``mtp_loss_weight``) is listed under ``assumed``.

The program is imported here, at the top: against a program that lacks the
family the cell fails at once, with an ImportError, before any weight is
made.

**Routing replay** (``reference/replayed.py``): for each of the three
checked steps the adapter runs the program's own forward
(``glm_moe_lite.chosen_experts``, no gradient) on that step's batch with the
parameters the step starts from — the seeded weights for the first, the
trainer's live state at the report of the step before for the others — and
leaves every token's chosen experts where the reference finds them.
"""
from __future__ import annotations

import functools
import gc
import importlib.util
import os
from typing import Any, Dict, Iterable

import jax
import jax.numpy as jnp

from benchmarks.adapters.gpt import (  # noqa: F401 - the harness calls them
    _trainer_state,
    adam_first_moment,
    program_spans,
    seed_key,
)
from benchmarks.reference import replayed
from determined_clone_tpu.models import glm_moe_lite

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
EXAMPLE = os.path.join(REPO_ROOT, "examples", "glm_moe_lite", "model_def.py")

REFERENCE = "glm4_moe_lite"  # benchmarks/reference/glm4_moe_lite.py
CHECK_STEPS = 3              # harness/train.py follows that many

# configuration key -> GLMMoeLiteConfig field, the whole numbers
_SIZES = (
    "vocab_size", "hidden_size", "num_hidden_layers", "first_k_dense_replace",
    "num_nextn_predict_layers", "num_attention_heads", "q_lora_rank",
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "intermediate_size", "moe_intermediate_size", "n_routed_experts",
    "published_n_routed_experts", "first_expert", "num_experts_per_tok",
    "max_position_embeddings")


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes both the program and the reference are built with: the
    model's own under their source's names, and the five the train harness
    reads (``vocab``, ``layers``, ``d_model``, ``heads``, ``d_ff``)."""
    d: Dict[str, Any] = {k: int(config[k]) for k in _SIZES}
    a = config["assumed"]
    d.update(
        vocab=d["vocab_size"], layers=d["num_hidden_layers"],
        d_model=d["hidden_size"], heads=d["num_attention_heads"],
        d_ff=d["intermediate_size"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        init_std=float(a["init_std"]),
        embedding_std=float(a["embedding_std"]),
        selection_bias_std=float(a["selection_bias_std"]),
        bias_update_rate=float(a["bias_update_rate"]),
        mtp_loss_weight=float(a["mtp_loss_weight"]))
    return d


def _model_config(d: Dict[str, Any], remat: bool = True) -> Any:
    fields = {k: d[k] for k in _SIZES + (
        "routed_scaling_factor", "rope_theta", "rms_norm_eps", "init_std",
        "bias_update_rate", "mtp_loss_weight")}
    return glm_moe_lite.GLMMoeLiteConfig(**fields, remat=remat)


def model_config(config: Dict[str, Any]) -> Any:
    return _model_config(dims(config), bool(config["training"]["remat"]))


def _weights(key: jax.Array, d: Dict[str, Any]) -> Dict[str, Any]:
    """The program's own seeded initialisation (``glm_moe_lite.init``) at
    the configuration's constants: float32, every matrix and the head
    normal(0, init_std), the embedding normal(0, embedding_std), norm
    scales 1, the selection biases normal(0, selection_bias_std)."""
    return glm_moe_lite.init(key, _model_config(d),
                             bias_std=d["selection_bias_std"],
                             embedding_std=d["embedding_std"])


def make_weights(config: Dict[str, Any], seed: int,
                 shardings: Any = None) -> Dict[str, Any]:
    """The model's weights from the seed, on the device, in one jitted
    call."""
    make = jax.jit(functools.partial(_weights, d=dims(config)),
                   out_shardings=shardings)
    return make(seed_key(seed))


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

@functools.cache
def _example_trial() -> Any:
    """``examples/glm_moe_lite/model_def.py:GLMMoeLiteTrial``, by path."""
    spec = importlib.util.spec_from_file_location(
        "bench_glm_moe_lite_model_def", EXAMPLE)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.GLMMoeLiteTrial


def hyperparameters(config: Dict[str, Any], seq_len: int,
                    mesh: Dict[str, int]) -> Dict[str, Any]:
    d, t = dims(config), config["training"]
    hp = {k: d[k] for k in _SIZES if k != "max_position_embeddings"}
    hp.update({k: d[k] for k in (
        "routed_scaling_factor", "rope_theta", "rms_norm_eps",
        "bias_update_rate", "mtp_loss_weight")})
    hp.update(global_batch_size=int(t["global_batch_size"]),
              seq_len=int(seq_len), remat=bool(t["remat"]), mesh=dict(mesh))
    return hp


def hand_over(trial: Any, params: Any, batch: Any) -> None:
    """Leave the experts the program takes on ``batch`` with ``params``
    where the reference finds them."""
    chosen = _chooser(trial.cfg, trial.context.mesh)(
        params, tokens=jnp.asarray(batch[:, :-1]),
        targets=jnp.asarray(batch[:, 1:]))
    replayed.ROUTING[replayed.key(batch)] = {
        "first_expert": trial.cfg.first_expert,
        "experts": jax.device_get(chosen)}


@functools.cache
def _chooser(cfg: Any, mesh: Any) -> Any:
    return jax.jit(functools.partial(glm_moe_lite.chosen_experts, cfg=cfg,
                                     mesh=mesh))


def trial_class(config: Dict[str, Any], seed: int, batches: Any) -> Any:
    """The example's trial with the benchmark's weights, data and optimizer
    constants in place of its own: everything the Trainer compiles and runs
    (``loss``, ``apply_statistics``, ``sharding_rules``, the mesh) stays
    the example's."""
    import optax

    opt = config["training"]["optimizer"]

    class BenchTrial(_example_trial()):
        def initial_params(self, rng):
            params = make_weights(config, seed)
            hand_over(self, params, batches.batch(0))
            return params

        def optimizer(self):
            return optax.chain(
                optax.clip_by_global_norm(float(opt["clip_global_norm"])),
                optax.adamw(float(opt["lr"]), b1=float(opt["b1"]),
                            b2=float(opt["b2"]), eps=float(opt["eps"]),
                            weight_decay=float(opt["weight_decay"]),
                            mask=glm_moe_lite.trained_mask))

        def training_data(self):
            return batches

        def validation_data(self):
            return None

    return BenchTrial


def run_training(config: Dict[str, Any], *, seed: int, chips: int,
                 mesh: Dict[str, int], seq_len: int, scheduling_unit: int,
                 prefetch_depth: int, batches: Iterable[Any], hooks: Any,
                 observe: bool, workdir: str) -> Dict[str, Any]:
    """One ``Trainer.fit`` of the configuration, driven exactly as
    ``adapters/gpt.py:run_training`` drives GPT's (``core.init`` ->
    ``TrialContext`` -> trial -> ``Trainer``, the harness in the seats of
    the searcher and of the metrics backend); before the report of a
    checked step reaches the harness, the next step's routing is handed
    over."""
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
        "entrypoint": "model_def:GLMMoeLiteTrial",
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
    replayed.ROUTING.clear()
    trial = None

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
            if group != "training":
                return
            if steps_completed < CHECK_STEPS:
                hand_over(trial, _trainer_state().params,
                          batches.batch(steps_completed))
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
    # the reference needs the 8.5 GB the trainer's last state held
    trial = None
    gc.collect()
    return {"spans": spans}
