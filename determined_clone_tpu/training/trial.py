"""JaxTrial — the high-level trial API (PyTorchTrial re-imagined for XLA).

The reference's PyTorchTrial (harness/determined/pytorch/_pytorch_trial.py:1416)
is a class of eager-mode hooks called per batch. Under jit that inversion
doesn't work — the framework must trace the user's functions instead. A
JaxTrial therefore declares pure functions over pytrees:

  initial_params(rng)            ≈ __init__ wrap_model
  optimizer()                    ≈ wrap_optimizer (an optax transformation —
                                    LR schedules are optax schedules, ≈ wrap_lr_scheduler)
  loss(params, batch, rng)       ≈ train_batch (traced; returns loss, metrics)
  apply_statistics(params, s)    state no gradient trains, moved after each step
  eval_metrics(params, batch[, rng])  ≈ evaluate_batch (traced)
  sharding_rules()               parallelism layout (≈ DeepSpeed config / MPU)
  training_data()/validation_data()  ≈ build_training_data_loader

The TrialContext carries what trial code may read: hparams, the experiment
config, the mesh, and the Core API context.
"""
from __future__ import annotations

import abc
from typing import Any, Dict, Iterable, Optional, Tuple

import jax
import optax

from determined_clone_tpu import core as core_mod
from determined_clone_tpu.config.experiment import ExperimentConfig
from determined_clone_tpu.parallel.mesh import MeshSpec, make_mesh
from determined_clone_tpu.parallel.sharding import ShardingRules, batch_spec


class TrialContext:
    def __init__(self, *, config: ExperimentConfig, hparams: Dict[str, Any],
                 core: core_mod.Context, mesh: Optional[Any] = None) -> None:
        self.config = config
        self.hparams = hparams
        self.core = core
        if mesh is None:
            mesh_hp = hparams.get("mesh")
            spec = MeshSpec.from_dict(mesh_hp) if mesh_hp else MeshSpec()
            n = config.resources.slots_per_trial or 1
            if n > len(jax.devices()):
                # never a quietly smaller mesh: a job asked for n chips
                raise RuntimeError(
                    f"resources.slots_per_trial={n} but only "
                    f"{len(jax.devices())} devices are present")
            devices = jax.devices()[:n]
            mesh = make_mesh(spec.resolve(n), devices)
        self.mesh = mesh

    @property
    def distributed(self) -> core_mod.DistributedContext:
        return self.core.distributed

    def get_hparam(self, name: str, default: Any = None) -> Any:
        node: Any = self.hparams
        for part in name.split("."):
            if not isinstance(node, dict) or part not in node:
                return default
            node = node[part]
        return node


class JaxTrial(abc.ABC):
    """Subclass and implement the pure functions; the Trainer does the rest."""

    def __init__(self, context: TrialContext) -> None:
        self.context = context

    # -- required -----------------------------------------------------------

    @abc.abstractmethod
    def initial_params(self, rng: jax.Array) -> Any:
        ...

    @abc.abstractmethod
    def optimizer(self) -> optax.GradientTransformation:
        ...

    @abc.abstractmethod
    def loss(self, params: Any, batch: Any, rng: jax.Array
             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Traced. Returns (scalar loss, metrics dict of device scalars),
        or with a third value what ``apply_statistics`` takes."""

    @abc.abstractmethod
    def training_data(self) -> Iterable[Any]:
        """Yield host-side batches (numpy pytrees) with GLOBAL batch dim."""

    # -- optional -----------------------------------------------------------

    def eval_metrics(self, params: Any, batch: Any,
                     rng: Optional[jax.Array] = None
                     ) -> Dict[str, jax.Array]:
        """Traced. Per-batch validation metrics (mean-reduced across batches).

        ``rng`` is threaded by the Trainer off the experiment's seeded key
        chain (``make_eval_step`` folds the train step count in, so every
        validation sees fresh randomness — never a constant reused key).
        Direct callers that pass no key get one derived from the
        experiment seed. Overrides with the plain ``(params, batch)``
        signature keep working; declare ``rng`` to receive the key."""
        if rng is None:
            rng = jax.random.PRNGKey(self.context.config.experiment_seed)
        loss, metrics = self.loss(params, batch, rng)[:2]
        return {"loss": loss, **metrics}

    def apply_statistics(self, params: Any, statistics: Any) -> Any:
        """Traced, after the optimizer's update of every step, for a
        ``loss`` that returns ``(loss, metrics, statistics)``: the new
        ``params``, with the state that no gradient trains moved by what
        the forward pass counted (an expert layer's selection bias by its
        experts' loads). ``statistics`` is any pytree of device arrays;
        ``metrics`` stays scalars for the report."""
        raise NotImplementedError(
            "loss() returned statistics: override apply_statistics()")

    def validation_data(self) -> Optional[Iterable[Any]]:
        return None

    def train_step_flops(self) -> Optional[Any]:
        """Analytic FLOPs for ONE optimizer step over one global batch —
        a :class:`telemetry.flops.StepFlops` or a plain float. Model
        trials that know their architecture should override (e.g. via
        ``telemetry.flops.gpt_train_step_flops``); None makes the Trainer
        fall back to the ``6 * n_params * tokens`` approximation."""
        return None

    def tokens_per_sample(self) -> Optional[int]:
        """Tokens per sample (sequence length) for the 6N fallback;
        None → counted as 1 token per sample."""
        return None

    def sharding_rules(self) -> ShardingRules:
        return ShardingRules()

    def batch_spec(self, batch: Any) -> Any:
        """PartitionSpec pytree for one batch; default: leading dim over
        (dp, fsdp) on every leaf."""
        return jax.tree.map(
            lambda x: batch_spec(extra_dims=max(0, x.ndim - 1)), batch
        )

    @property
    def global_batch_size(self) -> int:
        return int(self.context.get_hparam("global_batch_size", 32))
