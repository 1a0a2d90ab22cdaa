"""The jitted training step: loss → grads → optax update, fully sharded.

This is the TPU replacement for the reference's per-batch hot loop
(_PyTorchTrialController._train_batch, harness/determined/pytorch/
_pytorch_trial.py:877): instead of eager torch ops + NCCL allreduce, the
whole step is one XLA program over the mesh — gradient reductions,
ZeRO-style reduce-scatters and TP collectives are inserted by the
partitioner from the shardings alone.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from determined_clone_tpu.parallel.sharding import ShardingRules

# (params, batch, rng) -> loss | (loss, metrics) | (loss, metrics, statistics)
LossFn = Callable[..., Any]


@dataclasses.dataclass
class TrainState:
    """Functional train state (params + optimizer state + step + rng)."""

    params: Any
    opt_state: Any
    step: jax.Array
    rng: jax.Array

    def tree_flatten(self):  # pragma: no cover - registered below
        return (self.params, self.opt_state, self.step, self.rng), None


jax.tree_util.register_pytree_node(
    TrainState,
    lambda s: ((s.params, s.opt_state, s.step, s.rng), None),
    lambda _, c: TrainState(*c),
)


def create_train_state(params: Any, tx: optax.GradientTransformation,
                       rng: jax.Array) -> TrainState:
    return TrainState(
        params=params,
        opt_state=tx.init(params),
        step=jnp.zeros((), jnp.int32),
        rng=rng,
    )


def state_shardings(state: TrainState, mesh: Mesh,
                    rules: ShardingRules) -> TrainState:
    """Shardings for a whole TrainState. Optimizer-state leaves mirror their
    parameter's sharding (the ZeRO-1/2 property: Adam moments are sharded
    exactly like the params they track); scalars replicate."""
    param_sh = rules.shardings_for(state.params, mesh)
    param_struct = jax.tree_util.tree_structure(state.params)
    rep = NamedSharding(mesh, P())

    def is_params_like(node: Any) -> bool:
        """A subtree congruent with params (optax moment buffers: Adam mu/nu,
        etc. — they carry the params' own shardings)."""
        try:
            return jax.tree_util.tree_structure(node) == param_struct
        except Exception:
            return False

    def opt_sharding(opt_state):
        return jax.tree.map(
            lambda node: param_sh if is_params_like(node) else rep,
            opt_state,
            is_leaf=is_params_like,
        )
    return TrainState(
        params=param_sh,
        opt_state=opt_sharding(state.opt_state),
        step=rep,
        rng=rep,
    )


def make_train_step(
    loss_fn: LossFn,
    tx: optax.GradientTransformation,
    *,
    mesh: Optional[Mesh] = None,
    state_sharding: Optional[TrainState] = None,
    batch_sharding: Optional[Any] = None,
    donate: bool = True,
    steps_per_dispatch: int = 1,
    apply_statistics: Optional[Callable[[Any, Any], Any]] = None,
) -> Callable[..., Tuple[TrainState, Dict[str, jax.Array]]]:
    """Build the jitted train step.

    ``loss_fn(params, batch, rng)`` returns a scalar loss or
    ``(loss, metrics_dict)``. Gradient reduction across dp/fsdp is implicit:
    the batch is sharded over those axes, so XLA emits the reduce-scatter /
    all-reduce the specs imply.

    A loss that returns a third value, ``(loss, metrics, statistics)``,
    has state that no gradient trains (an expert layer's selection bias,
    moved by the forward pass's own counts): after the optimizer's update
    the step sets ``params = apply_statistics(params, statistics)``
    (``JaxTrial.apply_statistics``). ``statistics`` is any pytree of
    arrays; it never leaves the device.

    With ``steps_per_dispatch=k > 1`` the returned callable takes
    ``(state, batch_0, ..., batch_{k-1})`` and runs all k optimizer steps
    inside ONE jitted program: the batches are stacked device-side and
    ``lax.scan``ned through the step body with the train state as donated
    carry, and per-step metrics are summed on device. One Python dispatch
    (and one donation round-trip) then covers k batches — semantically
    identical to k sequential calls of the k=1 step, including the per-step
    rng split chain, so seeded runs are bit-compatible modulo the metric
    re-association. Pair with ``MetricAccumulator.add(metrics, count=k)``.
    """

    def step_fn(state: TrainState, batch: Any):
        rng, step_rng = jax.random.split(state.rng)

        def wrapped(params):
            out = loss_fn(params, batch, step_rng)
            statistics = None
            if isinstance(out, tuple) and len(out) == 3:
                loss, metrics, statistics = out
            elif isinstance(out, tuple):
                loss, metrics = out
            else:
                loss, metrics = out, {}
            return loss, (metrics, statistics)

        (loss, (metrics, statistics)), grads = jax.value_and_grad(
            wrapped, has_aux=True)(state.params)
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, state.opt_state,
                                           state.params)
            params = optax.apply_updates(state.params, updates)
        if statistics is not None:
            if apply_statistics is None:
                raise ValueError(
                    "the loss returned statistics and no apply_statistics "
                    "was given to take them")
            params = apply_statistics(params, statistics)
        new_state = TrainState(
            params=params, opt_state=opt_state, step=state.step + 1, rng=rng
        )
        with jax.named_scope("optimizer"):
            gnorm = optax.global_norm(grads)
        out_metrics = {"loss": loss.astype(jnp.float32),
                       "grad_norm": gnorm.astype(jnp.float32), **metrics}
        return new_state, out_metrics

    k = int(steps_per_dispatch)
    if k < 1:
        raise ValueError(f"steps_per_dispatch must be >= 1, got {k}")

    if k == 1:
        fn: Callable[..., Any] = step_fn
        n_batch_args = 1
    else:
        def fused_fn(state: TrainState, *batches: Any):
            # stack the k batches device-side: the scan's leading axis
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)

            def body(carry: TrainState, batch: Any):
                return step_fn(carry, batch)

            new_state, per_step = jax.lax.scan(body, state, stacked)
            # sum (not mean) so the accumulator's count-weighted mean stays
            # exact when a chunk mixes fused and single-step dispatches
            summed = jax.tree.map(lambda m: jnp.sum(m, axis=0), per_step)
            return new_state, summed

        fn = fused_fn
        n_batch_args = k

    kwargs: Dict[str, Any] = {}
    if state_sharding is not None:
        in_shardings = (state_sharding,) + (batch_sharding,) * n_batch_args
        out_shardings = (state_sharding, None)
        kwargs = dict(in_shardings=in_shardings, out_shardings=out_shardings)
    if donate:
        kwargs["donate_argnums"] = (0,)
    return jax.jit(fn, **kwargs)


def capture_compile(
    step: Callable[..., Any],
    example_args: Tuple[Any, ...],
    *,
    program: str = "train_step",
    registry: Optional[Any] = None,
    tracer: Optional[Any] = None,
    mesh: Optional[Mesh] = None,
) -> Tuple[Callable[..., Any], Optional[Any]]:
    """Explicit ``lower()``/``compile()`` capture for a built step.

    Replaces the implicit first-call compile with a measured one: compile
    wall time, a sha256 fingerprint of the lowered StableHLO, and the
    compiled program's cost/memory analysis land in the registry/tracer
    (telemetry/xla.py has the mechanics). With ``mesh``, the compiled
    (post-SPMD) HLO is additionally parsed for collectives — op counts
    and byte volumes per mesh axis (telemetry/collectives.py). The
    returned callable runs the AOT executable — the program that was
    measured is the program that executes — and falls back to ``step``'s
    jit cache on a shape mismatch (remainder batches). ``example_args``
    contribute shapes only; nothing runs during lowering. On any failure
    the original ``step`` comes back with a ``None`` record.
    """
    from determined_clone_tpu.telemetry import xla as xla_telemetry

    return xla_telemetry.aot_compile(
        step, example_args, program=program,
        registry=registry, tracer=tracer, mesh=mesh)


def param_count(tree: Any) -> int:
    """Total parameter count of a pytree — the N in the 6*N FLOPs
    approximation (telemetry/flops.py) when a trial provides no analytic
    per-step count."""
    return sum(int(x.size) for x in jax.tree.leaves(tree))


def program_cache_size(fn: Any) -> Optional[int]:
    """Best-effort size of a jitted callable's compilation cache, or None
    when this jax version doesn't expose it. Growth between two reads means
    a (re)trace+compile happened — ``telemetry.Telemetry.wrap_jit`` and
    tests use this to count XLA compiles; traced wrappers propagate
    the probe so the count survives instrumentation."""
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        return None
    try:
        return int(probe())
    except Exception:
        return None


def make_eval_step(
    eval_fn: Callable[..., Dict[str, jax.Array]],
    *,
    state_sharding: Optional[TrainState] = None,
    batch_sharding: Optional[Any] = None,
    rng: Optional[jax.Array] = None,
) -> Callable[[TrainState, Any], Dict[str, jax.Array]]:
    """Jitted evaluation step over params only.

    When ``rng`` is given and ``eval_fn`` declares an ``rng`` parameter,
    each call receives ``fold_in(rng, state.step)`` — derived from the
    experiment's seeded chain and fresh per validation boundary, never the
    constant-key-per-eval antipattern (JAX002). Trials with the plain
    ``(params, batch)`` signature are called unchanged.
    """
    import inspect

    wants_rng = False
    if rng is not None:
        try:
            wants_rng = "rng" in inspect.signature(eval_fn).parameters
        except (TypeError, ValueError):
            wants_rng = False

    def step_fn(state: TrainState, batch: Any):
        if wants_rng:
            return eval_fn(state.params, batch,
                           rng=jax.random.fold_in(rng, state.step))
        return eval_fn(state.params, batch)

    kwargs: Dict[str, Any] = {}
    if state_sharding is not None:
        kwargs = dict(in_shardings=(state_sharding, batch_sharding))
    return jax.jit(step_fn, **kwargs)
