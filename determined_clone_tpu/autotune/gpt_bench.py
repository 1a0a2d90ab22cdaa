"""Measure callable for autotuning the GPT flagship model.

The dsat "model profile info" trial analogue: builds the mesh + sharded
train step for one candidate config and times a few real steps.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def make_gpt_measure(cfg=None, *, seq_len: int = 64, warmup: int = 1,
                     steps: int = 3):
    """Returns ``measure(mesh_axes, remat, per_device_batch) -> samples/sec``
    over the current jax.devices()."""
    import optax
    from jax.sharding import NamedSharding

    from determined_clone_tpu.models import gpt
    from determined_clone_tpu.parallel import MeshSpec, make_mesh, shard_put
    from determined_clone_tpu.training.train_step import (
        create_train_state,
        make_train_step,
        state_shardings,
    )

    if cfg is None:
        cfg = gpt.GPTConfig(vocab_size=256, n_layers=2, d_model=64,
                            n_heads=4, d_ff=128, max_seq_len=seq_len)

    def measure(mesh_axes: Dict[str, int], remat: bool,
                per_device_batch: int) -> float:
        import dataclasses
        import time

        run_cfg = dataclasses.replace(cfg, remat=remat)
        # dp is always re-derived (MeshSpec dp=-1 absorbs the remainder)
        spec_kwargs = {k: v for k, v in mesh_axes.items()
                       if k != "dp" and v > 1}
        n_devices = 1
        for v in mesh_axes.values():
            n_devices *= v
        mesh = make_mesh(MeshSpec(dp=-1, **spec_kwargs),
                         jax.devices()[:n_devices])

        params = gpt.init(jax.random.PRNGKey(0), run_cfg)
        tx = optax.adamw(1e-3)
        state = create_train_state(params, tx, jax.random.PRNGKey(1))
        sharding = state_shardings(state, mesh, gpt.GPT_SHARDING_RULES)
        state = shard_put(state, sharding)

        global_batch = per_device_batch * n_devices
        tokens = jax.random.randint(
            jax.random.PRNGKey(2), (global_batch, seq_len + 1), 0,
            run_cfg.vocab_size)
        batch_sharding = NamedSharding(mesh, gpt.TOKENS_SPEC)
        tokens = shard_put(tokens, batch_sharding)

        def loss_fn(p, b, rng):
            return gpt.loss_fn(p, run_cfg, b[:, :-1], b[:, 1:],
                               mesh=mesh), {}

        step = make_train_step(loss_fn, tx, mesh=mesh,
                               state_sharding=sharding,
                               batch_sharding=batch_sharding)
        # at least one warmup step: compilation must not land in the timed
        # region (and `metrics` must exist for the sync below)
        for _ in range(max(1, warmup)):
            state, metrics = step(state, tokens)
        jax.block_until_ready(metrics["loss"])
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step(state, tokens)
        jax.block_until_ready(metrics["loss"])
        dt = time.perf_counter() - t0
        loss = float(metrics["loss"])
        if not jnp.isfinite(loss):
            raise RuntimeError(f"non-finite loss {loss} for {mesh_axes}")
        return global_batch * steps / dt

    return measure
