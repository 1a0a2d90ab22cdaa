"""The plain reference against the program at tiny widths on the CPU: same
weights (the benchmark's), float32 on both sides, so they agree closely;
and the reference's own AdamW against optax's."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks.harness import spec
from benchmarks.tests.conftest import DATA

ROOTS = (DATA, spec.BENCH_DIR)


@pytest.fixture(scope="module")
def setup():
    with open(os.path.join(DATA, "configs", "tiny.json")) as f:
        config = json.load(f)
    adapter = spec.load_module("adapters", "gpt", ROOTS)
    ref = spec.load_module("reference", adapter.REFERENCE, ROOTS)
    params = adapter.make_weights(config, seed=2 ** 31 + 3)
    return config, adapter, ref, params


def test_forward_matches_gpt_apply_in_float32(setup):
    import dataclasses

    from determined_clone_tpu.models import gpt

    config, adapter, ref, params = setup
    d = adapter.dims(config)
    cfg = dataclasses.replace(adapter.model_config(config, remat=False),
                              compute_dtype=jnp.float32, attention_impl="mha")
    tokens = jax.random.randint(jax.random.PRNGKey(1), (3, 40), 0, d["vocab"])
    with jax.default_matmul_precision("highest"):
        theirs = gpt.apply(params, cfg, tokens)
    ours = ref.forward(params, tokens, n_heads=d["heads"], precision="f32")
    np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-5)
    # the remat flag changes memory, not numbers
    again = ref.forward(params, tokens, n_heads=d["heads"], remat=True)
    np.testing.assert_allclose(again, ours, rtol=1e-6, atol=1e-6)


def test_loss_and_gradients_match_the_programs_loss(setup):
    import dataclasses

    from determined_clone_tpu.models import gpt

    config, adapter, ref, params = setup
    d = adapter.dims(config)
    cfg = dataclasses.replace(adapter.model_config(config, remat=False),
                              compute_dtype=jnp.float32, attention_impl="mha")
    batch = jax.random.randint(jax.random.PRNGKey(2), (4, 33), 0, d["vocab"])

    def theirs(p):
        with jax.default_matmul_precision("highest"):
            return gpt.loss_fn(p, cfg, batch[:, :-1], batch[:, 1:])

    want_loss, want_grads = jax.value_and_grad(theirs)(params)
    loss, grads = ref.loss_and_grads(params, batch, n_heads=d["heads"],
                                     rows_per_block=2)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(got, want, rtol=5e-3, atol=1e-7)


def test_adamw_and_clip_match_optax(setup):
    config, adapter, ref, params = setup
    opt = dict(config["training"]["optimizer"])
    clip = opt.pop("clip_global_norm")
    tx = optax.chain(optax.clip_by_global_norm(clip), optax.adamw(
        opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"]))
    state = tx.init(params)
    p_opt = p_ref = params
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    for step in (1, 2, 3):
        grads = jax.tree.map(
            lambda x: 30.0 * jax.random.normal(jax.random.PRNGKey(step),
                                               x.shape), params)
        updates, state = tx.update(grads, state, p_opt)
        p_opt = optax.apply_updates(p_opt, updates)
        p_ref, mu, nu = ref.adamw_step(
            p_ref, ref.clip_by_global_norm(grads, clip), mu, nu, step, **opt)
    for got, want in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_opt)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


def test_lower_precisions_move_the_logits(setup):
    config, adapter, ref, params = setup
    d = adapter.dims(config)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 32), 0, d["vocab"])
    exact = ref.forward(params, tokens, n_heads=d["heads"], precision="f32")
    err = {p: float(jnp.max(jnp.abs(ref.forward(
        params, tokens, n_heads=d["heads"], precision=p) - exact)))
        for p in ("bf16", "fp8")}
    assert 0 < err["bf16"] < err["fp8"]
    assert err["fp8"] > 4 * err["bf16"]
