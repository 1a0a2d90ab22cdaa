"""Pipeline parallelism (pp) and MoE/expert parallelism (ep) tests.

Runs on the 8-device virtual CPU mesh from conftest.py — the same trick as
the reference's artificial slots (agent/internal/detect/detect.go:39-56).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from determined_clone_tpu.models import gpt
from determined_clone_tpu.ops.moe import expert_capacity, moe_ffn, moe_init
from determined_clone_tpu.parallel import (
    MeshSpec,
    make_mesh,
    pipeline_apply,
    pipeline_bubble_fraction,
    shard_put,
)
from jax.sharding import NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# pipeline_apply
# ---------------------------------------------------------------------------

def _affine_stage_fn(local_params, x):
    """Scan this stage's layers: x -> tanh(x @ w + b)."""
    def body(h, lp):
        return jnp.tanh(h @ lp["w"] + lp["b"]), None
    out, _ = jax.lax.scan(body, x, local_params)
    return out


def _sequential_reference(stacked, x):
    return _affine_stage_fn(stacked, x)


@pytest.mark.parametrize("pp", [2, 4])
def test_pipeline_matches_sequential(pp):
    mesh = make_mesh(MeshSpec(dp=-1, pp=pp))
    L, B, D, M = 8, 8, 16, 4
    key = jax.random.PRNGKey(0)
    kw, kb, kx = jax.random.split(key, 3)
    stacked = {
        "w": jax.random.normal(kw, (L, D, D)) * 0.3,
        "b": jax.random.normal(kb, (L, D)) * 0.1,
    }
    x = jax.random.normal(kx, (B, D))

    expected = _sequential_reference(stacked, x)

    def run(params, x):
        return pipeline_apply(_affine_stage_fn, params, x, mesh=mesh,
                              num_microbatches=M)

    got = jax.jit(run)(stacked, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_gradients_match_sequential():
    mesh = make_mesh(MeshSpec(dp=-1, pp=2))
    L, B, D, M = 4, 4, 8, 2
    key = jax.random.PRNGKey(1)
    kw, kx = jax.random.split(key)
    stacked = {"w": jax.random.normal(kw, (L, D, D)) * 0.3,
               "b": jnp.zeros((L, D))}
    x = jax.random.normal(kx, (B, D))

    def loss_pp(params):
        y = pipeline_apply(_affine_stage_fn, params, x, mesh=mesh,
                           num_microbatches=M)
        return jnp.sum(y ** 2)

    def loss_seq(params):
        return jnp.sum(_sequential_reference(params, x) ** 2)

    g_pp = jax.jit(jax.grad(loss_pp))(stacked)
    g_seq = jax.grad(loss_seq)(stacked)
    for a, b in zip(jax.tree.leaves(g_pp), jax.tree.leaves(g_seq)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_pipeline_pytree_carrier():
    """Aux leaves ride through the pipeline alongside activations."""
    mesh = make_mesh(MeshSpec(dp=-1, pp=2))
    L, B, D = 4, 4, 8
    stacked = {"w": jnp.stack([jnp.eye(D) * (i + 1) for i in range(L)])}

    def stage(local, carrier):
        def body(c, lp):
            h, acc = c
            h = h @ lp["w"]
            return (h, acc + jnp.sum(h, axis=-1)), None
        (h, acc), _ = jax.lax.scan(body, (carrier["x"], carrier["acc"]), local)
        return {"x": h, "acc": acc}

    x = jnp.ones((B, D))
    carrier = {"x": x, "acc": jnp.zeros((B,))}
    out = jax.jit(lambda p, c: pipeline_apply(stage, p, c, mesh=mesh,
                                              num_microbatches=2))(stacked, carrier)
    # h after layer i: prod_{j<=i} (j+1) * ones; acc = sum_i D * i!
    factors = np.cumprod(np.arange(1, L + 1))
    np.testing.assert_allclose(np.asarray(out["x"]),
                               np.full((B, D), factors[-1]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out["acc"]),
                               np.full((B,), D * factors.sum()), rtol=1e-6)


def test_pipeline_pp1_shortcut():
    mesh = make_mesh(MeshSpec(dp=-1, pp=1))
    stacked = {"w": jnp.ones((2, 4, 4)), "b": jnp.zeros((2, 4))}
    x = jnp.ones((4, 4))
    out = pipeline_apply(_affine_stage_fn, stacked, x, mesh=mesh,
                         num_microbatches=2)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_sequential_reference(stacked, x)))


def test_pipeline_rejects_bad_microbatch():
    mesh = make_mesh(MeshSpec(dp=-1, pp=2))
    stacked = {"w": jnp.ones((2, 4, 4)), "b": jnp.zeros((2, 4))}
    with pytest.raises(ValueError, match="not divisible"):
        pipeline_apply(_affine_stage_fn, stacked, jnp.ones((5, 4)), mesh=mesh,
                       num_microbatches=2)


def test_bubble_fraction():
    assert pipeline_bubble_fraction(1, 4) == pytest.approx(3 / 4)
    assert pipeline_bubble_fraction(15, 2) == pytest.approx(1 / 16)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def test_moe_ffn_shapes_and_aux():
    key = jax.random.PRNGKey(0)
    params = moe_init(key, n_experts=4, d_model=16, d_ff=32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
    y, aux = jax.jit(functools.partial(
        moe_ffn, k=2, compute_dtype=jnp.float32))(params, x)
    assert y.shape == x.shape
    assert y.dtype == x.dtype
    assert jnp.isfinite(aux)
    # perfectly balanced routing gives aux == 1; anything routed gives >= 1-ish
    assert float(aux) > 0.5


def test_moe_capacity_drops_overflow():
    """With capacity 1 slot per expert, most tokens fall through (output 0)."""
    key = jax.random.PRNGKey(0)
    E, D = 2, 8
    params = moe_init(key, n_experts=E, d_model=D, d_ff=16)
    # Router biased so all tokens pick expert 0.
    params["router"]["kernel"] = jnp.zeros((D, E)).at[:, 0].set(1.0)
    N = 16
    x = jnp.ones((1, N, D))
    cap = expert_capacity(N, E, 0.1)
    assert cap == 1
    y, _ = jax.jit(functools.partial(
        moe_ffn, k=1, capacity_factor=0.1, compute_dtype=jnp.float32))(
        params, x)
    # exactly `cap` tokens routed to expert 0 produce nonzero output
    nonzero_rows = int(jnp.sum(jnp.any(jnp.abs(y[0]) > 1e-6, axis=-1)))
    assert nonzero_rows == cap


def test_moe_grads_flow():
    key = jax.random.PRNGKey(0)
    params = moe_init(key, n_experts=4, d_model=8, d_ff=16)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 8))

    def loss(p):
        y, aux = moe_ffn(p, x, compute_dtype=jnp.float32)
        return jnp.sum(y ** 2) + 0.01 * aux

    grads = jax.jit(jax.grad(loss))(params)
    gnorm = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert gnorm > 0


def test_moe_gpt_trains_on_ep_mesh():
    """MoE GPT runs a jitted fwd/bwd with expert weights sharded over ep."""
    import optax

    from determined_clone_tpu.training.train_step import (
        create_train_state, make_train_step, state_shardings)

    mesh = make_mesh(MeshSpec(dp=-1, ep=2))
    cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, d_model=32, n_heads=2,
                        d_ff=64, max_seq_len=32, remat=False, moe_experts=4)
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    assert "moe" in params["blocks"] and "mlp_up" not in params["blocks"]

    tx = optax.adam(1e-3)
    state = create_train_state(params, tx, jax.random.PRNGKey(1))
    sharding = state_shardings(state, mesh, gpt.GPT_SHARDING_RULES)
    state = shard_put(state, sharding)
    # expert dim actually sharded over ep
    up_sh = sharding.params["blocks"]["moe"]["up"]["kernel"]
    assert "ep" in str(up_sh.spec)

    batch_sharding = NamedSharding(mesh, gpt.TOKENS_SPEC)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (8, 17), 0, 128)
    tokens = shard_put(tokens, batch_sharding)

    def loss_fn(p, b, rng):
        return gpt.loss_fn(p, cfg, b[:, :-1], b[:, 1:]), {}

    step = make_train_step(loss_fn, tx, mesh=mesh, state_sharding=sharding,
                           batch_sharding=batch_sharding)
    state, m = step(state, tokens)
    assert jnp.isfinite(m["loss"])
    assert int(state.step) == 1


def test_pipelined_gpt_matches_scan_gpt():
    """The pipelined GPT forward equals the lax.scan forward, params shared."""
    mesh = make_mesh(MeshSpec(dp=-1, pp=2))
    cfg = gpt.GPTConfig(vocab_size=64, n_layers=4, d_model=32, n_heads=2,
                        d_ff=64, max_seq_len=16, remat=False,
                        pipeline_microbatches=2,
                        compute_dtype=jnp.float32)
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)

    ref = jax.jit(lambda p, t: gpt.apply(p, cfg, t))(params, tokens)
    pp = jax.jit(lambda p, t: gpt.apply(p, cfg, t, mesh=mesh))(params, tokens)
    np.testing.assert_allclose(np.asarray(pp), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_pipelined_gpt_train_step_full_mesh():
    """Full train step on a dp×pp×ep mesh: every 'missing in reference' axis
    (SURVEY.md §2.7) live at once."""
    import optax

    from determined_clone_tpu.training.train_step import (
        create_train_state, make_train_step, state_shardings)

    mesh = make_mesh(MeshSpec(dp=-1, pp=2, ep=2))
    cfg = gpt.GPTConfig(vocab_size=64, n_layers=4, d_model=32, n_heads=2,
                        d_ff=64, max_seq_len=16, remat=True, moe_experts=2,
                        pipeline_microbatches=2)
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    tx = optax.adamw(1e-3)
    state = create_train_state(params, tx, jax.random.PRNGKey(1))
    sharding = state_shardings(state, mesh, gpt.GPT_PP_SHARDING_RULES)
    state = shard_put(state, sharding)

    batch_sharding = NamedSharding(mesh, gpt.TOKENS_SPEC)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (8, 17), 0, 64)
    tokens = shard_put(tokens, batch_sharding)

    def loss_fn(p, b, rng):
        return gpt.loss_fn(p, cfg, b[:, :-1], b[:, 1:], mesh=mesh), {}

    step = make_train_step(loss_fn, tx, mesh=mesh, state_sharding=sharding,
                           batch_sharding=batch_sharding)
    state, m = step(state, tokens)
    state, m = step(state, tokens)
    assert jnp.isfinite(m["loss"])
    assert int(state.step) == 2
