"""``models/glm_moe_lite.py`` and the expert layer with a reverse mode
(``ops/moe.py:routed_experts_trained``), on the CPU at tiny widths, against
``benchmarks/reference/glm4_moe_lite.py`` (plain ``jax.numpy``) and against
``jax.grad`` of a dense masked form.

The tiny cell through the benchmark's harness, the configuration against
the catalog and the readers are ``benchmarks/tests/test_glm4_moe_lite.py``
(collected by ``tests/test_benchmark_harness.py``).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks.reference import glm4_moe_lite as ref
from determined_clone_tpu.models import glm_moe_lite as glm
from determined_clone_tpu.ops import moe
from determined_clone_tpu.training.train_step import (
    create_train_state,
    make_train_step,
)

D, F, E, HELD, K = 32, 16, 16, 4, 3


def _layer(key, n_experts=E, held=HELD, d=D, f=F):
    k = jax.random.split(key, 8)
    normal = jax.random.normal
    return {
        "router": {"kernel": 0.3 * normal(k[0], (d, n_experts)),
                   "bias": 0.01 * normal(k[1], (n_experts,))},
        "experts_gate": {"kernel": 0.2 * normal(k[2], (held, d, f))},
        "experts_up": {"kernel": 0.2 * normal(k[3], (held, d, f))},
        "experts_down": {"kernel": 0.2 * normal(k[4], (held, f, d))},
        "shared_gate": {"kernel": 0.2 * normal(k[5], (d, f))},
        "shared_up": {"kernel": 0.2 * normal(k[6], (d, f))},
        "shared_down": {"kernel": 0.2 * normal(k[7], (f, d))},
    }


def _dense_masked(p, h, first_expert, k=K, scale=1.8):
    """The held experts' part with every expert over every token and a
    0 / 1 mask of pairs: what ``jax.grad`` differentiates by itself."""
    experts, gates = moe.route(p["router"], h, k=k, scale=scale)
    y = jnp.zeros_like(h)
    for e in range(p["experts_gate"]["kernel"].shape[0]):
        w = jnp.sum(jnp.where(experts == first_expert + e, gates, 0.0), -1)
        act = jax.nn.silu(h @ p["experts_gate"]["kernel"][e]) \
            * (h @ p["experts_up"]["kernel"][e])
        y = y + w[:, None] * (act @ p["experts_down"]["kernel"][e])
    return y


# how the selection bias shapes the routing, and the tile it is cut into
ROUTINGS = {
    # expert 5 (held: experts 4..7) is chosen by nobody
    "a_held_expert_is_empty": (lambda b: b.at[5].set(-10.0), 40, 8),
    # every token's first choice is expert 6: it gets a pair of every token
    "one_expert_gets_every_token": (lambda b: b.at[6].set(10.0), 40, 8),
    # 37 tokens: every expert's last tile is part full
    "the_pairs_end_mid_tile": (lambda b: b, 37, 8),
    # a tile larger than all the pairs together
    "one_tile_holds_every_pair": (lambda b: b, 24, 256),
}


@pytest.mark.parametrize("routing", list(ROUTINGS))
def test_expert_layers_backward_is_jax_grads_of_the_dense_form(routing):
    """Forward, and the gradient to the rows, the three expert stacks and
    (through the gates) the router, float32 products on both sides: they
    differ by the order of the sums only, 1e-5 of the largest entry. The
    selection bias gets no gradient from either."""
    shape_bias, n, tile = ROUTINGS[routing]
    p = _layer(jax.random.PRNGKey(1))
    p["router"]["bias"] = shape_bias(p["router"]["bias"])
    h = jax.random.normal(jax.random.PRNGKey(2), (n, D))
    ct = jax.random.normal(jax.random.PRNGKey(3), (n, D))

    def program(p, h):
        y, stats = moe.routed_experts_trained(
            p, h, first_expert=4, n_experts=E, k=K, scale=1.8, rows=tile,
            compute_dtype=jnp.float32)
        return jnp.sum(y * ct), (y, stats)

    with jax.default_matmul_precision("highest"):
        (_, (y, stats)), got = jax.jit(jax.value_and_grad(
            program, argnums=(0, 1), has_aux=True))(p, h)
        want_y, want = jax.jit(lambda p, h: (
            _dense_masked(p, h, 4),
            jax.grad(lambda p, h: jnp.sum(_dense_masked(p, h, 4) * ct),
                     argnums=(0, 1))(p, h)))(p, h)
    np.testing.assert_allclose(y, want_y, atol=1e-5 * float(
        jnp.max(jnp.abs(want_y))))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(
            a, b, atol=1e-5 * max(float(jnp.max(jnp.abs(b))), 1.0),
            err_msg=jax.tree_util.keystr(path))
    assert not np.any(np.asarray(got[0]["router"]["bias"]))
    # the statistics are the routing's own
    experts, _ = moe.route(p["router"], h, k=K, scale=1.8)
    load = np.bincount(np.asarray(experts).reshape(-1), minlength=E)
    np.testing.assert_array_equal(stats["load"], load)
    assert int(stats["pairs_held"]) == load[4:8].sum()
    assert int(stats["experts_hit"]) == (load[4:8] > 0).sum()
    if routing == "a_held_expert_is_empty":
        assert load[5] == 0 and int(stats["experts_hit"]) == 3
    if routing == "one_expert_gets_every_token":
        assert load[6] == n


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One expert layer cut as the cell cuts it: 64 experts, 4 a token, 8
    held a chip. The routed parts the eight shares compute (``first_expert``
    0, 8, .., 56), with the shared expert counted once, are the uncut
    reference's layer: forward and the rows' gradient (float32 products;
    sums in another order: 1e-5 of the largest entry)."""
    n, n_experts, held = 48, 64, 8
    p = _layer(jax.random.PRNGKey(4), n_experts=n_experts, held=n_experts)
    h = jax.random.normal(jax.random.PRNGKey(5), (n, D))
    ct = jax.random.normal(jax.random.PRNGKey(6), (n, D))
    shared = lambda h: ref.swiglu(  # noqa: E731
        *(p[f"shared_{m}"]["kernel"] for m in ("gate", "up", "down")), h,
        "f32")

    def shares(h):
        y = shared(h)
        for first in range(0, n_experts, held):
            cut = {**p, **{name: {"kernel": p[name]["kernel"][
                first:first + held]} for name in (
                "experts_gate", "experts_up", "experts_down")}}
            y = y + moe.routed_experts_trained(
                cut, h, first_expert=first, n_experts=n_experts, k=ref.TOP_K,
                scale=ref.ROUTED_SCALE, rows=8,
                compute_dtype=jnp.float32)[0]
        return y

    def uncut(h):
        return ref.expert_layer(p, h, first_expert=0)[0]

    def both(layer):
        return jax.jit(lambda h: (layer(h), jax.value_and_grad(
            lambda h: jnp.sum(layer(h) * ct))(h)))

    with jax.default_matmul_precision("highest"):
        got_y, (got, got_grad) = both(shares)(h)
        want_y, (want, want_grad) = both(uncut)(h)
    np.testing.assert_allclose(got_y, want_y, atol=1e-5 * float(
        jnp.max(jnp.abs(want_y))))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(got_grad, want_grad, atol=1e-5 * float(
        jnp.max(jnp.abs(want_grad))))


def test_served_layer_and_trained_layer_are_one_function_of_the_rows():
    """``routed_experts`` (the two served cells') and the trained layer
    give the same rows for the same routing, bfloat16 products both: the
    served loop multiplies a tile of one expert's pairs, the trained one
    grouped products over a chunk of sorted pairs, so the float32 sums of
    the same bfloat16 terms come in another order (1e-5 of the largest
    entry; an activation rounded to bfloat16 the other way would show as
    4e-3)."""
    p = _layer(jax.random.PRNGKey(7))
    h = jax.random.normal(jax.random.PRNGKey(8), (40, D))
    served, counts, experts = jax.jit(functools.partial(
        moe.routed_experts, first_expert=4, n_held=HELD, n_experts=E, k=K,
        scale=1.8))(
        {k: ({"kernel": v["kernel"].astype(jnp.bfloat16)}
             if k.startswith("experts_") else v) for k, v in p.items()}, h)
    trained, stats = jax.jit(functools.partial(
        moe.routed_experts_trained, first_expert=4, n_experts=E, k=K,
        scale=1.8, rows=16))(p, h)
    np.testing.assert_allclose(served, trained, atol=1e-5 * float(
        jnp.max(jnp.abs(served))))
    np.testing.assert_array_equal(experts, stats["experts"])
    assert [int(stats["pairs_held"]), int(stats["experts_hit"])] \
        == counts.tolist()


@pytest.fixture(scope="module")
def tiny():
    cfg = glm.GLMMoeLiteConfig.tiny()
    params = jax.jit(functools.partial(glm.init, cfg=cfg))(
        jax.random.PRNGKey(0))
    batch = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                               cfg.vocab_size)
    return cfg, params, batch


def _reference_losses(cfg, params, batch, **kw):
    with jax.default_matmul_precision("highest"):
        nxt, mtp, _ = jax.jit(functools.partial(
            ref.summed_losses, n_heads=cfg.num_attention_heads,
            first_expert=cfg.first_expert, **kw))(params, batch)
    rows, width = batch.shape
    return float(nxt) / (rows * (width - 1)), \
        float(mtp) / (rows * (width - 2))


def test_prediction_loss_reads_the_second_next_token(tiny):
    """``loss_mtp`` is the reference's (its module fed ``t_{i+1}``,
    predicting ``t_{i+2}``) within bfloat16 products' reach (1e-3 of a loss
    of about 5.5), and not that of a module fed ``t_i`` (which is further
    off than that). Changing the last token changes ``loss_mtp`` through
    the one position that predicts it, and ``loss_next`` through one."""
    cfg, params, batch = tiny
    # the tiny preset keeps 3 experts a token; the reference's is the
    # published 4
    cfg = glm.GLMMoeLiteConfig(**{
        **{f.name: getattr(cfg, f.name) for f in
           __import__("dataclasses").fields(cfg)},
        "num_experts_per_tok": ref.TOP_K})
    loss_fn = jax.jit(glm.loss_fn, static_argnums=1)
    loss, metrics, stats = loss_fn(params, cfg, batch[:, :-1], batch[:, 1:])
    right = _reference_losses(cfg, params, batch)
    wrong = _reference_losses(cfg, params, batch, mtp_inputs_shift=0)
    assert abs(float(metrics["loss_next"]) - right[0]) < 1e-3 * right[0]
    assert abs(float(metrics["loss_mtp"]) - right[1]) < 1e-3 * right[1]
    assert abs(float(metrics["loss_mtp"]) - wrong[1]) > 3e-3 * right[1]
    np.testing.assert_allclose(
        loss, metrics["loss_next"] + cfg.mtp_loss_weight
        * metrics["loss_mtp"], rtol=1e-6)
    assert set(stats) == {"sparse", "mtp"}
    assert stats["sparse"].shape == (cfg.n_sparse, 16)
    other = batch.at[:, -1].set((batch[:, -1] + 1) % cfg.vocab_size)
    _, moved, _ = loss_fn(params, cfg, other[:, :-1], other[:, 1:])
    assert float(moved["loss_mtp"]) != float(metrics["loss_mtp"])
    # ... while the token before the last two is an input of neither loss's
    # last position alone: both losses move
    assert float(moved["loss_next"]) != float(metrics["loss_next"])


def test_selection_bias_moves_by_the_loads_and_by_nothing_else(tiny):
    """Three steps of ``make_train_step``: after each, every expert's bias
    has moved by ``bias_update_rate`` against its load's error (0 where the
    load is the mean), in the stack and in the prediction module; Adam's
    moments of the bias stay zero, weight decay is masked off it."""
    cfg, params, batch = tiny
    tx = optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1,
                    mask=glm.trained_mask))
    step = make_train_step(
        lambda p, b, r: glm.loss_fn(p, cfg, b[:, :-1], b[:, 1:]), tx,
        apply_statistics=lambda p, s: glm.update_selection_bias(p, cfg, s),
        donate=False)
    state = create_train_state(params, tx, jax.random.PRNGKey(2))
    loss_fn = jax.jit(glm.loss_fn, static_argnums=1)
    for _ in range(3):
        before = state.params
        _, _, loads = loss_fn(before, cfg, batch[:, :-1], batch[:, 1:])
        state, metrics = step(state, batch)
        for name, router_of in (("sparse", lambda p: p["sparse"]["router"]),
                                ("mtp", lambda p: p["mtp"]["layer"]["router"])):
            load = np.asarray(loads[name])
            want = np.asarray(router_of(before)["bias"]) \
                + cfg.bias_update_rate * np.sign(
                    load.mean(-1, keepdims=True) - load)
            np.testing.assert_allclose(router_of(state.params)["bias"], want,
                                       atol=1e-7)
        assert {"loss", "loss_next", "loss_mtp", "moe_pairs_held",
                "moe_experts_hit", "moe_load_max_over_mean",
                "grad_norm"} <= set(metrics)
    adam = [n for n in jax.tree.leaves(
        state.opt_state, is_leaf=lambda n: hasattr(n, "mu"))
        if hasattr(n, "mu")][0]
    for moment in (adam.mu, adam.nu):
        assert not np.any(np.asarray(moment["sparse"]["router"]["bias"]))
        assert not np.any(np.asarray(
            moment["mtp"]["layer"]["router"]["bias"]))
    mask = glm.trained_mask(params)
    assert mask["sparse"]["router"]["bias"] is False
    assert mask["sparse"]["router"]["kernel"] is True
    assert sum(not m for m in jax.tree.leaves(mask)) == 2


def test_a_loss_with_statistics_needs_someone_to_take_them(tiny):
    cfg, params, batch = tiny
    tx = optax.sgd(1e-3)
    step = make_train_step(
        lambda p, b, r: glm.loss_fn(p, cfg, b[:, :-1], b[:, 1:]), tx)
    with pytest.raises(ValueError, match="apply_statistics"):
        step(create_train_state(params, tx, jax.random.PRNGKey(2)), batch)


def test_two_tuple_losses_step_as_before():
    """GPT's step program: a loss that returns ``(loss, metrics)`` traces
    to the same jaxpr whether or not the trial offers
    ``apply_statistics``."""
    tx = optax.sgd(1e-2)

    def loss(p, b, r):
        return jnp.sum((p["w"] * b) ** 2), {"m": jnp.sum(b)}

    state = create_train_state({"w": jnp.ones((4,))}, tx,
                               jax.random.PRNGKey(0))
    batch = jnp.arange(4.0)
    plain = jax.make_jaxpr(make_train_step(loss, tx, donate=False))(
        state, batch)
    offered = jax.make_jaxpr(make_train_step(
        loss, tx, donate=False, apply_statistics=lambda p, s: p))(
        state, batch)
    assert str(plain) == str(offered)


def test_sharding_rules_place_every_leaf_and_the_experts_over_ep():
    from jax.sharding import PartitionSpec as P

    from determined_clone_tpu.parallel import MeshSpec, make_mesh

    cfg = glm.GLMMoeLiteConfig.tiny()
    shapes = jax.eval_shape(lambda: glm.init(jax.random.PRNGKey(0), cfg))
    mesh = make_mesh(MeshSpec(fsdp=1), jax.devices()[:1])
    placed = glm.GLM_MOE_LITE_SHARDING_RULES.shardings_for(shapes, mesh)
    assert jax.tree.structure(placed) == jax.tree.structure(shapes)
    rules = dict(glm.GLM_MOE_LITE_SHARDING_RULES.rules)
    assert rules[r"experts_(gate|up)/kernel$"] == P(None, "ep", "fsdp", "tp")
    assert rules[r"experts_down/kernel$"] == P(None, "ep", "tp", "fsdp")
    assert set(glm.param_shapes(cfg)) == {
        "/".join(str(k.key) for k in path) for path, _
        in jax.tree_util.tree_flatten_with_path(shapes)[0]}


def test_configuration_refuses_what_the_kernels_cannot_take():
    with pytest.raises(ValueError, match="one head size"):
        glm.GLMMoeLiteConfig(v_head_dim=128)
    with pytest.raises(ValueError, match="held experts"):
        glm.GLMMoeLiteConfig(n_routed_experts=8, first_expert=60)
