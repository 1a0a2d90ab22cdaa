"""Pallas flash attention: numerics vs the XLA reference, gradients,
shape guards, and GPT integration (interpret mode on CPU — same kernel
code path the TPU compiles).

Every run of a kernel here is under ``jax.jit``: un-jitted, the
interpreter dispatches a grid step's operations one by one. The shapes are
the smallest that cross the branch a case names; the train cells' own
(T = 1024, D = 64, bf16) are compiled for a described v5e in
``tests/test_chip_compile.py`` and run on the chip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from determined_clone_tpu.ops import flash_attention as flash_mod
from determined_clone_tpu.ops.attention import NEG_INF, mha
from determined_clone_tpu.ops.flash_attention import flash_attention


flash = jax.jit(flash_attention,
                static_argnames=("causal", "block_q", "block_k"))


def _qkv(B=2, T=128, H=2, D=32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
def test_matches_mha(causal):
    q, k, v = _qkv()
    ref = mha(q, k, v, causal=causal)
    out = flash(q, k, v, causal=causal, block_q=64, block_k=64)
    assert jnp.max(jnp.abs(ref - out)) < 1e-4


def test_uneven_q_k_blocks():
    # q blocks smaller than k blocks and vice versa
    q, k, v = _qkv(T=128)
    ref = mha(q, k, v, causal=True)
    for bq, bk in [(32, 64), (64, 32), (128, 128)]:
        out = flash(q, k, v, causal=True, block_q=bq, block_k=bk)
        assert jnp.max(jnp.abs(ref - out)) < 1e-4, (bq, bk)


def test_block_clamps_to_seq():
    # seq shorter than the default blocks: clamp instead of error
    q, k, v = _qkv(T=64)
    out = flash(q, k, v)  # default block 128 > 64
    assert jnp.max(jnp.abs(mha(q, k, v) - out)) < 1e-4


def test_indivisible_seq_rejected():
    q, k, v = _qkv(T=96)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, block_q=64, block_k=64)


def test_gradients_match_reference():
    q, k, v = _qkv(T=128)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, block_q=64, block_k=64) ** 2).sum()

    def f_ref(q, k, v):
        return (mha(q, k, v) ** 2).sum()

    gf = jax.jit(jax.grad(f_flash, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(f_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gf, gr):
        assert jnp.max(jnp.abs(a - b)) < 1e-3


# (B, T, H, D, block_q, block_k). Read in place, heads side by side in a
# lane block: two of D = 64 at one block whole and in pieces, four of
# D = 32, one of D = 128; an odd head count, whose last lane block hangs
# over the edge (five of D = 32: four and one; three of D = 64: two and
# one), over several blocks, q blocks smaller and larger than k blocks.
# Transposed to a head a row: D = 48
PARITY_SHAPES = [
    (1, 128, 2, 64, None, None),
    (1, 512, 2, 64, None, None),
    (1, 256, 4, 32, 128, 128),
    (1, 256, 1, 128, 128, 128),
    (1, 256, 5, 32, 128, 128),
    (2, 256, 3, 64, 64, 128),
    (1, 256, 2, 32, 128, 64),
    (1, 128, 2, 48, None, None),
]
# worst element against fp32 mha on the same (rounded) inputs
PARITY_TOL = {jnp.float32: 2e-4, jnp.bfloat16: 6e-2}


def _parity_inputs(shape, dtype):
    B, T, H, D, bq, bk = shape
    q, k, v = (x.astype(dtype) for x in _qkv(B=B, T=T, H=H, D=D, seed=3))
    as_f32 = tuple(x.astype(jnp.float32) for x in (q, k, v))
    return (q, k, v), as_f32, dict(block_q=bq, block_k=bk)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [True, False],
                         ids=["causal", "full"])
@pytest.mark.parametrize("shape", PARITY_SHAPES,
                         ids=lambda s: "x".join(str(n) for n in s))
def test_forward_and_lse_match_mha(shape, causal, dtype):
    (q, k, v), as_f32, blocks = _parity_inputs(shape, dtype)
    B, T, H, D = q.shape
    block = flash_mod.Blocks(
        blocks["block_q"] or T, blocks["block_k"] or T,
        flash_mod.block_sizes(T, T, D, dtype).fwd.tile)
    lay = flash_mod.layout(H, D)

    @jax.jit   # the kernel, its reference and the residual's: one program
    def run(q, k, v, qf, kf, vf):
        out = flash_attention(q, k, v, causal=causal, **blocks)
        # the residual the backward recomputes probabilities from
        o, lse = flash_mod._fwd_call(
            *(flash_mod.to_kernel_layout(x, lay) for x in (q, k, v)),
            lay=lay, head_dim=D, causal=causal, block=block, interpret=True,
            with_lse=True, cost=flash_mod.flash_cost(
                B, H, T, T, D, causal, dtype)["flash_fwd"])
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) / np.sqrt(D)
        if causal:
            scores = jnp.where(jnp.tril(jnp.ones((T, T), bool)), scores,
                               NEG_INF)
        return (out, mha(qf, kf, vf, causal=causal),
                flash_mod.from_kernel_layout(o, lay, B, D), lse,
                jax.nn.logsumexp(scores, axis=-1))          # [B, H, T]

    out, ref, o, lse, want = run(q, k, v, *as_f32)
    assert out.dtype == dtype
    assert jnp.max(jnp.abs(ref - out.astype(jnp.float32))) \
        < PARITY_TOL[dtype]
    assert lse.shape == ((B * lay.groups, lay.heads, T) if lay.in_place
                         else (B * H, 1, T))
    assert lse.dtype == jnp.float32
    # a lane block short of heads carries rows for heads that are not there
    assert jnp.max(jnp.abs(lse.reshape(B, -1, T)[:, :H] - want)) < 1e-3
    assert jnp.array_equal(o, out)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [True, False],
                         ids=["causal", "full"])
@pytest.mark.parametrize("shape", PARITY_SHAPES,
                         ids=lambda s: "x".join(str(n) for n in s))
def test_gradients_match_mha(shape, causal, dtype):
    (q, k, v), as_f32, blocks = _parity_inputs(shape, dtype)
    w = jnp.asarray(np.random.RandomState(4).randn(*q.shape), jnp.float32)

    def f_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, **blocks)
        return (out.astype(jnp.float32) * w).sum()

    def f_ref(q, k, v):
        return (mha(q, k, v, causal=causal) * w).sum()

    got, want = jax.jit(lambda x, xf: (   # one program for both
        jax.grad(f_flash, argnums=(0, 1, 2))(*x),
        jax.grad(f_ref, argnums=(0, 1, 2))(*xf)))((q, k, v), as_f32)
    for name, a, b in zip("qkv", got, want):
        assert a.dtype == dtype
        gap = jnp.max(jnp.abs(a.astype(jnp.float32) - b))
        assert gap < 5 * PARITY_TOL[dtype] * jnp.max(jnp.abs(b)), name


def test_block_rule_at_the_cells_shapes_and_at_a_clamp():
    """What ``block_sizes`` returns at the two train cells' per-chip
    shapes (T = 1024, D = 64, bf16: a few hundred rows a side, the v5e
    sweep of PERF.md section 6), and where a sequence or VMEM clamps it."""
    cells = flash_mod.block_sizes(1024, 1024, 64, jnp.bfloat16)
    for block in cells:
        tq, tk = block.tiles
        assert 128 <= tq <= 1024 and 128 <= tk <= 1024
        assert block.q % tq == 0 and block.k % tk == 0
        assert 1024 % block.q == 0 and 1024 % block.k == 0
        assert (1024 // block.q) * (1024 // block.k) <= 16  # steps a head
    # a sequence shorter than a block is one block, one piece
    for block in flash_mod.block_sizes(64, 64, 32, jnp.float32):
        assert (block.q, block.k) == (64, 64) == block.tiles
    # a length that 1024 and 512 do not divide falls back to a multiple of
    # 128 that does; one that is no multiple of 128 has to be padded first
    for block in flash_mod.block_sizes(1152, 1152, 64, jnp.bfloat16):
        assert 1152 % block.q == 0 and block.q % 128 == 0
    with pytest.raises(ValueError, match="seq_multiple"):
        flash_mod.block_sizes(1100, 1100, 64, jnp.bfloat16)
    assert flash_mod.seq_multiple(1023) == 128 and -1023 % 128 == 1
    assert flash_mod.seq_multiple(33) == 16
    # a wide fp32 head shrinks the blocks under the VMEM budget
    wide = flash_mod.block_sizes(8192, 8192, 1024, jnp.float32)
    for narrow, cell in zip(wide, cells):
        assert narrow.q * narrow.k < cell.q * cell.k
    # heads reach the kernels side by side in lane blocks (medium: 16 of
    # D = 64, two a block; xl: 25, twelve pairs and one alone), a head a
    # row only where D neither divides 128 nor is a multiple of it
    assert flash_mod.layout(16, 64) == (True, 2, 8)
    assert flash_mod.layout(25, 64) == (True, 2, 13)
    assert flash_mod.layout(8, 128) == (True, 1, 8)
    assert flash_mod.layout(8, 96) == (False, 1, 1)
    # pieces are square and only of a square block
    assert flash_mod.Blocks(512, 512, 128).tiles == (128, 128)
    assert flash_mod.Blocks(256, 512, 128).tiles == (256, 512)
    assert flash_mod.Blocks(64, 64, 256).tiles == (64, 64)


def _primitives(jaxpr, found):
    """Names of every primitive of a jaxpr and of the jaxprs its equations
    hold, without looking inside a kernel."""
    for eqn in jaxpr.eqns:
        found.append(eqn.primitive.name)
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _primitives(sub, found)
    return found


def test_grad_is_three_kernels_and_no_scan():
    """The backward is Pallas too: a jitted grad holds the forward kernel
    and the two backward kernels and no ``scan`` / ``while`` (what
    differentiating ``causal_blockwise_attention`` used to leave)."""
    q, k, v = _qkv(T=256)
    grad = jax.grad(lambda q, k, v: flash_attention(
        q, k, v, block_q=128, block_k=128).sum(), argnums=(0, 1, 2))
    names = _primitives(jax.make_jaxpr(grad)(q, k, v).jaxpr, [])
    assert "scan" not in names and "while" not in names, names
    assert names.count("pallas_call") == 3
    assert not hasattr(flash_mod, "causal_blockwise_attention")


def test_cost_counts_the_attended_pairs():
    """``flash_cost``: causal attention over T keys is T (T + 1) / 2 pairs
    a head (the benchmark's count, ``4 D (T + 1) / 2`` a token forward),
    the backward kernels are 4 and 3 products, bytes are the operands
    once."""
    cost = flash_mod.flash_cost(8, 16, 1024, 1024, 64, True, jnp.bfloat16)
    pairs = 8 * 16 * 1024 * 1025 // 2
    assert cost["flash_fwd"].flops == 4 * 64 * pairs
    assert cost["flash_bwd_dkv"].flops == 8 * 64 * pairs
    assert cost["flash_bwd_dq"].flops == 6 * 64 * pairs
    assert cost["flash_fwd"].transcendentals == pairs
    tensor = 8 * 16 * 1024 * 64 * 2
    assert cost["flash_fwd"].bytes_accessed == 4 * tensor + 8 * 16 * 1024 * 4
    full = flash_mod.flash_cost(8, 16, 1024, 1024, 64, False, jnp.bfloat16)
    assert full["flash_fwd"].flops == 4 * 64 * 8 * 16 * 1024 * 1024


def test_bf16_inputs():
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(T=128))
    out = flash(q, k, v, block_q=64, block_k=64)
    assert out.dtype == jnp.bfloat16
    ref = mha(q, k, v)
    assert jnp.max(jnp.abs(ref.astype(jnp.float32) -
                           out.astype(jnp.float32))) < 0.05


def test_gpt_with_flash_attention_trains():
    import optax

    from determined_clone_tpu.models import gpt
    from determined_clone_tpu.training.train_step import (
        create_train_state,
        make_train_step,
    )

    cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, d_model=64, n_heads=4,
                        d_ff=128, max_seq_len=64, remat=False,
                        attention_impl="flash", attention_block_size=32)
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 33), 0, 128)

    # flash output agrees with mha inside the full model (BEFORE training:
    # the train step donates the state, freeing these param buffers)
    # explicit mha: on TPU the config default ("auto") resolves to flash,
    # which would make this parity check compare the kernel to itself
    cfg_ref = gpt.GPTConfig(vocab_size=128, n_layers=2, d_model=64, n_heads=4,
                            d_ff=128, max_seq_len=64, remat=False,
                            attention_impl="mha")
    apply = jax.jit(gpt.apply, static_argnums=1)
    logits_ref = apply(params, cfg_ref, tokens[:, :-1])
    logits_flash = apply(params, cfg, tokens[:, :-1])
    assert jnp.max(jnp.abs(logits_ref - logits_flash)) < 0.05

    tx = optax.sgd(0.1)
    state = create_train_state(params, tx, jax.random.PRNGKey(1))

    def loss_fn(p, b, rng):
        return gpt.loss_fn(p, cfg, b[:, :-1], b[:, 1:]), {}

    step = make_train_step(loss_fn, tx)
    state, m1 = step(state, tokens)
    state, m2 = step(state, tokens)
    assert jnp.isfinite(m2["loss"])
    assert float(m2["loss"]) < float(m1["loss"])


def test_auto_attention_resolves_per_backend():
    """TPU-first default: "auto" must pick the fused kernel on TPU and
    plain XLA attention elsewhere, and unknown impls fail loudly."""
    import dataclasses

    from determined_clone_tpu.models import gpt

    cfg = gpt.GPTConfig.tiny()
    assert cfg.attention_impl == "auto"  # the out-of-the-box default
    # literal per-backend expectations (NOT the implementation's own
    # predicate, which would make this assertion tautological)
    if jax.default_backend() == "tpu":
        assert gpt.resolved_attention_impl(cfg) == "flash"
    else:
        assert gpt.resolved_attention_impl(cfg) == "mha"
    assert gpt.resolved_attention_impl(
        dataclasses.replace(cfg, attention_impl="flash")) == "flash"
    with pytest.raises(ValueError, match="bogus"):
        gpt.resolved_attention_impl(
            dataclasses.replace(cfg, attention_impl="bogus"))


def test_flash_mha_loss_parity_over_training():
    """Kernel regression gate (VERDICT r3 #2): same-seed training with the
    Pallas kernel must track the XLA-attention loss curve step for step.
    A numerics bug that still 'trains' would slip a smoke test; a
    per-step curve comparison catches it."""
    import dataclasses

    import optax

    from determined_clone_tpu.models import gpt
    from determined_clone_tpu.training.train_step import (
        create_train_state,
        make_train_step,
    )

    cfg_flash = gpt.GPTConfig(
        vocab_size=128, n_layers=2, d_model=64, n_heads=4, d_ff=128,
        max_seq_len=64, remat=False, attention_impl="flash",
        attention_block_size=32)
    cfg_mha = dataclasses.replace(cfg_flash, attention_impl="mha")
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 65), 0, 128)

    curves = {}
    for name, cfg in [("flash", cfg_flash), ("mha", cfg_mha)]:
        params = gpt.init(jax.random.PRNGKey(0), cfg)
        tx = optax.adam(3e-3)
        state = create_train_state(params, tx, jax.random.PRNGKey(1))

        def loss_fn(p, b, rng, cfg=cfg):
            return gpt.loss_fn(p, cfg, b[:, :-1], b[:, 1:]), {}

        step = make_train_step(loss_fn, tx)
        losses = []
        for _ in range(6):
            state, m = step(state, tokens)
            losses.append(float(m["loss"]))
        curves[name] = losses

    for lf, lm in zip(curves["flash"], curves["mha"]):
        assert abs(lf - lm) / max(abs(lm), 1e-6) < 0.02, (curves)
    # and both actually trained
    assert curves["flash"][-1] < curves["flash"][0]


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_every_kernel_works_a_square_block_through_in_pieces(causal):
    """Blocks of 256 x 256 in pieces of 128 in all three kernels (the block
    rule gives the backward kernels pieces from T = 256 and 1024 on, the
    forward none: ``_CAPS``): four pieces a block, under the causal mask
    the one above the diagonal skipped and the two on it masked. Output and
    the three gradients against ``mha``. T = 256 is the smallest length
    with two pieces a side (a piece is a lane tile)."""
    q, k, v = _qkv(B=1, T=256, H=2, D=64, seed=6)
    w = jnp.asarray(np.random.RandomState(7).randn(*q.shape), jnp.float32)
    pieces = flash_mod.Blocks(256, 256, 128)
    assert pieces.tiles == (128, 128)

    def both(attend):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: (attend(q, k, v) * w).sum(), argnums=(0, 1, 2)))

    got, got_grads = both(lambda q, k, v: flash_mod._flash_attention_cvjp(
        q, k, v, causal, flash_mod.BlockSizes(pieces, pieces, pieces),
        True))(q, k, v)
    want, want_grads = both(lambda q, k, v: mha(q, k, v, causal=causal))(
        q, k, v)
    assert abs(got - want) < 1e-3 * abs(want)
    for name, a, b in zip("qkv", got_grads, want_grads):
        assert jnp.max(jnp.abs(a - b)) < 1e-3 * jnp.max(jnp.abs(b)), name


@pytest.mark.parametrize("seq_len", [50, 255])
def test_flash_pads_indivisible_seq_in_gpt(seq_len):
    """The everyday loss pattern slices tokens[:, :-1], giving T values
    (e.g. 1023) the kernel cannot tile. The model must pad to the multiple
    the kernel asks for and slice transparently, and still match mha
    numerics, gradients included. Two lengths, one on each side of
    ``seq_multiple``'s branch: 50 is under a lane tile and is padded to a
    sublane multiple (64, one block); 255 is past one and is padded to
    whole lane tiles (256, which ``flash_bwd_dkv`` works through in four
    pieces). 1023 -> 1024 crosses the same branch at sixteen times the
    pieces to trace; the train cells' T = 1024 is compiled for the chip in
    ``tests/test_chip_compile.py``."""
    import dataclasses

    from determined_clone_tpu.models import gpt

    cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, d_model=64, n_heads=4,
                        d_ff=128, max_seq_len=1024, remat=False,
                        attention_impl="flash")
    cfg_mha = dataclasses.replace(cfg, attention_impl="mha")
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, seq_len + 1), 0,
                                128)
    params = jax.jit(gpt.init, static_argnums=1)(jax.random.PRNGKey(0), cfg)
    assert flash_mod.seq_multiple(seq_len) == (16 if seq_len == 50 else 128)
    apply = jax.jit(gpt.apply, static_argnums=1)
    logits_flash = apply(params, cfg, tokens[:, :-1])
    logits_mha = apply(params, cfg_mha, tokens[:, :-1])
    assert logits_flash.shape == logits_mha.shape
    assert jnp.max(jnp.abs(logits_flash - logits_mha)) < 0.05

    def loss(p, c):
        return gpt.loss_fn(p, c, tokens[:, :-1], tokens[:, 1:])

    grad = jax.jit(jax.grad(loss), static_argnums=1)
    g_flash = grad(params, cfg)
    g_mha = grad(params, cfg_mha)
    for a, b in zip(jax.tree.leaves(g_flash), jax.tree.leaves(g_mha)):
        assert jnp.linalg.norm(a - b) <= 0.05 * jnp.linalg.norm(b) + 1e-6


def _kernel_names(jaxpr):
    """The Pallas kernels of a jaxpr and of the jaxprs its equations hold,
    by name, in order."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["name"])
        else:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found.extend(_kernel_names(sub))
    return found


@pytest.mark.parametrize("other", ["no_remat", "dots_alone"])
def test_remat_keeps_the_kernels_output_and_log_sum_exp(monkeypatch, other):
    """GPT's rematerialised block keeps ``flash_fwd``'s two outputs
    (``save_flash_residuals``), so the gradient program of a two-layer
    scanned loss holds the forward kernel once, as without remat, where the
    policy over dots alone holds it twice; and since what is kept is what
    the second forward would have made, the gradients are the same to the
    last bit."""
    import dataclasses

    from determined_clone_tpu.models import gpt

    cfg = gpt.GPTConfig(vocab_size=128, n_layers=2, d_model=64, n_heads=4,
                        d_ff=128, max_seq_len=64, remat=True,
                        attention_impl="flash")
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 65), 0, 128)

    def grad(c):
        traced = jax.jit(jax.grad(lambda p: gpt.loss_fn(
            p, c, tokens[:, :-1], tokens[:, 1:]))).trace(params)
        return (traced.lower().compile()(params),
                _kernel_names(traced.jaxpr.jaxpr))

    kept, kernels = grad(cfg)
    assert sorted(kernels) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    if other == "no_remat":
        theirs, kernels = grad(dataclasses.replace(cfg, remat=False))
        assert sorted(kernels) == ["flash_bwd_dkv", "flash_bwd_dq",
                                   "flash_fwd"]
    else:
        monkeypatch.setattr(flash_mod, "save_flash_residuals",
                            jax.checkpoint_policies.nothing_saveable)
        theirs, kernels = grad(cfg)
        assert kernels.count("flash_fwd") == 2, kernels
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(theirs)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_a_fully_rematerialised_layer_keeps_them_too(monkeypatch):
    """``glm_moe_lite``'s layer keeps nothing else: its gradient program
    holds the forward kernel once a run of layers (dense, sparse, the
    prediction module) where a layer that keeps nothing holds it twice, and
    the gradients are the same to the last bit."""
    from determined_clone_tpu.models import glm_moe_lite as glm

    cfg = glm.GLMMoeLiteConfig.tiny()
    assert cfg.remat
    params = jax.jit(glm.init, static_argnums=1)(jax.random.PRNGKey(0), cfg)
    batch = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                               cfg.vocab_size)

    def grad():
        traced = jax.jit(jax.grad(lambda p: glm.loss_fn(
            p, cfg, batch[:, :-1], batch[:, 1:])[0])).trace(params)
        return (traced.lower().compile()(params),
                _kernel_names(traced.jaxpr.jaxpr))

    kept, kernels = grad()
    assert [kernels.count(k) for k in (
        "flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")] == [3, 3, 3], kernels
    monkeypatch.setattr(flash_mod, "save_flash_residuals",
                        jax.checkpoint_policies.nothing_saveable)
    nothing, kernels = grad()
    assert kernels.count("flash_fwd") == 6, kernels
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(nothing)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_forward_alone_names_nothing():
    """The names are the differentiated forward's: a forward-only program
    (serving, evaluation) keeps the jaxpr it had."""
    q, k, v = _qkv(T=128)
    names = _primitives(jax.make_jaxpr(flash_attention)(q, k, v).jaxpr, [])
    assert "name" not in names, names
    assert names.count("pallas_call") == 1
    grad = jax.grad(lambda q, k, v: flash_attention(q, k, v).sum(),
                    argnums=(0, 1, 2))
    assert _primitives(jax.make_jaxpr(grad)(q, k, v).jaxpr,
                       []).count("name") == 2
