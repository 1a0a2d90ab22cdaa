"""Model-family tests — tiny deterministic models, the reference's fixture
strategy (harness/tests/experiment/fixtures/pytorch_onevar_model.py etc.)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from determined_clone_tpu.models import bert, gpt, mlp, mnist_cnn, resnet
from determined_clone_tpu.ops import attention
from determined_clone_tpu.parallel import MeshSpec, make_mesh, shard_put
from determined_clone_tpu.parallel.sharding import batch_spec


class TestAttention:
    def test_blockwise_matches_full(self):
        key = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(key, 3)
        B, T, H, D = 2, 64, 4, 16
        q = jax.random.normal(kq, (B, T, H, D))
        k = jax.random.normal(kk, (B, T, H, D))
        v = jax.random.normal(kv, (B, T, H, D))
        full = attention.mha(q, k, v, causal=True)
        blocked = attention.causal_blockwise_attention(q, k, v, block_size=16)
        np.testing.assert_allclose(np.asarray(full), np.asarray(blocked),
                                   atol=1e-5, rtol=1e-5)

    def test_causality(self):
        key = jax.random.PRNGKey(1)
        B, T, H, D = 1, 32, 2, 8
        q, k, v = (jax.random.normal(kk, (B, T, H, D))
                   for kk in jax.random.split(key, 3))
        out1 = attention.mha(q, k, v, causal=True)
        # perturbing the future must not change the past
        k2 = k.at[:, T // 2:].set(0.0)
        v2 = v.at[:, T // 2:].set(0.0)
        out2 = attention.mha(q, k2, v2, causal=True)
        np.testing.assert_allclose(np.asarray(out1[:, : T // 2]),
                                   np.asarray(out2[:, : T // 2]), atol=1e-5)

    def test_rotary_preserves_norm(self):
        x = jax.random.normal(jax.random.PRNGKey(2), (1, 16, 2, 8))
        rot = attention.rotary_embedding(x, jnp.arange(16))
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(x), axis=-1),
            np.linalg.norm(np.asarray(rot), axis=-1),
            rtol=1e-5,
        )


class TestMLP:
    def test_shapes_and_grad(self):
        cfg = mlp.MLPConfig(in_dim=16, hidden_dims=(8,), n_classes=4)
        params = mlp.init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (5, 16))
        y = jnp.array([0, 1, 2, 3, 0])
        logits = mlp.apply(params, cfg, x)
        assert logits.shape == (5, 4)
        g = jax.grad(mlp.loss_fn)(params, cfg, x, y)
        assert jax.tree.structure(g) == jax.tree.structure(params)

    def test_learns_linearly_separable(self):
        cfg = mlp.MLPConfig(in_dim=2, hidden_dims=(16,), n_classes=2)
        params = mlp.init(jax.random.PRNGKey(0), cfg)
        key = jax.random.PRNGKey(42)
        x = jax.random.normal(key, (256, 2))
        y = (x[:, 0] > 0).astype(jnp.int32)

        @jax.jit
        def step(p):
            loss, g = jax.value_and_grad(mlp.loss_fn)(p, cfg, x, y)
            return jax.tree.map(lambda w, gw: w - 0.5 * gw, p, g), loss

        for _ in range(60):
            params, loss = step(params)
        assert float(loss) < 0.1


class TestMnistCNN:
    def test_forward(self):
        cfg = mnist_cnn.MnistCNNConfig(n_filters_1=4, n_filters_2=8)
        params = mnist_cnn.init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (3, 28, 28, 1))
        logits = mnist_cnn.apply(params, cfg, x)
        assert logits.shape == (3, 10)
        flat = mnist_cnn.apply(params, cfg, x.reshape(3, 784))
        np.testing.assert_allclose(np.asarray(logits), np.asarray(flat), atol=1e-6)

    def test_dropout_only_when_training(self):
        cfg = mnist_cnn.MnistCNNConfig(n_filters_1=4, n_filters_2=8)
        params = mnist_cnn.init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 28, 28, 1))
        key = jax.random.PRNGKey(5)
        eval1 = mnist_cnn.apply(params, cfg, x, training=False, dropout_key=key)
        eval2 = mnist_cnn.apply(params, cfg, x, training=False, dropout_key=key)
        np.testing.assert_allclose(np.asarray(eval1), np.asarray(eval2))
        tr1 = mnist_cnn.apply(params, cfg, x, training=True, dropout_key=key)
        tr2 = mnist_cnn.apply(
            params, cfg, x, training=True, dropout_key=jax.random.PRNGKey(6)
        )
        assert not np.allclose(np.asarray(tr1), np.asarray(tr2))


@functools.lru_cache(maxsize=None)
def jitted(fn):
    """``fn(params, cfg, ...)`` under ``jax.jit`` with the configuration
    static: one trace a shape, not one dispatch (and one small program to
    compile) an operation."""
    return jax.jit(fn, static_argnums=1)


@functools.lru_cache(maxsize=None)
def _seeded(model, cfg):
    """``model.init`` of key 0, once a process: no case writes to it."""
    return jitted(model.init)(jax.random.PRNGKey(0), cfg)


class TestResNet:
    def setup_method(self):
        self.cfg = resnet.ResNetConfig.tiny()
        self.params = _seeded(resnet, self.cfg)

    def test_forward_shape_and_dtype(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
        logits = jitted(resnet.apply)(self.params, self.cfg, x)
        assert logits.shape == (2, self.cfg.n_classes)
        assert logits.dtype == jnp.float32

    def test_depth_variants_param_structure(self):
        # one bottleneck param group per block, depths from the variant table
        n_blocks = sum(self.cfg.stage_blocks)
        import re
        block_keys = [k for k in self.params if re.fullmatch(r"s\d+b\d+", k)]
        assert len(block_keys) == n_blocks
        with pytest.raises(ValueError):
            resnet.ResNetConfig(depth=37).stage_blocks

    def test_grad_structure(self):
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 32, 3))
        y = jnp.array([0, 1])
        g = jitted(jax.grad(resnet.loss_fn))(self.params, self.cfg, x, y)
        assert jax.tree.structure(g) == jax.tree.structure(self.params)
        # every leaf receives gradient signal (no dead branches): a
        # disconnected block would produce exactly-zero grads
        norms = [float(jnp.abs(l).sum()) for l in jax.tree.leaves(g)]
        assert all(np.isfinite(n) and n > 0 for n in norms)

    def test_loss_decreases(self):
        x = jax.random.normal(jax.random.PRNGKey(3), (8, 32, 32, 3))
        y = jax.random.randint(jax.random.PRNGKey(4), (8,), 0,
                               self.cfg.n_classes)

        @jax.jit
        def step(p):
            loss, g = jax.value_and_grad(resnet.loss_fn)(p, self.cfg, x, y)
            return jax.tree.map(lambda w, gw: w - 0.05 * gw, p, g), loss

        params = self.params
        params, first = step(params)
        for _ in range(10):
            params, loss = step(params)
        assert float(loss) < float(first)

    def test_sharded_forward_matches_single(self):
        # dp+fsdp data parallelism with the auto-ZeRO-3 fallback rules
        mesh = make_mesh(MeshSpec(dp=4, fsdp=2))
        x = jax.random.normal(jax.random.PRNGKey(5), (8, 32, 32, 3))
        expect = jitted(resnet.apply)(self.params, self.cfg, x)
        from determined_clone_tpu.parallel.sharding import ShardingRules

        shardings = ShardingRules().shardings_for(self.params, mesh)
        sp = shard_put(self.params, shardings)
        sx = shard_put(x, NamedSharding(mesh, batch_spec(extra_dims=3)))
        got = jax.jit(lambda p, v: resnet.apply(p, self.cfg, v))(sp, sx)
        np.testing.assert_allclose(np.asarray(expect), np.asarray(got),
                                   atol=1e-4, rtol=1e-4)


class TestBert:
    def setup_method(self):
        self.cfg = bert.BertConfig.tiny()
        self.params = _seeded(bert, self.cfg)

    def test_classify_shape_and_dtype(self):
        tokens = jnp.zeros((2, 16), jnp.int32)
        logits = jitted(bert.classify)(self.params, self.cfg, tokens)
        assert logits.shape == (2, self.cfg.n_classes)
        assert logits.dtype == jnp.float32

    def test_mlm_logits_tied_to_embedding(self):
        tokens = jnp.zeros((1, 8), jnp.int32)
        logits = jitted(bert.mlm_logits)(self.params, self.cfg, tokens)
        assert logits.shape == (1, 8, self.cfg.vocab_size)
        # perturbing the embedding table must move the MLM projection too
        p2 = jax.tree.map(lambda x: x, self.params)
        p2["embed"] = {"table": self.params["embed"]["table"] + 0.1}
        logits2 = jitted(bert.mlm_logits)(p2, self.cfg, tokens)
        assert not np.allclose(np.asarray(logits), np.asarray(logits2))

    def test_bidirectional_not_causal(self):
        # flipping a LATER token must change EARLIER positions (encoder,
        # unlike the GPT causality test)
        t1 = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, 256)
        t2 = t1.at[0, -1].set((t1[0, -1] + 1) % 256)
        e1 = jitted(bert.encode)(self.params, self.cfg, t1)
        e2 = jitted(bert.encode)(self.params, self.cfg, t2)
        assert not np.allclose(np.asarray(e1[:, 0]), np.asarray(e2[:, 0]),
                               atol=1e-6)

    def test_pad_mask_blocks_padding(self):
        # garbage in padded positions must not leak into real tokens
        tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 16), 0, 256)
        mask = jnp.concatenate(
            [jnp.ones((1, 8), jnp.float32), jnp.zeros((1, 8), jnp.float32)], 1)
        garbage = tokens.at[0, 8:].set(255)
        encode = jitted(bert.encode)
        e1 = encode(self.params, self.cfg, tokens, pad_mask=mask)
        e2 = encode(self.params, self.cfg, garbage, pad_mask=mask)
        np.testing.assert_allclose(np.asarray(e1[:, :8]),
                                   np.asarray(e2[:, :8]), atol=1e-5)

    def test_classify_loss_decreases(self):
        tokens = jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0, 256)
        labels = jax.random.randint(jax.random.PRNGKey(4), (8,), 0, 2)

        @jax.jit
        def step(p):
            loss, g = jax.value_and_grad(bert.classify_loss)(
                p, self.cfg, tokens, labels)
            return jax.tree.map(lambda w, gw: w - 0.1 * gw, p, g), loss

        params = self.params
        params, first = step(params)
        for _ in range(10):
            params, loss = step(params)
        assert float(loss) < float(first)

    def test_mlm_loss_masks_positions(self):
        tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 16), 0, 256)
        targets = jax.random.randint(jax.random.PRNGKey(6), (2, 16), 0, 256)
        mask = jnp.zeros((2, 16)).at[:, :4].set(1.0)
        mlm_loss = jitted(bert.mlm_loss)
        loss = mlm_loss(self.params, self.cfg, tokens, targets, mask)
        # changing targets at UNMASKED positions must not move the loss
        targets2 = targets.at[:, 8:].set(0)
        loss2 = mlm_loss(self.params, self.cfg, tokens, targets2, mask)
        np.testing.assert_allclose(float(loss), float(loss2), rtol=1e-6)

    def test_sharded_forward_matches_single(self):
        mesh = make_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
        tokens = jax.random.randint(jax.random.PRNGKey(7), (8, 16), 0, 256)
        expect = jitted(bert.classify)(self.params, self.cfg, tokens)
        shardings = bert.BERT_SHARDING_RULES.shardings_for(self.params, mesh)
        sp = shard_put(self.params, shardings)
        st = shard_put(tokens, NamedSharding(mesh, batch_spec(extra_dims=1)))
        got = jax.jit(lambda p, t: bert.classify(p, self.cfg, t))(sp, st)
        np.testing.assert_allclose(np.asarray(expect), np.asarray(got),
                                   atol=2e-2, rtol=2e-2)


class TestGPT:
    def setup_method(self):
        self.cfg = gpt.GPTConfig.tiny()
        self.params = _seeded(gpt, self.cfg)

    def test_stacked_blocks_shape(self):
        qkv = self.params["blocks"]["attn_qkv"]["kernel"]
        assert qkv.shape == (2, 64, 192)  # [L, D, 3D]

    def test_forward_shape_and_dtype(self):
        tokens = jnp.zeros((2, 16), jnp.int32)
        logits = jitted(gpt.apply)(self.params, self.cfg, tokens)
        assert logits.shape == (2, 16, self.cfg.vocab_size)
        assert logits.dtype == jnp.float32

    def test_causality(self):
        t1 = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, 256)
        t2 = t1.at[0, -1].set((t1[0, -1] + 1) % 256)
        l1 = jitted(gpt.apply)(self.params, self.cfg, t1)
        l2 = jitted(gpt.apply)(self.params, self.cfg, t2)
        np.testing.assert_allclose(np.asarray(l1[:, :-1]), np.asarray(l2[:, :-1]),
                                   atol=1e-4)

    def test_loss_decreases(self):
        tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 32), 0, 256)
        inputs, targets = tokens[:, :-1], tokens[:, 1:]

        @jax.jit
        def step(p):
            loss, g = jax.value_and_grad(gpt.loss_fn)(p, self.cfg, inputs, targets)
            return jax.tree.map(lambda w, gw: w - 0.1 * gw, p, g), loss

        params = self.params
        params, first = step(params)
        for _ in range(10):
            params, loss = step(params)
        assert float(loss) < float(first)

    def test_sharded_forward_matches_single(self):
        mesh = make_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
        tokens = jax.random.randint(jax.random.PRNGKey(4), (8, 16), 0, 256)
        expect = jitted(gpt.apply)(self.params, self.cfg, tokens)

        shardings = gpt.GPT_SHARDING_RULES.shardings_for(self.params, mesh)
        sharded_params = shard_put(self.params, shardings)
        sharded_tokens = shard_put(
            tokens, NamedSharding(mesh, batch_spec(extra_dims=1))
        )

        @jax.jit
        def fwd(p, t):
            return gpt.apply(p, self.cfg, t)

        got = fwd(sharded_params, sharded_tokens)
        np.testing.assert_allclose(np.asarray(expect), np.asarray(got),
                                   atol=2e-2, rtol=2e-2)

    def test_blockwise_attention_config(self):
        cfg = gpt.GPTConfig(vocab_size=256, n_layers=2, d_model=64, n_heads=4,
                            d_ff=128, max_seq_len=128, remat=False,
                            blockwise_attention=True, attention_block_size=16)
        tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 32), 0, 256)
        base = jitted(gpt.apply)(self.params, self.cfg, tokens)
        blocked = jitted(gpt.apply)(self.params, cfg, tokens)
        # bf16 compute: different summation order → small noise
        np.testing.assert_allclose(np.asarray(base), np.asarray(blocked),
                                   atol=1e-2, rtol=1e-2)

    def test_dropout_active_only_in_training(self):
        cfg = gpt.GPTConfig(vocab_size=256, n_layers=2, d_model=64, n_heads=4,
                            d_ff=128, max_seq_len=128, remat=False, dropout=0.5)
        params = gpt.init(jax.random.PRNGKey(0), cfg)
        tokens = jnp.zeros((1, 8), jnp.int32)
        key = jax.random.PRNGKey(9)
        e1 = gpt.apply(params, cfg, tokens)
        e2 = gpt.apply(params, cfg, tokens, training=False, dropout_key=key)
        np.testing.assert_allclose(np.asarray(e1), np.asarray(e2))
        t1 = gpt.apply(params, cfg, tokens, training=True, dropout_key=key)
        assert not np.allclose(np.asarray(e1), np.asarray(t1))

    def test_param_count(self):
        n = gpt.param_count(self.params)
        assert n > 50_000  # tiny but real
