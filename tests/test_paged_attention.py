"""The paged decode-attention kernel (``ops/paged_attention.py``), in
Pallas interpret mode on the CPU, against the plain form it replaces:
``decode_attention_rows`` over every row's whole gathered table.

The pool is laid out to catch what a read through the table can get wrong:
shuffled, non-contiguous block ids under a layer's offset; every block no
row's context fills is NaN (a block read past a row's length poisons the
result); the table's entries past a row's length name *another row's* live
blocks (a copy too many would go unnoticed by the NaNs, not by the sums).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from determined_clone_tpu.models import gpt
from determined_clone_tpu.ops import paged_attention as pa
from determined_clone_tpu.ops.attention import decode_attention_rows
from determined_clone_tpu.serving.kv_cache import (
    CacheLayout,
    KVCacheConfig,
    kv_row_width,
)

BLOCK, WIDTH = 16, 64  # tables of 1024 positions, as both serve cells'


def _case(heads, head_dim, lengths, dtype=jnp.bfloat16, seed=0):
    """(q, k_pool, v_pool, tables, lengths) with the layout above, and the
    gathered, NaN-free context the plain form reads."""
    rng = np.random.default_rng(seed)
    B, R = len(lengths), kv_row_width(heads, head_dim)
    D = heads * head_dim
    layer_blocks = B * WIDTH + 5
    offset = layer_blocks            # the second layer of two
    lengths = np.asarray(lengths, np.int32)
    filled = -(-lengths // BLOCK)
    ids = rng.permutation(layer_blocks)[:B * WIDTH].reshape(B, WIDTH)
    pools = []
    for _ in range(2):
        pool = np.full((2 * layer_blocks, BLOCK, R), np.nan, np.float32)
        for b in range(B):
            rows = rng.standard_normal((filled[b], BLOCK, R))
            rows[..., D:] = 0.0
            pool[offset + ids[b, :filled[b]]] = rows
        pools.append(jnp.asarray(pool, dtype))
    tables = ids.copy()
    for b in range(B):  # past the length: the next live row's blocks
        other = next((o % B for o in range(b + 1, b + B) if filled[o % B]),
                     None)
        if other is not None:
            tables[b, filled[b]:] = ids[other, 0]
    q = jnp.asarray(rng.standard_normal((B, 1, heads, head_dim)), dtype)
    return (q, *pools, jnp.asarray(offset + tables, jnp.int32),
            jnp.asarray(lengths))


def _plain(q, k_pool, v_pool, tables, lengths):
    B, S = tables.shape[0], WIDTH * BLOCK
    mask = jnp.arange(S)[None, :] < lengths[:, None]
    gathered = [jnp.where(mask[:, :, None],
                          pool[tables].reshape(B, S, -1), 0)
                for pool in (k_pool, v_pool)]
    return decode_attention_rows(q, *gathered, mask[:, None, None, :])


@pytest.mark.parametrize("lengths", [
    (0, 1, 16, 17, 1024, 300), (1024, 1024), (5,), (0, 0, 33, 0)],
    ids=["mixed", "full", "one-row", "mostly-padding"])
@pytest.mark.parametrize("heads,row_width", [(16, 1024), (25, 1664)],
                         ids=["medium", "xl"])
def test_kernel_matches_the_plain_form_over_the_gathered_context(
        heads, row_width, lengths):
    """Both serve cells' rows (16 heads fill 1024 columns; 25 heads, 1600
    of 1664), at lengths 0 (a padding row: zeros, nothing read), 1, a
    whole block, a block and one, the whole table, and a batch of them."""
    q, k_pool, v_pool, tables, n = _case(heads, 64, lengths)
    assert k_pool.shape[-1] == row_width
    out = jax.jit(pa.paged_attention)(q, k_pool, v_pool, tables, n)
    assert out.shape == q.shape and out.dtype == q.dtype
    out = np.asarray(out, np.float32)
    assert np.isfinite(out).all()
    live = np.asarray(n) > 0
    assert (out[~live] == 0).all()
    want = np.asarray(jax.jit(_plain)(q, k_pool, v_pool, tables, n),
                      np.float32)
    # the same rounding points; the fp32 sums run in another order, which
    # can move a result by one bf16 step
    np.testing.assert_allclose(out[live], want[live], rtol=2 ** -7,
                               atol=2 ** -9)


@pytest.mark.parametrize("rows,chunk", [(1, 128), (2, 512), (3, 1024)])
def test_any_sizes_give_the_rules_numbers(rows, chunk):
    """Rows a grid step and positions a product are the sweep's to vary:
    they move no number (a chunk is a whole product either way; only the
    fp32 sum over chunks is ordered by it)."""
    q, k_pool, v_pool, tables, n = _case(16, 64, (700, 0, 18, 1024, 64, 3))
    rule = jax.jit(pa.paged_attention)(q, k_pool, v_pool, tables, n)
    sz = pa.Sizes(rows, chunk, WIDTH * BLOCK // chunk)
    out = jax.jit(functools.partial(pa.paged_attention, sz=sz))(
        q, k_pool, v_pool, tables, n)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(rule, np.float32),
                               rtol=2 ** -7, atol=2 ** -9)


def test_float32_and_a_small_block_run_interpreted():
    """What the serving tests' tiny models bring: float32 rows of 8
    positions a block, a table no multiple of the rule's chunk."""
    rng = np.random.default_rng(1)
    B, H, hd, block, width = 3, 4, 8, 8, 6
    R = kv_row_width(H, hd)
    k_pool, v_pool = (jnp.asarray(rng.standard_normal((24, block, R)),
                                  jnp.float32).at[..., H * hd:].set(0)
                      for _ in range(2))
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.float32)
    tables = jnp.asarray(rng.permutation(24)[:B * width].reshape(B, width),
                         jnp.int32)
    n = jnp.asarray([48, 9, 0], jnp.int32)
    out = jax.jit(pa.paged_attention)(q, k_pool, v_pool, tables, n)
    mask = jnp.arange(width * block)[None, :] < n[:, None]
    want = decode_attention_rows(
        q, k_pool[tables].reshape(B, -1, R), v_pool[tables].reshape(B, -1, R),
        mask[:, None, None, :])
    np.testing.assert_allclose(out[:2], want[:2], rtol=1e-5, atol=1e-6)
    assert not out[2].any()


def test_sizes_and_cost_come_from_the_shapes():
    assert pa.sizes(64, 16) == pa.Sizes(1, 256, 4)   # both serve cells
    assert pa.sizes(63, 16) == pa.Sizes(1, 256, 4)   # 1008 positions
    assert pa.sizes(6, 8) == pa.Sizes(1, 48, 1)      # a table under 256
    assert pa.sizes(2, 512) == pa.Sizes(1, 512, 2)   # a block over 256
    # the bytes of attending 1000 rows of 1024 bf16 values in 24 layers:
    # K and V, each once
    cost = pa.paged_cost(1000, 1024, 24, heads=16, dtype=jnp.bfloat16)
    assert cost.bytes_accessed == 1000 * 2 * 1024 * 2 * 24
    assert cost.flops == 2 * 2 * 1000 * 1024 * 24
    assert cost.transcendentals == 1000 * 16 * 24


def test_decode_takes_the_kernel_where_the_training_path_takes_flash(
        monkeypatch):
    """Which path a decode step takes is read from the shapes and from
    ``resolved_attention_impl``, the training path's rule: T == 1 and
    "flash" (or "auto" on a chip) reach the kernel, everything else the
    gather. Compiled, the kernel also has to fit: a block of whole tiles."""
    cfg = gpt.GPTConfig(vocab_size=64, n_layers=2, d_model=32, n_heads=4,
                        d_ff=64, max_seq_len=32, remat=False)
    params = jax.jit(gpt.init, static_argnums=1)(jax.random.PRNGKey(0), cfg)
    cache = KVCacheConfig(num_blocks=8, block_size=8)
    called = []
    real = pa.paged_attention
    monkeypatch.setattr(pa, "paged_attention",
                        lambda *a, **k: called.append(1) or real(*a, **k))

    def step(cfg, t):
        from determined_clone_tpu.serving.kv_cache import init_kv_pools

        k_pool, v_pool = init_kv_pools(cfg, cache)
        del called[:]
        # a jit of its own every time: the path is chosen while tracing
        logits, _, _ = jax.jit(
            lambda p, *rest: gpt.forward_paged(p, cfg, *rest))(
            params, jnp.ones((2, t), jnp.int32),
            jnp.tile(jnp.arange(t, dtype=jnp.int32), (2, 1)),
            jnp.asarray([[True] * t, [False] * t]),
            jnp.zeros((2,), jnp.int32), k_pool, v_pool,
            jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32))
        return bool(called), logits

    import dataclasses

    flash = dataclasses.replace(cfg, attention_impl="flash")
    mha = dataclasses.replace(cfg, attention_impl="mha")
    took, by_kernel = step(flash, 1)
    assert took
    took, plain = step(mha, 1)
    assert not took
    np.testing.assert_allclose(by_kernel[0], plain[0], rtol=2e-2, atol=2e-2)
    assert not step(flash, 4)[0]                      # T > 1 gathers
    assert not step(dataclasses.replace(cfg, attention_impl="auto"), 1)[0]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gpt.resolved_attention_impl(cfg) == "flash"
    assert pa.fits(64, 16, 16, 1024, jnp.bfloat16)    # both serve cells
    assert pa.fits(64, 16, 25, 1664, jnp.bfloat16)
    assert not pa.fits(4, 8, 4, 128, jnp.bfloat16)    # half a bf16 tile
    assert not pa.fits(4096, 16, 16, 1024, jnp.bfloat16)  # 64 k positions


def test_uniform_cache_counts_attended_and_tabled_rows():
    """The decode step's span args and counters for the uniform cache:
    ``kv_rows`` at the rows' real lengths, ``table_rows`` what whole
    tables of the batch bucket span."""
    layout = CacheLayout(KVCacheConfig(num_blocks=64, block_size=16), 1024)
    assert layout.row_args == ("kv_rows", "table_rows")
    assert layout.step_rows([100, 17, 1], 4) == (118, 4 * 1024)
    assert gpt.PAGED.row_counters == ("serving_kv_rows_attended_total",
                                      "serving_kv_rows_tabled_total")
