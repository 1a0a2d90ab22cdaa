"""The EVA paged decode-attention kernel (``ops/eva_paged_attention.py``),
in Pallas interpret mode on the CPU, against the plain form it replaces:
``eva_attention`` (``T == 1``) over every row's whole gathered table under
the mask of its two lengths.

The pool is laid out to catch what a read through the table can get wrong:
shuffled, non-contiguous block ids under a layer's offset; every block no
row attends is NaN (a block read past a range's length poisons the result);
the slots of a partly filled block past the length hold 1e30 in K (what a
ring slot held in the last window, large: one score of it would take the
whole softmax) and NaN in V (it must reach no sum, not even times a
probability of zero); summary entries a row did not reserve are -1.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from determined_clone_tpu.models import evabyte
from determined_clone_tpu.ops import eva_paged_attention as epa
from determined_clone_tpu.ops.attention import eva_attention
from determined_clone_tpu.serving.kv_cache import (
    KVCacheConfig,
    WindowSummaryLayout,
    init_kv_pools,
    kv_row_width,
)

STALE = 1e30
# (block, ring entries, summary entries): window 64 and 32 summary rows a
# table, as the tiny model's
TINY = (8, 8, 4)


def _case(lengths, heads=4, head_dim=32, dtype=jnp.float32, seed=0,
          geometry=TINY):
    """(q, k_pool, v_pool, tables, window_rows, summary_rows) for rows that
    attend ``lengths`` = [(window rows, summary rows), ...]."""
    block, ring, summaries = geometry
    width = ring + summaries
    rng = np.random.default_rng(seed)
    B, R, D = len(lengths), kv_row_width(heads, head_dim), heads * head_dim
    layer_blocks = B * width + 3
    offset = layer_blocks               # the second layer of two
    ids = rng.permutation(layer_blocks)[:B * width].reshape(B, width)
    tables = ids.copy()
    pools = [np.full((2 * layer_blocks, block, R), np.nan, np.float32)
             for _ in range(2)]
    for b, (w, s) in enumerate(lengths):
        for first, n in ((0, w), (ring, s)):
            filled = -(-n // block)
            for pool, stale in zip(pools, (STALE, np.nan)):
                rows = rng.standard_normal((filled * block, R))
                rows[:, D:] = 0.0
                rows[n:] = stale
                pool[offset + ids[b, first:first + filled]] = rows.reshape(
                    filled, block, R)
        tables[b, ring + -(-s // block):] = -1   # not reserved
    q = jnp.asarray(rng.standard_normal((B, 1, heads, head_dim)), dtype)
    w, s = (jnp.asarray(n, jnp.int32) for n in zip(*lengths))
    tables = offset + jnp.maximum(jnp.asarray(tables, jnp.int32), 0)
    return (q, *(jnp.asarray(p, dtype) for p in pools), tables, w, s)


def _plain(q, k_pool, v_pool, tables, window_rows, summary_rows, *,
           geometry):
    block, ring, summaries = geometry
    B = tables.shape[0]
    mask = jnp.concatenate(
        [jnp.arange(ring * block)[None] < window_rows[:, None],
         jnp.arange(summaries * block)[None] < summary_rows[:, None]],
        axis=1)
    gathered = [jnp.where(mask[:, :, None],
                          pool[tables].reshape(B, mask.shape[1], -1), 0)
                for pool in (k_pool, v_pool)]
    return eva_attention(q, *gathered, mask[:, None, :])


def _check(case, rtol, atol, geometry=TINY, **kw):
    q, *_, w, s = case
    out = jax.jit(functools.partial(   # not one dispatch an operation
        epa.eva_paged_attention, window_blocks=geometry[1], **kw))(*case)
    assert out.shape == q.shape and out.dtype == q.dtype
    out = np.asarray(out, np.float32)
    assert np.isfinite(out).all()
    live = np.asarray(w + s) > 0
    assert (out[~live] == 0).all()
    want = np.asarray(jax.jit(functools.partial(_plain, geometry=geometry))(
        *case), np.float32)
    np.testing.assert_allclose(out[live], want[live], rtol=rtol, atol=atol)
    return out


EDGES = {
    "a-windows-first-token": [(1, 8)],
    "a-windows-last-row": [(64, 16)],
    "no-summaries-yet": [(37, 0)],
    "last-blocks-partly-filled": [(13, 20)],
    "a-row-of-length-0": [(0, 0), (9, 8), (0, 0)],
    "whole-table": [(64, 32)],
    "mixed": [(1, 32), (0, 0), (64, 0), (23, 12), (8, 8), (57, 27)],
}


@pytest.mark.parametrize("dtype,rtol,atol", [
    (jnp.float32, 1e-5, 1e-6),
    # the same rounding points; the fp32 sums run in another order, which
    # can move a result by one bf16 step
    (jnp.bfloat16, 2 ** -7, 2 ** -9)], ids=["fp32", "bf16"])
@pytest.mark.parametrize("lengths", EDGES.values(), ids=EDGES.keys())
def test_kernel_matches_the_plain_form_over_the_gathered_context(
        lengths, dtype, rtol, atol):
    """Every edge of the two lengths: one window row beside summaries only,
    a full ring, no summaries, both ranges ending inside a block (the rest
    of it stale), rows that attend nothing (zeros, nothing read), -1 entries
    past the reserved summaries, and a batch of them."""
    _check(_case(lengths, dtype=dtype), rtol, atol)


@pytest.mark.parametrize("chunk", [8, 16, 24, 96])
def test_any_chunk_gives_the_rules_numbers(chunk):
    """Rows a buffer move no number: a chunk may hold the end of the
    window's rows, the gap after them and the first summaries at once, or
    one block; only the fp32 sum over chunks is ordered by it."""
    case = _case(EDGES["mixed"], seed=2)
    rule = _check(case, 1e-5, 1e-6)
    sz = epa.Sizes(chunk, -(-96 // chunk))
    out = _check(case, 1e-5, 1e-6, sz=sz)
    np.testing.assert_allclose(out, rule, rtol=1e-5, atol=1e-6)


def test_published_row_width_streams_through_two_buffers():
    """32 heads of 128 (rows of 4096 bf16 values), blocks of 16, more
    chunks than buffers: every buffer is filled more than once."""
    geometry = (16, 4, 2)
    case = _case([(50, 32), (0, 0), (64, 17)], heads=32, head_dim=128,
                 dtype=jnp.bfloat16, geometry=geometry)
    _check(case, 2 ** -7, 2 ** -9, geometry, sz=epa.Sizes(16, 6))


def test_sizes_and_fits_come_from_the_shapes(monkeypatch):
    assert epa.sizes(192, 16) == epa.Sizes(256, 12)   # the serve cell
    assert epa.sizes(12, 8) == epa.Sizes(96, 1)       # a table under 256
    assert epa.sizes(33, 8) == epa.Sizes(256, 2)      # 264 rows
    assert epa.sizes(2, 512) == epa.Sizes(512, 2)     # a block over 256
    assert epa.fits(12, 8, 4, 128, jnp.float32)       # interpreted: any
    monkeypatch.setattr(epa, "_should_interpret", lambda: False)
    assert epa.fits(192, 16, 32, 4096, jnp.bfloat16)  # the serve cell
    assert epa.fits(12, 8, 4, 128, jnp.float32)
    assert not epa.fits(12, 8, 4, 128, jnp.bfloat16)  # half a bf16 tile
    assert not epa.fits(192, 16, 32, 4000, jnp.bfloat16)  # no whole lanes
    assert not epa.fits(192, 16, 256, 32768, jnp.bfloat16)  # 64 MB of buffers


@pytest.mark.parametrize("length", [1, 2, 63, 64, 65, 100, 128, 129, 191,
                                    192])
def test_the_steps_lengths_are_the_layouts_attended_rows(length):
    """What ``_paged_backbone`` hands the kernel from ``positions`` and
    ``token_mask`` is what the engine counts on the step's span
    (``WindowSummaryLayout.attended_rows``), across three windows; a row
    with no real token attends nothing."""
    cfg = evabyte.EvaByteConfig.tiny()
    layout = WindowSummaryLayout(KVCacheConfig(64, cfg.chunk_size), 256,
                                 window=cfg.window_size, chunk=cfg.chunk_size)
    positions = jnp.asarray([[length - 1], [length - 1]], jnp.int32)
    window, summary = evabyte.decode_rows(
        cfg, positions, jnp.asarray([[True], [False]]))
    assert (int(window[0]), int(summary[0])) == layout.attended_rows(length)
    assert (int(window[1]), int(summary[1])) == (0, 0)


def test_decode_takes_the_kernel_and_slices_the_plain_form(monkeypatch):
    """Which path a call takes is read from its shapes alone: one token a
    row reaches the kernel where it fits, a slice gathers; both give the
    plain form's logits."""
    cfg = dataclasses.replace(evabyte.EvaByteConfig.tiny(),
                              compute_dtype=jnp.float32,
                              param_dtype=jnp.float32)
    params = jax.jit(evabyte.init, static_argnums=1)(
        jax.random.PRNGKey(0), cfg)
    cache = KVCacheConfig(40, cfg.chunk_size)
    layout = cfg.paged_model().cache_layout(cfg, cache)
    tables = np.zeros((2, layout.table_width), np.int32)
    layout.lay_table(tables[0], list(range(1, 11)))
    layout.lay_table(tables[1], list(range(11, 21)))
    called = []
    real = epa.eva_paged_attention
    monkeypatch.setattr(epa, "eva_paged_attention",
                        lambda *a, **k: called.append(1) or real(*a, **k))

    def run(t, fits=True):
        monkeypatch.setattr(epa, "fits", lambda *a: fits)
        del called[:]
        rng = np.random.default_rng(0)
        pools = [jnp.asarray(rng.standard_normal(p.shape), p.dtype).at[
            ..., cfg.d_model:].set(0) for p in init_kv_pools(cfg, cache)]
        # a jit of its own every time: the path is chosen while tracing
        logits, _, _ = jax.jit(
            lambda p, *rest: evabyte.forward_paged(p, cfg, *rest))(
            params, jnp.ones((2, t), jnp.int32),
            jnp.asarray([[72 + i for i in range(t)], [3] * t], jnp.int32),
            jnp.asarray([[True] * t, [t == 1] * t]),
            jnp.zeros((2,), jnp.int32), *pools, jnp.asarray(tables))
        return bool(called), np.asarray(logits)

    took, by_kernel = run(1)
    assert took
    took, plain = run(1, fits=False)
    assert not took
    np.testing.assert_allclose(by_kernel, plain, rtol=1e-5, atol=1e-6)
    assert not run(8)[0]                               # T > 1 gathers
