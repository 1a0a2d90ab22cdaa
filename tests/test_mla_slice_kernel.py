"""``ops/mla_attention.py:mla_slice`` (one Pallas kernel, interpreted here)
against the plain-JAX loop it replaced, kept below as the oracle."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from determined_clone_tpu.ops import mla_attention as mla

NEG_INF = mla.NEG_INF
BLOCK, R, RANK = 4, 128, 96


def plain_slice(q, blocks, tables, allowed, positions, token_mask, *, scale,
                key_blocks=32, query_block=512):
    """The slice form as the package had it before the kernel: a
    ``lax.scan`` over blocks of queries, inside it a loop over passes of
    ``key_blocks`` blocks of positions, an online softmax over
    ``[B, query_block, H, key_blocks * block]`` fp32 scores. Arguments as
    ``mla_slice``; returns the sums over whole rows, [B, T, H, R] fp32."""
    B, T, H, R = q.shape
    bs, W = blocks.shape[1], tables.shape[1]
    nb = min(key_blocks, W)
    S = nb * bs
    pad = -W % nb
    tables = jnp.pad(tables, ((0, 0), (0, pad)))
    if allowed is not None:
        allowed = jnp.pad(allowed, ((0, 0), (0, 0), (0, pad * bs)))
    qb = math.gcd(T, query_block)

    def queries(_, block):
        q, *selection, positions, token_mask = block

        def step(s, carry):
            m_run, l_run, acc = carry
            phys = jax.lax.dynamic_slice_in_dim(tables, s * nb, nb, axis=1)
            chunk = blocks[phys].reshape(B, S, R)
            scores = jnp.einsum("bthr,bsr->bths", q, chunk,
                                preferred_element_type=jnp.float32) * scale
            if selection:
                seen = jax.lax.dynamic_slice_in_dim(
                    selection[0], s * S, S, axis=2)[:, :, None, :]
            else:
                seen = ((s * S + jnp.arange(S) <= positions[:, :, None])
                        & token_mask[:, :, None])[:, :, None, :]
            m_new = jnp.maximum(m_run, jnp.max(
                jnp.where(seen, scores, NEG_INF), axis=-1))
            p = jnp.where(seen, jnp.exp(scores - m_new[..., None]), 0.0)
            fade = jnp.exp(m_run - m_new)
            acc = acc * fade[..., None] + jnp.einsum(
                "bths,bsr->bthr", p.astype(blocks.dtype), chunk,
                preferred_element_type=jnp.float32)
            return m_new, l_run * fade + jnp.sum(p, axis=-1), acc

        last = jnp.max(jnp.where(token_mask, positions, 0))
        stat = jnp.full((B, qb, H), NEG_INF, jnp.float32)
        _, l_run, acc = jax.lax.fori_loop(
            0, last // S + 1, step,
            (stat, jnp.zeros_like(stat),
             jnp.zeros((B, qb, H, R), jnp.float32)))
        return None, acc / jnp.maximum(l_run, 1e-30)[..., None]

    def by_block(a):
        return jnp.moveaxis(a.reshape(B, T // qb, qb, *a.shape[2:]), 1, 0)

    inputs = (q, positions, token_mask) if allowed is None \
        else (q, allowed, positions, token_mask)
    _, out = jax.lax.scan(queries, None, tuple(map(by_block, inputs)))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H, R)


def _case(heads, *, starts, reals, selected, width=10, T=16, seed=0):
    """A call of ``len(starts)`` rows: row ``b``'s slice starts at position
    ``starts[b]`` and holds ``reals[b]`` real tokens of ``T``; its table
    names ``width`` of the pool's blocks in scrambled order. The blocks no
    table names, and those past each row's last real query's key tile
    (``key_blocks=2``: 8 positions), hold NaN."""
    rng = np.random.default_rng(seed)
    B = len(starts)
    tables = rng.permutation(np.arange(1, 3 * B * width))[:B * width] \
        .reshape(B, width).astype(np.int32)
    pool = np.full((3 * B * width, BLOCK, R), np.nan, np.float32)
    positions = np.asarray(starts)[:, None] + np.arange(T)[None]
    mask = np.arange(T)[None] < np.asarray(reals)[:, None]
    for b in range(B):
        last = max(starts[b] + reals[b] - 1, 0)
        reached = (last // (2 * BLOCK) + 1) * 2
        pool[tables[b, :reached]] = rng.normal(
            size=(reached, BLOCK, R)) * 0.5
    q = rng.normal(size=(B, T, heads, R)).astype(np.float32)
    S = width * BLOCK
    allowed = None
    if selected:
        # up to six of the positions a real query may attend, and for the
        # first real query of every row none at all
        allowed = np.zeros((B, T, S), bool)
        for b in range(B):
            for t in range(1, reals[b]):
                n = positions[b, t] + 1
                allowed[b, t, rng.permutation(n)[:6]] = True
    return (jnp.asarray(q), jnp.asarray(pool), jnp.asarray(tables),
            None if allowed is None else jnp.asarray(allowed),
            jnp.asarray(positions, jnp.int32), jnp.asarray(mask))


CASES = {
    # the last real query on a key tile's last position (7, 15) and first (8)
    "tile_last": dict(starts=(0, 8), reals=(8, 8)),
    "tile_first": dict(starts=(8, 0), reals=(1, 9)),
    # a ragged tail beside a wholly padded row
    "ragged_and_padded": dict(starts=(12, 20), reals=(11, 0)),
    "whole_table": dict(starts=(24, 4), reals=(16, 16)),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("heads", [2, 8])
@pytest.mark.parametrize("selected", [True, False],
                         ids=["allowed", "causal"])
def test_kernel_gives_the_plain_loops_sums(selected, heads, case):
    """The kernel's sums are the plain loop's over the rows' first ``rank``
    columns, to fp32 rounding in another order; zeros where a query
    attends nothing (padding, or a real query handed no position); nothing
    of a block past the last real query's key tile is read (NaN there);
    the result is ``rank`` wide, or ``R`` where none is given."""
    q, pool, tables, allowed, positions, mask = _case(
        heads, selected=selected, **CASES[case])
    kw = dict(scale=0.25, key_blocks=2, query_block=8)
    got = mla.mla_slice(q, pool, tables, allowed, positions, mask,
                        rank=RANK, **kw)
    assert got.shape == q.shape[:3] + (RANK,) and got.dtype == jnp.float32
    want = plain_slice(q, jnp.nan_to_num(pool), tables, allowed, positions,
                       mask, **kw)[..., :RANK]
    got, want = np.asarray(got), np.asarray(want)
    real = np.asarray(mask)
    if selected:
        nothing = ~np.asarray(allowed).any(-1)
        assert nothing[real].any()
    else:
        nothing = ~real
    assert np.isfinite(got).all()
    assert np.abs(got[nothing]).max(initial=0.0) == 0.0
    assert np.abs(np.where(real[..., None, None], got - want, 0)).max() < 2e-6
    if case == "whole_table":
        whole = mla.mla_slice(q, pool, tables, allowed, positions, mask, **kw)
        assert whole.shape == q.shape
        assert np.abs(np.asarray(whole)[..., :RANK] - got).max() < 2e-6


def test_the_tiles_are_the_shapes_own():
    """Rows a product and positions a key tile from ``H`` and the block
    alone: both serve cells' slices get 2048 rows over 512 positions, a
    short table or slice what there is of it."""
    assert mla.tiles(2048, 64, 512, 64) == mla.Tiles(32, 8)
    assert mla.tiles(512, 32, 800, 64) == mla.Tiles(64, 8)
    assert mla.tiles(48, 4, 12, 4) == mla.Tiles(16, 12)


def test_the_hosts_count_of_key_tiles_is_the_kernels():
    """``key_tiles`` reckons on the host, from where each row's slice
    starts and how many real tokens it holds, the tiles the call's
    ``n_tiles`` tell the kernel to multiply."""
    T, width, block = 128, 40, 64
    starts, counts = [0, 2048, 1536, 0], [128, 1, 77, 0]
    tl = mla.tiles(T, 64, width, block)
    assert tl == mla.Tiles(32, 8)
    positions = np.asarray(starts)[:, None] + np.arange(T)[None]
    reach = np.where(np.arange(T)[None] < np.asarray(counts)[:, None],
                     positions, -1)
    n_tiles = np.maximum(reach.reshape(4, -1, tl.queries).max(-1), 0) \
        // (tl.key_blocks * block) + 1
    assert mla.key_tiles(starts, counts, T - 3, 64, width, block,
                         layers=5) == {
        "mla_key_tiles": 5 * int(n_tiles.sum()),
        "mla_key_tiles_dense": 5 * 4 * 4 * 5}
    assert n_tiles.tolist() == [[1, 1, 1, 1], [5, 1, 1, 1], [4, 4, 4, 1],
                                [1, 1, 1, 1]]


def test_a_prefill_span_says_what_share_of_the_key_tiles_ran():
    """Through the engine (Kimi-Linear's tiny model, slices of 32): every
    ``serving_prefill`` span carries ``mla_key_tiles`` and
    ``mla_key_tiles_dense`` as the family reckons them from the call's
    rows, and a slice late in a prompt multiplies more tiles than the
    first one."""
    import test_kimi_linear as tk
    from determined_clone_tpu.models import kimi_linear as kl
    from determined_clone_tpu.telemetry import MetricsRegistry, Tracer

    params = kl.init(jax.random.PRNGKey(0), tk.CFG)
    tracer = Tracer(enabled=True)
    telemetry = type("T", (), {"registry": MetricsRegistry(),
                               "tracer": tracer})()
    with tk._engine(params, telemetry=telemetry) as eng:
        eng.submit(tk._tokens(3, 150).tolist(),
                   max_new_tokens=2).result(timeout=600)
        layout = eng._layout
    prefills = [e["args"] for e in tracer.events()
                if e.get("name") == "serving_prefill"]
    assert [a["tokens"] for a in prefills] == [32, 32, 32, 32, 22]
    layers = len(tk.CFG.full_attn_layers)
    for i, a in enumerate(prefills):
        want = kl.PAGED.prefill_counts(tk.CFG, layout, [32 * i],
                                       [a["tokens"]], a["length"])
        assert {k: a[k] for k in want} == want
        assert 0 < a["mla_key_tiles"] <= a["mla_key_tiles_dense"]
    tiles_of = mla.tiles(32, tk.CFG.num_attention_heads,
                         layout.table_width - 1, tk.BLOCK)
    per = tiles_of.key_blocks * tk.BLOCK
    assert prefills[0]["mla_key_tiles"] == layers * (31 // per + 1)
    assert prefills[-1]["mla_key_tiles"] == layers * (149 // per + 1)
