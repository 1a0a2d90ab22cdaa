"""What the serving engine needs of a decoder family.

``serving/engine.py`` imports no model. It asks the model config it was
given for this (``model_cfg.paged_model()``) and runs everything through
it: the paged forward it jits, the layout of a sequence's cache in pool
blocks, and the engine features the family's cache cannot serve yet, which
the engine refuses at construction. Every served family's module ends
in its instance of it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from determined_clone_tpu.parallel.sharding import tree_paths_and_leaves

# engine features a family may name in ``unsupported``
ENGINE_FEATURES = ("prefix_cache", "kv_store", "speculative")


@functools.partial(jax.jit, static_argnums=1)
def _cast(leaves: Tuple[Any, ...], dtypes: Tuple[Any, ...]) -> Tuple[Any, ...]:
    return tuple(x.astype(d) for x, d in zip(leaves, dtypes))


def cast_leaves(params: Any,
                read_as: Callable[[str], Optional[Any]]) -> Any:
    """``params`` with every leaf in the type ``read_as("a/b/c")`` names
    for its path (None: as it is). A leaf that already has its type comes
    back as the same object, so a tree in its serving form costs nothing
    and calling this twice is calling it once; the others are cast in one
    jitted call, on the device where they are device arrays."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    cast = {}
    for i, (path, leaf) in enumerate(tree_paths_and_leaves(params)):
        dtype = read_as(path)
        if dtype is not None and leaf.dtype != jnp.dtype(dtype):
            cast[i] = jnp.dtype(dtype)
    if cast:
        done = _cast(tuple(leaves[i] for i in cast), tuple(cast.values()))
        for i, leaf in zip(cast, done):
            leaves[i] = leaf
    return jax.tree_util.tree_unflatten(treedef, leaves)


def run_rows(run: Callable[..., Tuple[Any, ...]], tokens: jax.Array,
             positions: jax.Array, token_mask: jax.Array,
             block_tables: jax.Array, last_index: Optional[jax.Array],
             pools: Tuple[jax.Array, ...], *, tokens_per_pass: int,
             block: Optional[int] = None,
             counters: int = 0) -> Tuple[Any, ...]:
    """A call's packed rows through a family's ``run(tokens, positions,
    token_mask, tables, last, *pools) -> (logits, *pools)``: the whole
    batch in one pass when there is one row or ``B x T`` is within
    ``tokens_per_pass``, else a row at a time (``lax.scan``), the pools
    carried from row to row and the logits stacked. With ``block``, a
    slice (``T > 1``) is first padded to whole cache blocks of that many
    positions; ``run`` trims what it returns. With ``counters``, ``run``
    returns two more values, an int32 vector of that length that is summed
    over the rows and something of every token that is stacked beside the
    logits. ``last`` is None where ``last_index`` is."""
    if block and tokens.shape[1] > 1 and tokens.shape[1] % block:
        pad = ((0, 0), (0, -tokens.shape[1] % block))
        tokens, positions, token_mask = (
            jnp.pad(a, pad) for a in (tokens, positions, token_mask))
    rows = (tokens, positions, token_mask, block_tables, last_index)
    B, T = tokens.shape
    if B == 1 or B * T <= tokens_per_pass:
        return run(*rows, *pools)

    n = len(pools)

    def one_row(carry, row):
        logits, *out = run(*(None if a is None else a[None] for a in row),
                           *carry[:n])
        if not counters:
            return tuple(out), logits[0]
        hit, noted = out[n:]
        return (*out[:n], carry[n] + hit), (logits[0], noted[0])

    zeros = (jnp.zeros((counters,), jnp.int32),) if counters else ()
    carry, stacked = jax.lax.scan(one_row, (*pools, *zeros), rows)
    if not counters:
        return (stacked, *carry)
    return (stacked[0], *carry, stacked[1])


def _as_given(params: Any, cfg: Any) -> Any:
    return params


def _kv_pools(cfg: Any, cache: Any, max_batch: int) -> Tuple[Any, ...]:
    # imported here: serving/ imports the models at import time
    from determined_clone_tpu.serving.kv_cache import init_kv_pools

    return init_kv_pools(cfg, cache)


@dataclasses.dataclass(frozen=True)
class PagedModel:
    """One decoder family on the paged serving path.

    forward_paged(params, cfg, tokens, positions, token_mask, last_index,
        *pools, block_tables) -> (logits [B, V], *pools): a prefill slice
        or a decode step (``models/gpt.py:forward_paged`` states the
        contract); the engine samples ``argmax(logits)``. ``pools`` are
        the family's own (``init_pools``): the engine holds them as one
        tuple, hands them over donated and keeps what comes back, and
        looks into none of them.
    forward_paged_logits(the same minus last_index) -> (logits at every
        position, *pools): the speculative verify step.
    init(key, cfg) -> params.
    init_pools(cfg, cache, max_batch) -> the tuple of zeroed device arrays
        the cache lives in, one per name in ``pool_names``, for an engine
        of ``max_batch`` rows. The default is the uniform cache's K and V
        pools ``[L, N, block, R]`` (``kv_cache.init_kv_pools``), which is
        GPT's and EvaByte's.
    serving_params(params, cfg) -> params: the tree the engine serves
        from, every leaf in the type the paged forward reads it in
        (:func:`cast_leaves`), so that no serving program converts a
        weight. Same structure, idempotent; the default keeps the tree.
    cache_layout(cfg, cache) -> serving/kv_cache.py:CacheLayout: block
        kinds, reservation, table rows.
    unsupported: ``ENGINE_FEATURES`` the family's cache cannot serve.
    row_counters: one counter name per entry of ``cache_layout``'s
        ``row_args``, for the cache rows a decode step reads; empty =
        not counted.
    step_counters: names of what only the device knows of a call (how a
        step's tokens were routed, say). A family that names some
        returns, after its pools, one int32 vector of that length from
        ``forward_paged`` and ``forward_paged_logits``; the engine reads
        it in the transfer that reads the sampled tokens, puts it on the
        call's span and adds it to counters ``serving_<name>_total``.
        Empty (the default): the programs return logits and pools, no
        more.
    token_records: whether the programs note something of every token
        (to which experts it was routed, say). A family that does returns,
        last, one int32 array [B, T, W] from ``forward_paged`` and
        ``forward_paged_logits``; the engine reads it in the same
        transfer, keeps each row's real tokens' records and hands a
        request's back, in the order of its positions, as
        ``RequestResult.token_records``.
    prefill_counts(cfg, layout, starts, counts, length) -> {name: int}:
        what the host can say of a prefill call's work from where its
        rows' slices start, how many real tokens each holds (padding rows
        0) and the call's bucketed ``length``; the engine puts it on the
        call's ``serving_prefill`` span. None (the default): nothing.
    """
    family: str
    forward_paged: Callable[..., Any]
    forward_paged_logits: Callable[..., Any]
    init: Callable[..., Any]
    cache_layout: Callable[[Any, Any], Any]
    serving_params: Callable[[Any, Any], Any] = _as_given
    init_pools: Callable[[Any, Any, int], Tuple[Any, ...]] = _kv_pools
    pool_names: Tuple[str, ...] = ("k_pool", "v_pool")
    unsupported: Tuple[str, ...] = ()
    row_counters: Tuple[str, ...] = ()
    step_counters: Tuple[str, ...] = ()
    token_records: bool = False
    prefill_counts: Optional[Callable[..., Dict[str, int]]] = None

    def __post_init__(self) -> None:
        unknown = set(self.unsupported) - set(ENGINE_FEATURES)
        if unknown:
            raise ValueError(f"unknown engine features {sorted(unknown)}")
