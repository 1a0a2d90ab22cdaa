"""What the serving engine needs of a decoder family.

``serving/engine.py`` imports no model. It asks the model config it was
given for this (``model_cfg.paged_model()``) and runs everything through
it: the paged forward it jits, the layout of a sequence's cache in pool
blocks, and the engine features the family's cache cannot serve yet, which
the engine refuses at construction. ``models/gpt.py`` and
``models/evabyte.py`` each end in their instance of it.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Tuple

# engine features a family may name in ``unsupported``
ENGINE_FEATURES = ("prefix_cache", "kv_store", "speculative")


@dataclasses.dataclass(frozen=True)
class PagedModel:
    """One decoder family on the paged serving path.

    forward_paged(params, cfg, tokens, positions, token_mask, last_index,
        k_pool, v_pool, block_tables) -> (logits [B, V], k_pool, v_pool):
        a prefill slice or a decode step (``models/gpt.py:forward_paged``
        states the contract); the engine samples ``argmax(logits)``.
    forward_paged_logits(the same minus last_index) -> (logits at every
        position, k_pool, v_pool): the speculative verify step.
    init(key, cfg) -> params.
    cache_layout(cfg, cache) -> serving/kv_cache.py:CacheLayout: block
        kinds, reservation, table rows. Pools are
        ``kv_cache.init_kv_pools(cfg, cache)`` for every family.
    unsupported: ``ENGINE_FEATURES`` the family's cache cannot serve.
    row_counters: one counter name per kind of ``cache_layout``'s, for the
        cache rows a decode step attends; empty = not counted.
    """
    family: str
    forward_paged: Callable[..., Any]
    forward_paged_logits: Callable[..., Any]
    init: Callable[..., Any]
    cache_layout: Callable[[Any, Any], Any]
    unsupported: Tuple[str, ...] = ()
    row_counters: Tuple[str, ...] = ()

    def __post_init__(self) -> None:
        unknown = set(self.unsupported) - set(ENGINE_FEATURES)
        if unknown:
            raise ValueError(f"unknown engine features {sorted(unknown)}")
