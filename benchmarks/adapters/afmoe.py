"""Adapter ``afmoe``: how a configuration file of Arcee's Trinity family
(``model_type`` ``afmoe``) becomes the system under test — an
``InferenceEngine`` over ``models/afmoe.py`` — and where its seeded weights
come from. Serving only: the family has no training path (ROADMAP B-M).

The configuration file keeps its source's key names (``hidden_size``,
``layer_types``, ``num_dense_layers``, ``num_key_value_heads``,
``sliding_window``, ``num_experts``, ``num_experts_per_tok``,
``route_scale``, ``max_position_embeddings``, ...). ``num_experts`` is the
experts held here; ``published_num_experts`` (the router's width) and
``first_expert`` sit beside it, and what the source does not state
(``init_std``, ``embedding_std``, ``selection_bias_std``, ``dtype``) is
listed under ``assumed``. Its serving sizes sit under ``serving_sizes``,
not ``serving`` (``README-evabyte.md`` says why); the ring the sliding
layers keep is ``sliding_window + chunk_prefill_len`` positions.

The program is imported here, at the top: against a program that lacks the
family the cell fails at once, with an ImportError, before any weight is
made.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmarks.adapters.gpt import (  # noqa: F401 - the harness calls them
    new_telemetry,
    program_spans,
    seed_key,
)
from benchmarks.reference import served
from determined_clone_tpu.models import afmoe

REFERENCE = "afmoe"  # benchmarks/reference/afmoe.py


def dims(config: Dict[str, Any]) -> Dict[str, int]:
    """The sizes both the program and the reference are built with."""
    return {
        "vocab": int(config["vocab_size"]),
        "layers": int(config["num_hidden_layers"]),
        "d_model": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "positions": int(config["max_position_embeddings"]),
    }


def model_config(config: Dict[str, Any]) -> Any:
    d = dims(config)
    return afmoe.AfmoeConfig(
        vocab_size=d["vocab"], hidden_size=d["d_model"],
        num_hidden_layers=d["layers"],
        layer_types=tuple(config["layer_types"]),
        num_dense_layers=int(config["num_dense_layers"]),
        num_attention_heads=d["heads"],
        num_key_value_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        sliding_window=int(config["sliding_window"]),
        rope_theta=float(config["rope_theta"]),
        intermediate_size=int(config["intermediate_size"]),
        moe_intermediate_size=int(config["moe_intermediate_size"]),
        num_experts=int(config["num_experts"]),
        published_num_experts=int(config["published_num_experts"]),
        first_expert=int(config["first_expert"]),
        num_experts_per_tok=int(config["num_experts_per_tok"]),
        route_scale=float(config["route_scale"]),
        mup_enabled=bool(config["mup_enabled"]),
        max_position_embeddings=d["positions"],
        rms_norm_eps=float(config["rms_norm_eps"]),
        prefill_slice_len=int(config["serving_sizes"]["chunk_prefill_len"]),
        init_std=float(config["init_std"]),
        compute_dtype=jnp.dtype(config["dtype"]),
        param_dtype=jnp.dtype(config["dtype"]))


def make_weights(config: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The model's weights from the seed, on the device, in one jitted
    call: the program's own draw (``afmoe.init``: the matrices normal(0,
    init_std), a layer at a time) at the configuration's ``embedding_std``
    and ``selection_bias_std``. A run starts here: what an earlier run's
    program said of its sequences is forgotten."""
    served.TOKEN_RECORDS.clear()
    return jax.jit(functools.partial(
        afmoe.init, cfg=model_config(config),
        bias_std=float(config["selection_bias_std"]),
        embedding_std=float(config["embedding_std"])))(seed_key(seed))


def build_engine(config: Dict[str, Any], params: Any,
                 telemetry: Optional[Any]) -> Any:
    """``InferenceEngine`` at the configuration's serving sizes: chunked
    prefill in slices of ``chunk_prefill_len``, a pool of ``max_batch``
    full-length sequences' blocks for the full layers and a slot (a ring
    in every sliding layer) a batch row. Whoever reads a request's result
    also leaves what the programs noted of its tokens (to which experts
    each went: ``RequestResult.token_records``), with the prompt's length,
    where the reference finds it (``reference/served.py``), as
    ``adapters/glm_moe_dsa.py`` does."""
    from determined_clone_tpu.serving import (
        BucketSpec,
        InferenceEngine,
        KVCacheConfig,
    )

    s = config["serving_sizes"]
    cfg = model_config(config)
    block = int(s["kv_block_size"])
    blocks = int(s["kv_blocks"]) or int(s["max_batch"]) \
        * InferenceEngine.blocks_per_sequence(cfg, block)
    engine = InferenceEngine(
        params, cfg,
        buckets=BucketSpec.build(int(s["max_batch"]),
                                 int(s["max_prefill_len"]),
                                 min_prefill_len=int(s["min_prefill_len"])),
        cache=KVCacheConfig(num_blocks=blocks, block_size=block),
        max_queue_depth=int(s["max_queue_depth"]), telemetry=telemetry,
        chunk_prefill_len=int(s["chunk_prefill_len"]))
    submit = engine.submit

    def submit_and_note(prompt, **kw):
        handle = submit(prompt, **kw)
        result = handle.result

        def result_noted(timeout=None):
            done = result(timeout)
            if done.token_records is not None:
                # with the prompt's length: the reference applies the head
                # to the served positions alone
                served.TOKEN_RECORDS[(*map(int, prompt), *done.tokens)] \
                    = (len(prompt), done.token_records)
            return done

        handle.result = result_noted
        return handle

    engine.submit = submit_and_note
    return engine
