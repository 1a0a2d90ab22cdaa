"""Adapter ``kimi_linear``: how a configuration file of the Kimi-Linear
family becomes the system under test — an ``InferenceEngine`` over
``models/kimi_linear.py`` — and where its seeded weights come from. Serving
only: the family has no training path (ROADMAP B-M).

The configuration file keeps its source's key names (``hidden_size``,
``linear_attn_config``, ``kv_lora_rank``, ``num_experts``,
``num_experts_per_token``, ``model_max_length``, ...). ``num_experts`` is
the experts held here; ``published_num_experts`` (the router's width) and
``first_expert`` sit beside it, and what the source does not state
(``init_std``, ``embedding_std``, ``selection_bias_std``, ``dtype``) is
listed under ``assumed``. Its serving sizes sit under ``serving_sizes``,
not ``serving`` (``README-evabyte.md`` says why).

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
from determined_clone_tpu.models import kimi_linear

REFERENCE = "kimi_linear"  # benchmarks/reference/kimi_linear.py


def dims(config: Dict[str, Any]) -> Dict[str, int]:
    """The sizes both the program and the reference are built with."""
    return {
        "vocab": int(config["vocab_size"]),
        "layers": int(config["num_hidden_layers"]),
        "d_model": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "positions": int(config["model_max_length"]),
    }


def model_config(config: Dict[str, Any]) -> Any:
    d = dims(config)
    linear = config["linear_attn_config"]
    return kimi_linear.KimiLinearConfig(
        vocab_size=d["vocab"], hidden_size=d["d_model"],
        num_hidden_layers=d["layers"],
        kda_layers=tuple(linear["kda_layers"]),
        full_attn_layers=tuple(linear["full_attn_layers"]),
        first_k_dense_replace=int(config["first_k_dense_replace"]),
        kda_num_heads=int(linear["num_heads"]),
        kda_head_dim=int(linear["head_dim"]),
        short_conv_kernel_size=int(linear["short_conv_kernel_size"]),
        num_attention_heads=d["heads"],
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_head_dim=int(config["qk_nope_head_dim"]),
        qk_rope_head_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]),
        intermediate_size=int(config["intermediate_size"]),
        moe_intermediate_size=int(config["moe_intermediate_size"]),
        num_experts=int(config["num_experts"]),
        published_num_experts=int(config["published_num_experts"]),
        first_expert=int(config["first_expert"]),
        num_experts_per_token=int(config["num_experts_per_token"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        model_max_length=d["positions"],
        rms_norm_eps=float(config["rms_norm_eps"]),
        init_std=float(config["init_std"]),
        compute_dtype=jnp.dtype(config["dtype"]),
        param_dtype=jnp.dtype(config["dtype"]))


def make_weights(config: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The model's weights from the seed, on the device, in one jitted
    call: the program's own draw (``kimi_linear.init``: the matrices
    normal(0, init_std), a layer at a time; ``A_log``, ``dt_bias`` and the
    convolution's taps as the configuration's ``assumed`` says) at the
    configuration's ``embedding_std`` and ``selection_bias_std``. A run
    starts here: what an earlier run's program said of its sequences is
    forgotten."""
    served.TOKEN_RECORDS.clear()
    return jax.jit(functools.partial(
        kimi_linear.init, cfg=model_config(config),
        bias_std=float(config["selection_bias_std"]),
        embedding_std=float(config["embedding_std"])))(seed_key(seed))


def build_engine(config: Dict[str, Any], params: Any,
                 telemetry: Optional[Any]) -> Any:
    """``InferenceEngine`` at the configuration's serving sizes: chunked
    prefill in slices of ``chunk_prefill_len``, a pool of ``max_batch``
    full-length sequences' blocks and a slot a batch row. Whoever reads a
    request's result also leaves what the programs noted of its tokens (to
    which experts each went: ``RequestResult.token_records``), with the
    prompt's length, where the reference finds it (``reference/served.py``),
    as ``adapters/glm_moe_dsa.py`` does."""
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
                # (81920 wide) to the served positions alone
                served.TOKEN_RECORDS[(*map(int, prompt), *done.tokens)] \
                    = (len(prompt), done.token_records)
            return done

        handle.result = result_noted
        return handle

    engine.submit = submit_and_note
    return engine
