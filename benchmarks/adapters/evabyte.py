"""Adapter ``evabyte``: how a configuration file of the EvaByte family
becomes the system under test — an ``InferenceEngine`` over
``models/evabyte.py`` — and where its seeded weights come from. Serving
only: the family has no training path yet (ROADMAP B-M).

The configuration file keeps its source's key names (``hidden_size``,
``num_attention_heads``, ``intermediate_size``, ``num_hidden_layers``,
``window_size``, ``chunk_size``, ...). Its serving sizes sit under
``serving_sizes``, not ``serving``: ``harness/scopes.py:pool_shapes`` reads
GPT-2's key names from any configuration that has a ``serving`` block.

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
from determined_clone_tpu.models import evabyte

REFERENCE = "evabyte"  # benchmarks/reference/evabyte.py


def dims(config: Dict[str, Any]) -> Dict[str, int]:
    """The sizes both the program and the reference are built with."""
    return {
        "vocab": int(config["vocab_size"]),
        "layers": int(config["num_hidden_layers"]),
        "d_model": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "d_ff": int(config["intermediate_size"]),
        "positions": int(config["max_position_embeddings"]),
        "pred_heads": int(config["num_pred_heads"]),
    }


def _weights(key: jax.Array, d: Dict[str, int], std: float
             ) -> Dict[str, Any]:
    """Every matrix normal(0, init_std) held in bfloat16 (drawn a layer at
    a time: the fp32 draw of sixteen layers at once is larger than the
    chip); norm scales 0 (the norm multiplies by 1 + w); ``phi`` and ``mu``
    a standard normal clipped to [-1, 1] times init_std, as the source is
    understood to draw them; the head fp32, since ``fp32_logits`` computes
    it so."""
    V, L, D, F, H = d["vocab"], d["layers"], d["d_model"], d["d_ff"], d["heads"]
    keys = iter(jax.random.split(key, 11))
    f32 = jnp.float32

    def matrices(shape):
        return {"kernel": jax.lax.map(
            lambda k: (std * jax.random.normal(k, shape, f32)
                       ).astype(jnp.bfloat16),
            jax.random.split(next(keys), L))}

    def adaptive():
        return std * jnp.clip(
            jax.random.normal(next(keys), (L, H, D // H), f32), -1.0, 1.0)

    return {
        "embed": {"table": (std * jax.random.normal(next(keys), (V, D), f32)
                            ).astype(jnp.bfloat16)},
        "blocks": {
            "ln1": {"scale": jnp.zeros((L, D), f32)},
            "attn_q": matrices((D, D)), "attn_k": matrices((D, D)),
            "attn_v": matrices((D, D)), "attn_out": matrices((D, D)),
            "eva": {"phi": adaptive(), "mu": adaptive()},
            "ln2": {"scale": jnp.zeros((L, D), f32)},
            "mlp_gate": matrices((D, F)), "mlp_up": matrices((D, F)),
            "mlp_down": matrices((F, D)),
        },
        "final_norm": {"scale": jnp.zeros((D,), f32)},
        "lm_head": {"kernel": std * jax.random.normal(
            next(keys), (D, d["pred_heads"] * V), f32)},
    }


def make_weights(config: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The model's weights from the seed, on the device, in one jitted
    call."""
    make = jax.jit(functools.partial(_weights, d=dims(config),
                                     std=float(config["init_std"])))
    return make(seed_key(seed))


def model_config(config: Dict[str, Any]) -> Any:
    d = dims(config)
    return evabyte.EvaByteConfig(
        vocab_size=d["vocab"], n_layers=d["layers"], d_model=d["d_model"],
        n_heads=d["heads"], d_ff=d["d_ff"],
        window_size=int(config["window_size"]),
        chunk_size=int(config["chunk_size"]), max_seq_len=d["positions"],
        n_pred_heads=d["pred_heads"], rope_theta=float(config["rope_theta"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        init_std=float(config["init_std"]))


def build_engine(config: Dict[str, Any], params: Any,
                 telemetry: Optional[Any]) -> Any:
    """``InferenceEngine`` at the configuration's serving sizes: chunked
    prefill in window-aligned slices, a pool of ``max_batch`` full-length
    sequences of both block kinds."""
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
    return InferenceEngine(
        params, cfg,
        buckets=BucketSpec.build(int(s["max_batch"]),
                                 int(s["max_prefill_len"]),
                                 min_prefill_len=int(s["min_prefill_len"])),
        cache=KVCacheConfig(num_blocks=blocks, block_size=block),
        max_queue_depth=int(s["max_queue_depth"]), telemetry=telemetry,
        chunk_prefill_len=int(s["chunk_prefill_len"]))
