"""Adapter ``minicpm_sala``: how a configuration file of the MiniCPM-SALA
family becomes the system under test — an ``InferenceEngine`` over
``models/minicpm_sala.py`` — and where its seeded weights come from.
Serving only: the family has no training path (ROADMAP B-M).

The configuration file keeps its source's key names (``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``mixer_types``,
``scale_emb``, ...); what the source does not state sits beside them
(``sparse_config``, ``first_published_layer``, ``init_std``) and is listed
under ``assumed``. Its serving sizes sit under ``serving_sizes``, not
``serving`` (``README-evabyte.md`` says why).

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
from benchmarks.reference.minicpm_sala import LIGHTNING, SPARSE, decay_table
from determined_clone_tpu.models import minicpm_sala
from determined_clone_tpu.ops.sparse_attention import SparseConfig

REFERENCE = "minicpm_sala"  # benchmarks/reference/minicpm_sala.py


def dims(config: Dict[str, Any]) -> Dict[str, int]:
    """The sizes both the program and the reference are built with."""
    return {
        "vocab": int(config["vocab_size"]),
        "layers": int(config["num_hidden_layers"]),
        "d_model": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "d_ff": int(config["intermediate_size"]),
        "positions": int(config["max_position_embeddings"]),
    }


def _weights(key: jax.Array, config: Dict[str, Any]) -> Dict[str, Any]:
    """Every matrix, the embedding and the head normal(0, init_std) held in
    bfloat16 (drawn a layer at a time: the fp32 draw of six layers at once
    is larger than the chip); norm scales 1; the decay table by the Lightning
    Attention convention, from the lightning layers' published indices."""
    d = dims(config)
    V, D, F = d["vocab"], d["d_model"], d["d_ff"]
    Q, KV = d["heads"] * d["head_dim"], d["kv_heads"] * d["head_dim"]
    mixers = list(config["mixer_types"])
    std = float(config["init_std"])
    keys = iter(jax.random.split(key, 32))
    f32 = jnp.float32

    def matrices(n, shape):
        return {"kernel": jax.lax.map(
            lambda k: (std * jax.random.normal(k, shape, f32)
                       ).astype(jnp.bfloat16),
            jax.random.split(next(keys), n))}

    def ones(n, width):
        return {"scale": jnp.ones((n, width), f32)}

    def stack(n, kv_width):
        return {
            "ln1": ones(n, D), "attn_q": matrices(n, (D, Q)),
            "attn_k": matrices(n, (D, kv_width)),
            "attn_v": matrices(n, (D, kv_width)),
            "attn_gate": matrices(n, (D, Q)),
            "attn_out": matrices(n, (Q, D)),
            "q_norm": ones(n, d["head_dim"]),
            "k_norm": ones(n, d["head_dim"]),
            "ln2": ones(n, D), "mlp_gate": matrices(n, (D, F)),
            "mlp_up": matrices(n, (D, F)), "mlp_down": matrices(n, (F, D)),
        }

    first = int(config["first_published_layer"])
    lightning = stack(mixers.count(LIGHTNING), Q)
    lightning["out_norm"] = ones(mixers.count(LIGHTNING), Q)
    lightning["decay"] = jnp.asarray(decay_table(
        [first + i for i, kind in enumerate(mixers) if kind == LIGHTNING],
        d["heads"], int(config["published_num_hidden_layers"])))
    return {
        "embed": {"table": (std * jax.random.normal(next(keys), (V, D), f32)
                            ).astype(jnp.bfloat16)},
        "sparse": stack(mixers.count(SPARSE), KV),
        "lightning": lightning,
        "final_norm": {"scale": jnp.ones((D,), f32)},
        "lm_head": {"kernel": (std * jax.random.normal(
            next(keys), (D, V), f32)).astype(jnp.bfloat16)},
    }


def make_weights(config: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The model's weights from the seed, on the device, in one jitted
    call."""
    return jax.jit(functools.partial(_weights, config=config))(
        seed_key(seed))


def model_config(config: Dict[str, Any]) -> Any:
    d, s = dims(config), config["sparse_config"]
    if len(config["mixer_types"]) != d["layers"]:
        raise ValueError("mixer_types does not name num_hidden_layers layers")
    return minicpm_sala.MiniCPMSALAConfig(
        vocab_size=d["vocab"], mixer_types=tuple(config["mixer_types"]),
        first_layer=int(config["first_published_layer"]),
        n_published_layers=int(config["published_num_hidden_layers"]),
        d_model=d["d_model"], n_heads=d["heads"], n_kv_heads=d["kv_heads"],
        head_dim=d["head_dim"], d_ff=d["d_ff"], max_seq_len=d["positions"],
        scale_emb=float(config["scale_emb"]),
        scale_depth=float(config["scale_depth"]),
        dim_model_base=int(config["dim_model_base"]),
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        init_std=float(config["init_std"]),
        sparse=SparseConfig(
            kernel=int(s["kernel_size"]), stride=int(s["kernel_stride"]),
            block=int(s["block_size"]), topk=int(s["topk"]),
            init_blocks=int(s["init_blocks"]), window=int(s["window_size"]),
            dense_len=int(s["dense_len"])))


def build_engine(config: Dict[str, Any], params: Any,
                 telemetry: Optional[Any]) -> Any:
    """``InferenceEngine`` at the configuration's serving sizes: chunked
    prefill in slices of ``chunk_prefill_len``, a pool of ``max_batch``
    full-length sequences' blocks and a state slot a row."""
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
