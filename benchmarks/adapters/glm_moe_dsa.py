"""Adapter ``glm_moe_dsa``: how a configuration file of the GLM-5.2 family
becomes the system under test — an ``InferenceEngine`` over
``models/glm_moe_dsa.py`` — and where its seeded weights come from. Serving
only: the family has no training path (ROADMAP B-M).

The configuration file keeps its source's key names (``hidden_size``,
``q_lora_rank``, ``kv_lora_rank``, ``index_topk``, ``mlp_layer_types``,
``indexer_types``, ``n_routed_experts``, ...). ``n_routed_experts`` is the
experts held here; ``published_n_routed_experts`` (the router's width) and
``first_expert`` sit beside it, and what the source does not state
(``init_std``, ``embedding_std``, ``selection_bias_std``,
``index_norm_eps``, ``dtype``) is listed under ``assumed``. Its serving
sizes sit under ``serving_sizes``, not ``serving`` (``README-evabyte.md``
says why).

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
from determined_clone_tpu.models import glm_moe_dsa

REFERENCE = "glm_moe_dsa"  # benchmarks/reference/glm_moe_dsa.py


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
    if not len(config["mlp_layer_types"]) == len(config["indexer_types"]) \
            == d["layers"]:
        raise ValueError("mlp_layer_types / indexer_types do not name "
                         "num_hidden_layers layers")
    return glm_moe_dsa.GLMMoeDsaConfig(
        vocab_size=d["vocab"], hidden_size=d["d_model"],
        mlp_layer_types=tuple(config["mlp_layer_types"]),
        indexer_types=tuple(config["indexer_types"]),
        num_attention_heads=d["heads"],
        q_lora_rank=int(config["q_lora_rank"]),
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_head_dim=int(config["qk_nope_head_dim"]),
        qk_rope_head_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]),
        index_n_heads=int(config["index_n_heads"]),
        index_head_dim=int(config["index_head_dim"]),
        index_topk=int(config["index_topk"]),
        intermediate_size=int(config["intermediate_size"]),
        moe_intermediate_size=int(config["moe_intermediate_size"]),
        n_routed_experts=int(config["n_routed_experts"]),
        published_n_routed_experts=int(config["published_n_routed_experts"]),
        first_expert=int(config["first_expert"]),
        num_experts_per_tok=int(config["num_experts_per_tok"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        max_position_embeddings=d["positions"],
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        index_norm_eps=float(config["index_norm_eps"]),
        init_std=float(config["init_std"]),
        compute_dtype=jnp.dtype(config["dtype"]),
        param_dtype=jnp.dtype(config["dtype"]))


def _weights(key: jax.Array, config: Dict[str, Any]) -> Dict[str, Any]:
    """Every matrix and the head normal(0, init_std), the embedding
    normal(0, embedding_std), rounded to bfloat16 and held in the
    configuration's ``dtype``, drawn a layer at a time (the fp32 draw of
    three expert layers at once is larger than the chip); the router
    float32; norm scales 1,
    the indexer's LayerNorm bias 0; the router's selection bias normal(0,
    selection_bias_std). The shapes are the program's
    (``glm_moe_dsa.layer_shapes``), the numbers the benchmark's."""
    cfg = model_config(config)
    std, f32 = float(config["init_std"]), jnp.float32
    held = cfg.param_dtype
    bias_std = float(config["selection_bias_std"])
    keys = iter(jax.random.split(key, 64))

    def normal(shape, n, scale, dtype):
        return jax.lax.map(
            lambda k: (scale * jax.random.normal(k, shape, f32)
                       ).astype(jnp.bfloat16).astype(dtype),
            jax.random.split(next(keys), n))

    def leaf(path, shape, n):
        if path.endswith("/scale"):
            return jnp.ones((n, *shape), f32)
        if path == "router/bias":
            return normal(shape, n, bias_std, f32)
        if path.endswith("/bias"):
            return jnp.zeros((n, *shape), f32)
        return normal(shape, n, std,
                      f32 if path == "router/kernel" else held)

    params: Dict[str, Any] = {}
    for kind in sorted(set(cfg.kinds)):
        stack: Dict[str, Any] = {}
        for path, shape in glm_moe_dsa.layer_shapes(cfg, kind).items():
            group, name = path.split("/")
            stack.setdefault(group, {})[name] = leaf(
                path, shape, cfg.kinds.count(kind))
        params[kind] = stack
    V, D = cfg.vocab_size, cfg.hidden_size
    params["embed"] = {"table": normal(
        (V, D), 1, float(config["embedding_std"]), held)[0]}
    params["final_norm"] = {"scale": jnp.ones((D,), f32)}
    params["lm_head"] = {"kernel": normal((D, V), 1, std, held)[0]}
    return params


def make_weights(config: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The model's weights from the seed, on the device, in one jitted
    call. A run starts here: what an earlier run's program said of its
    sequences is forgotten."""
    served.TOKEN_RECORDS.clear()
    return jax.jit(functools.partial(_weights, config=config))(
        seed_key(seed))


def build_engine(config: Dict[str, Any], params: Any,
                 telemetry: Optional[Any]) -> Any:
    """``InferenceEngine`` at the configuration's serving sizes: chunked
    prefill in slices of ``chunk_prefill_len`` and a pool of ``max_batch``
    full-length sequences' blocks. Whoever reads a request's result also
    leaves what the programs noted of its tokens (to which experts each
    went: ``RequestResult.token_records``) where the reference finds it
    (``reference/served.py``)."""
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
                served.TOKEN_RECORDS[(*map(int, prompt), *done.tokens)] \
                    = done.token_records
            return done

        handle.result = result_noted
        return handle

    engine.submit = submit_and_note
    return engine
