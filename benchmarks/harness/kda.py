"""What the Kimi-Linear readers share (``layer_metrics/decode_kda_*``,
``prefill_kda_*``, ``kda_state_hbm_roofline_pct``,
``mla_dense_attn_hbm_roofline_pct``, ``moe_held_*``; PR 41): the bytes and
operations a decode step and a prefill slice have to move, from what the
program says of them. Device time of a decode step under a scope is
``harness/eva.py:scope_step_ms``, of a prefill slice
``harness/sala.py:prefill_scope_ms``, and the decode steps a trace holds are
``harness/glm.py:traced_steps``, each as it is.

The program (``determined_clone_tpu/models/kimi_linear.py``) names the
scopes ``kda`` (state read, decay, delta update, read-out, state write; the
chunk form too) and ``kda_conv`` (the short convolution, the tail read and
written) inside ``attn`` of the KDA layers, ``kv_cache`` (the latent row
written) and ``mla_attn`` (the absorbed query, the latents read through the
block table up to each row's length, softmax, ``W_UV``) inside ``attn`` of
the MLA layers, ``moe_route`` and ``moe_experts`` inside ``mlp``. A decode
step's spans carry ``kv_rows`` = ``selected_rows`` (latent rows cached and
read: every one) and ``state_slots`` (sequences whose states and tails the
step reads and writes), summed over the step's rows; its ``decode_commit``
span carries ``expert_pairs`` and ``expert_hits`` from the device; a
``serving_prefill`` span carries ``tokens``, the real tokens of the call's
slices. Where a trace or a span has none of this, every function here
returns None and nothing raises.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple

from benchmarks.harness import device, eva, glm, sala, scopes

CHUNK = 64  # ops/kda.py's


def _linear(config: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    return config.get("linear_attn_config")


def _sparse_layers(config: Dict[str, Any]) -> int:
    return int(config["num_hidden_layers"]) \
        - int(config["first_k_dense_replace"])


def state_step_bytes(a: Dict[str, Any], config: Dict[str, Any]) -> float:
    """Bytes a decode step has to move in the KDA layers: every served
    row's state ``[num_heads, d, d]`` float32 and its convolution tail
    ``[K - 1, 3 num_heads d]`` bfloat16, each once in and once out, in
    every KDA layer."""
    lin = _linear(config)
    H, d = int(lin["num_heads"]), int(lin["head_dim"])
    tail = (int(lin["short_conv_kernel_size"]) - 1) * 3 * H * d * 2
    return 2.0 * a["state_slots"] * (H * d * d * 4 + tail) \
        * len(lin["kda_layers"])


def latent_step_bytes(a: Dict[str, Any], config: Dict[str, Any],
                      itemsize: int = 2) -> float:
    """Bytes a decode step has to read of the latent cache: one latent of
    ``kv_lora_rank + qk_rope_head_dim`` values (the useful columns, not the
    row's padding) for every cached position of every row, in every MLA
    layer."""
    latent = int(config["kv_lora_rank"]) + int(config["qk_rope_head_dim"])
    return float(itemsize * a["kv_rows"] * latent
                 * len(_linear(config)["full_attn_layers"]))


# the held experts a step hits, three matrices ``hidden_size x
# moe_intermediate_size`` each: glm's count, under the same key names
experts_step_bytes = glm.experts_step_bytes


def chunk_form_cost(tokens: float, config: Dict[str, Any]
                    ) -> Tuple[float, float]:
    """``(operations, bytes)`` the chunked delta rule has to spend on
    ``tokens`` tokens in all KDA layers. Operations, a chunk of C tokens and
    a head of d channels: the two decayed Gram matrices (2 C^2 d
    multiply-adds), the solve applied to keys and values (2 C^2 d), the
    state read for the writes and for the outputs (2 C d^2), the in-chunk
    outputs (C^2 d) and the state's update (C d^2): 2 (5 C^2 d + 3 C d^2).
    Bytes, a token and a head: q, k, v in bfloat16, the log decay and the
    output in float32 (14 d); the state in and out once a slice is not
    counted (a slice is many chunks)."""
    lin = _linear(config)
    H, d = int(lin["num_heads"]), int(lin["head_dim"])
    layers = len(lin["kda_layers"])
    chunks = tokens / CHUNK
    ops = 2.0 * (5 * CHUNK * CHUNK * d + 3 * CHUNK * d * d) * chunks * H \
        * layers
    return ops, 14.0 * d * H * tokens * layers


def pairs_per_held_expert(ctx: Dict[str, Any]) -> Optional[float]:
    """Token-expert pairs a held expert got in a decode step, averaged over
    the window's steps and over all held experts of all expert layers."""
    config = ctx["cell"].config
    steps = glm.window_steps(ctx)
    if not steps or _linear(config) is None:
        return None
    held = _sparse_layers(config) * int(config["num_experts"])
    return sum(a["expert_pairs"] for a in steps) / len(steps) / held


def _peaks() -> Optional[Dict[str, float]]:
    import jax

    return device.PEAKS.get(jax.devices()[0].device_kind)


def hbm_share(ctx: Dict[str, Any], names: Sequence[str],
              needed: Callable[[Dict[str, Any], Dict[str, Any]], float]
              ) -> Optional[float]:
    """100 x the least time the chip's memory could take for ``needed(args
    of a traced step, config)`` bytes a step, over the device time a step
    spends under the scopes ``names``."""
    parsed = scopes.for_cell(ctx) if ctx["kind"] == "serve" else None
    config = ctx["cell"].config
    if parsed is None or _linear(config) is None:
        return None
    seconds = eva.scope_step_seconds(parsed, names)
    steps = [a for a in glm.traced_steps(ctx, parsed) if "state_slots" in a]
    peak = _peaks()
    if not seconds or not steps or peak is None:
        return None
    per_step = sum(needed(a, config) for a in steps) / len(steps)
    return 100.0 * per_step / peak["hbm_bytes_per_s"] / seconds


def prefill_roofline_share(ctx: Dict[str, Any], names: Sequence[str]
                           ) -> Optional[float]:
    """100 x the least time the chip could take for the chunked delta rule
    of one prefill call (the larger of its operations over the bfloat16
    peak and its bytes over the memory's peak, at the mean real tokens of
    the window's ``serving_prefill`` spans), over the device time such a
    call spends under the scopes ``names``."""
    parsed = scopes.for_cell(ctx) if ctx["kind"] == "serve" else None
    config = ctx["cell"].config
    if parsed is None or _linear(config) is None:
        return None
    seconds = sala.scope_span_seconds(parsed, sala.PREFILL_SPAN, names)
    tokens = [a["tokens"] for _, _, a
              in scopes.span_seconds(ctx, sala.PREFILL_SPAN)
              if "tokens" in a]
    peak = _peaks()
    if not seconds or not tokens or peak is None:
        return None
    ops, nbytes = chunk_form_cost(sum(tokens) / len(tokens), config)
    least = max(ops / peak["bf16_flops_per_s"],
                nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
