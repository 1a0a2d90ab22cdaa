"""Analytic FLOPs accounting and MFU.

Computes per-training-step floating point operations from a model config
alone — no tracing, no cost models — so every step can report
``flops_per_sec`` and ``mfu`` even on hardware where we only *assume* a
peak. The formulas follow the standard transformer accounting
(Kaplan/Chinchilla convention): a matmul of ``[m, k] @ [k, n]`` costs
``2*m*k*n`` FLOPs, and a training step costs roughly 3x the forward pass
(1x forward + 2x backward).

Per-token forward FLOPs by component, for a model with ``L`` layers,
model width ``d``, ``H`` heads, FFN width ``f``, sequence length ``s``,
vocab ``V``:

- attention projections (q,k,v,out):      ``L * 8 * d^2``
- attention scores + value mix:           ``L * 4 * s * d``
  (flash and plain MHA perform the same matmuls — flash saves memory
  traffic, not arithmetic, so both use this count)
- dense MLP (two matmuls):                ``L * 4 * d * f``
- MoE MLP (top-k of E experts):           ``L * k * 4 * d * f``
  plus router:                            ``L * 2 * d * E``
- embeddings/logits (tied or not, the logit matmul dominates):
                                          ``2 * d * V``

The widely used ``6 * n_params`` approximation is available as
:func:`dense_train_flops_per_token` for models we have no config for.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

# Training multiplier: forward + backward(2x).
TRAIN_MULT = 3.0

# Published per-chip peaks (Google Cloud TPU documentation, per
# generation): bf16 matmul FLOP/s, HBM bytes/s, and further down the ICI
# bytes/s. ONE table. The CPU number is a
# deliberately round order-of-magnitude estimate (tens of GFLOPs for a
# few vector cores) — its job is to keep the plumbing exercised in CPU
# tests, not to be a utilization claim. The provenance label says which.
TPU_PEAK_BF16_FLOPS: Dict[str, float] = {
    "v4": 275e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,
}
TPU_HBM_BYTES_PER_S: Dict[str, float] = {
    "v4": 1228e9,
    "v5e": 819e9,
    "v5p": 2765e9,
    "v6e": 1640e9,
}
# ``jax.devices()[0].device_kind`` -> generation. The kind the runtime
# reports is the only thing that names the chip; a kind missing here has
# no peak, and so no MFU (never a default).
TPU_DEVICE_KINDS: Dict[str, str] = {
    "TPU v4": "v4",
    "TPU v5 lite": "v5e",
    "TPU v5e": "v5e",
    "TPU v5": "v5p",
    "TPU v5p": "v5p",
    "TPU v6 lite": "v6e",
    "TPU v6e": "v6e",
}
CPU_PEAK_EST_FLOPS = 50e9


@dataclass(frozen=True)
class StepFlops:
    """FLOPs for one training step, with a component breakdown."""
    total: float
    per_token: float
    tokens: int
    breakdown: Dict[str, float]

    def flops_per_sec(self, step_seconds: float) -> float:
        if step_seconds <= 0:
            return 0.0
        return self.total / step_seconds


def attention_flops_per_token(d_model: int, seq_len: int,
                              n_layers: int) -> float:
    """Projections + scores + value mix, per token, forward pass."""
    proj = 8.0 * d_model * d_model
    mix = 4.0 * seq_len * d_model
    return n_layers * (proj + mix)


def mlp_flops_per_token(d_model: int, d_ff: int, n_layers: int, *,
                        moe_experts: int = 0, moe_k: int = 2) -> float:
    """Dense or MoE FFN per token, forward pass (router included).

    The MoE branch is the textbook top-k approximation (each token visits
    k experts, dispatch/combine free); :func:`moe_layer_flops` has the
    exact count for the einsum-dispatch implementation in ops/moe.py,
    which needs the token count and is what
    :func:`gpt_train_step_flops` uses when the config routes.
    """
    dense = 4.0 * d_model * d_ff
    if moe_experts and moe_experts > 1:
        k = max(1, min(moe_k, moe_experts))
        router = 2.0 * d_model * moe_experts
        return n_layers * (k * dense + router)
    return n_layers * dense


def moe_layer_flops(n_tokens: int, d_model: int, d_ff: int,
                    n_experts: int, *,
                    capacity_factor: float = 1.25) -> Dict[str, float]:
    """Exact forward FLOPs of one capacity-based MoE FFN layer for a
    batch of ``n_tokens`` tokens, matching the einsum-dispatch path in
    ops/moe.py term by term.

    With N tokens, E experts, capacity ``C = ceil(N/E · cf)``, width D,
    FFN width F, the five matmuls/einsums cost (2 FLOPs per MAC):

    - router  ``[N,D]@[D,E]``:            ``2·N·D·E``
    - dispatch ``nec,nd->ecd``:           ``2·N·E·C·D``
    - up      ``ecd,edf->ecf``:           ``2·E·C·D·F``
    - down    ``ecf,efd->ecd``:           ``2·E·C·F·D``
    - combine ``nec,ecd->nd``:            ``2·N·E·C·D``

    Note the count is shaped by E·C (experts always compute their full
    capacity buffer, padded slots included), not by top-k — that is the
    price of the static-shape dispatch form, and exactly why this differs
    from the per-token approximation in :func:`mlp_flops_per_token`.
    """
    n = float(n_tokens)
    d, f, e = float(d_model), float(d_ff), float(n_experts)
    c = float(max(1, math.ceil(n_tokens / n_experts * capacity_factor)))
    out = {
        "router": 2.0 * n * d * e,
        "dispatch": 2.0 * n * e * c * d,
        "up": 2.0 * e * c * d * f,
        "down": 2.0 * e * c * f * d,
        "combine": 2.0 * n * e * c * d,
    }
    out["total"] = sum(out.values())
    out["capacity"] = c
    return out


def embedding_flops_per_token(d_model: int, vocab_size: int) -> float:
    """Logit projection; the embedding lookup itself is a gather."""
    return 2.0 * d_model * vocab_size


def gpt_forward_flops_per_token(cfg: Any, seq_len: int) -> Dict[str, float]:
    """Per-token forward FLOPs breakdown for a GPT-family config.

    ``cfg`` is duck-typed (GPTConfig or anything with the same fields) so
    this module never imports models and stays dependency-free.
    """
    return {
        "attention": attention_flops_per_token(
            cfg.d_model, seq_len, cfg.n_layers),
        "mlp": mlp_flops_per_token(
            cfg.d_model, cfg.d_ff, cfg.n_layers,
            moe_experts=getattr(cfg, "moe_experts", 0),
            moe_k=getattr(cfg, "moe_k", 2)),
        "embedding": embedding_flops_per_token(cfg.d_model, cfg.vocab_size),
    }


def gpt_train_step_flops(cfg: Any, batch_size: int,
                         seq_len: Optional[int] = None) -> StepFlops:
    """Analytic FLOPs for one training step of a GPT-family model.

    MoE configs get the exact capacity-based count (dispatch/combine
    einsums grow with the token count, so only the step level — which
    knows the batch — can be exact; the per-token breakdown is derived
    back from it).
    """
    seq = int(seq_len or cfg.max_seq_len)
    tokens = int(batch_size) * seq
    breakdown = gpt_forward_flops_per_token(cfg, seq)
    moe_experts = getattr(cfg, "moe_experts", 0)
    if moe_experts and moe_experts > 1 and tokens > 0:
        layer = moe_layer_flops(
            tokens, cfg.d_model, cfg.d_ff, moe_experts,
            capacity_factor=getattr(cfg, "moe_capacity_factor", 1.25))
        breakdown["mlp"] = cfg.n_layers * layer["total"] / tokens
    per_token_fwd = sum(breakdown.values())
    per_token = TRAIN_MULT * per_token_fwd
    return StepFlops(
        total=per_token * tokens,
        per_token=per_token,
        tokens=tokens,
        breakdown={k: TRAIN_MULT * v * tokens for k, v in breakdown.items()},
    )


def gpt_prefill_flops(cfg: Any, prompt_len: int) -> Dict[str, float]:
    """Forward FLOPs of one serving prefill over a ``prompt_len`` prompt.

    Same accounting convention as training (full [T, S] score matmul —
    masking saves nothing arithmetically): each of the P prompt tokens
    costs ``attention(s=P) + mlp + embedding``, so the call total is just
    P times the per-token forward breakdown at sequence length P. Keys
    are component totals for the whole call, plus ``"total"``.
    """
    per_tok = gpt_forward_flops_per_token(cfg, int(prompt_len))
    out = {k: v * float(prompt_len) for k, v in per_tok.items()}
    out["total"] = sum(out.values())
    return out


def gpt_decode_flops_per_token(cfg: Any, context_len: int) -> Dict[str, float]:
    """Forward FLOPs of ONE incremental decode step at KV-cache context
    length ``context_len`` — the formula that makes serving MFU honest.

    With the KV cache, the new token pays the full projections
    (``L·8d²``) and MLP (``L·4df``) but its attention mix is linear in
    the *context*, not quadratic in the sequence: scores ``[1, c]`` and
    the value mix cost ``L·4·c·d`` (2cd QKᵀ + 2cd PV per layer). Compare
    :func:`gpt_prefill_flops`, where every prompt token pays ``4·P·d`` —
    the asymmetry is exactly why serving splits prefill from decode.
    """
    c = float(context_len)
    out = {
        "attention": cfg.n_layers * (8.0 * cfg.d_model * cfg.d_model
                                     + 4.0 * c * cfg.d_model),
        "mlp": mlp_flops_per_token(
            cfg.d_model, cfg.d_ff, cfg.n_layers,
            moe_experts=getattr(cfg, "moe_experts", 0),
            moe_k=getattr(cfg, "moe_k", 2)),
        "embedding": embedding_flops_per_token(cfg.d_model, cfg.vocab_size),
    }
    out["total"] = sum(out.values())
    return out


def gpt_generation_flops(cfg: Any, prompt_len: int, new_tokens: int, *,
                         prefill_from: int = 0) -> float:
    """Total forward FLOPs to serve one request: one prefill of
    ``prompt_len`` plus ``new_tokens - 1`` incremental decode steps (the
    first generated token falls out of the prefill logits; decode step j
    runs at context ``prompt_len + j``). Dividing the
    sum of this over all completed requests by wall-clock gives a
    tokens-level MFU.

    ``prefill_from`` accounts for prefix sharing: positions before it
    were aliased from the prefix cache, so only the suffix tokens pay
    prefill FLOPs (each still at full sequence length ``prompt_len`` —
    the same accounting convention as :func:`gpt_prefill_flops`). The
    re-scored last prompt token keeps the suffix count >= 1.
    """
    p, n = int(prompt_len), int(new_tokens)
    skip = min(max(0, int(prefill_from)), p - 1)
    per_tok = gpt_forward_flops_per_token(cfg, p)
    total = sum(per_tok.values()) * float(p - skip)
    for j in range(1, n):
        total += gpt_decode_flops_per_token(cfg, p + j)["total"]
    return total


def gpt_verify_flops(cfg: Any, context_len: int, k: int) -> Dict[str, float]:
    """Forward FLOPs of ONE speculative verify call: the target scores
    ``k + 1`` tokens (last committed token + k drafts) starting at
    context ``context_len``. Each scored token pays the full projections
    + MLP + embedding of a decode step, and its attention mix is linear
    in its OWN context — token i of the call sees ``context_len + i``
    cached positions — so the call total is the sum of k+1 consecutive
    decode-step counts. This is why acceptance rate is the whole game:
    the verify call costs what k+1 sequential decode steps cost, but
    only ``accepted + 1`` of its tokens are emitted.
    """
    out: Dict[str, float] = {}
    for i in range(int(k) + 1):
        step = gpt_decode_flops_per_token(cfg, int(context_len) + i)
        for key, v in step.items():
            out[key] = out.get(key, 0.0) + v
    return out


def gpt_speculative_step_flops(cfg: Any, draft_cfg: Any, context_len: int,
                               k: int) -> Dict[str, float]:
    """Forward FLOPs of one whole speculative iteration for one
    sequence: k single-token draft proposals (each an incremental decode
    step of the draft model at its growing context) plus the target's
    k+1-token verify call. Returns ``{"draft", "verify", "total"}`` —
    the per-emitted-token cost is ``total / (accepted + 1)``.
    """
    c = int(context_len)
    draft = sum(gpt_decode_flops_per_token(draft_cfg, c + i)["total"]
                for i in range(int(k)))
    verify = gpt_verify_flops(cfg, c, k)["total"]
    return {"draft": draft, "verify": verify, "total": draft + verify}


def dense_train_flops_per_token(n_params: int) -> float:
    """The ``6 * N`` approximation for configs we can't decompose."""
    return 6.0 * float(n_params)


def dense_train_step_flops(n_params: int, batch_size: int,
                           seq_len: int) -> StepFlops:
    per_token = dense_train_flops_per_token(n_params)
    tokens = int(batch_size) * int(seq_len)
    return StepFlops(total=per_token * tokens, per_token=per_token,
                     tokens=tokens, breakdown={"dense_6n": per_token * tokens})


def _published(tpu_table: Dict[str, float], cpu_estimate: float,
               platform: Optional[str], device_kind: Optional[str]
               ) -> Tuple[Optional[float], str]:
    """``(value, provenance)`` for the named device, or — for whichever
    of platform and kind the caller left out — for ``jax.devices()[0]``
    (imported here, so the module stays importable without jax)."""
    platform = platform and platform.lower()
    if platform is None or (platform == "tpu" and device_kind is None):
        import jax

        dev = jax.devices()[0]
        platform = platform or dev.platform
        device_kind = device_kind or dev.device_kind
    if platform == "cpu":
        return cpu_estimate, "cpu:est"
    gen = TPU_DEVICE_KINDS.get(device_kind) if platform == "tpu" else None
    if gen is None:
        return None, f"{platform}:unknown-kind:{device_kind}"
    return tpu_table[gen], f"tpu:{gen}"


def peak_flops_estimate(platform: Optional[str] = None,
                        device_kind: Optional[str] = None,
                        ) -> Tuple[Optional[float], str]:
    """Peak bf16 FLOP/s of one chip of the current (or the named) device.

    Returns ``(peak_flops, provenance)``. On TPU the peak is the published
    one for the ``device_kind`` the runtime reports (label ``"tpu:v5e"``);
    an accelerator whose kind is not in the table has **no** peak —
    ``(None, "tpu:unknown-kind:<kind>")`` — and consumers then publish no
    MFU rather than one against a guessed denominator. ``"cpu:est"`` is
    the order-of-magnitude CPU stand-in. MFU consumers carry the label
    next to the number.
    """
    return _published(TPU_PEAK_BF16_FLOPS, CPU_PEAK_EST_FLOPS,
                      platform, device_kind)


# Per-device interconnect bandwidth, bytes/s. TPU ICI numbers are
# published per-link aggregates; the CPU number stands in for "shared
# memory on one host" (a simulated --xla_force_host_platform_device_count
# mesh moves shards through RAM) — like CPU_PEAK_EST_FLOPS it exists to
# keep the comm-vs-compute plumbing exercised in CPU tests, and it
# carries a provenance label.
TPU_ICI_BYTES_PER_S: Dict[str, float] = {
    "v4": 300e9,
    "v5e": 200e9,
    "v5p": 600e9,
    "v6e": 450e9,
}
CPU_INTERCONNECT_EST_BYTES_PER_S = 10e9


def interconnect_bandwidth_estimate(platform: Optional[str] = None,
                                    device_kind: Optional[str] = None,
                                    ) -> Tuple[Optional[float], str]:
    """Per-device interconnect bandwidth (bytes/s) with the same
    ``(value, provenance)`` contract as :func:`peak_flops_estimate` —
    ``None`` for an accelerator kind that is not in the table. The
    analytic comm-vs-compute fraction (telemetry/collectives.py) divides
    collective payload bytes by this."""
    return _published(TPU_ICI_BYTES_PER_S, CPU_INTERCONNECT_EST_BYTES_PER_S,
                      platform, device_kind)


def mfu(flops_per_sec: float, peak_flops: Optional[float],
        n_devices: int = 1) -> Optional[float]:
    """Model FLOPs utilization against ``n_devices`` chips of peak; None
    when the chip has no known peak."""
    if not peak_flops or peak_flops <= 0:
        return None
    return flops_per_sec / (peak_flops * max(1, n_devices))
