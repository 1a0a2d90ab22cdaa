"""What the GLM-5.2 readers share (``layer_metrics/decode_dsa_index_*``,
``decode_mla_attn_*``, ``decode_moe_*``, ``prefill_dsa_index_*``,
``prefill_mla_attn_*``, ``dsa_selected_rows_pct``, ``moe_pairs_per_expert``,
``mla_attn_hbm_roofline_pct``, ``moe_experts_hbm_roofline_pct``; PR 33): what
the program says its decode steps read, and the bytes that is. Device time
of a decode step under a scope is ``harness/eva.py:scope_step_ms``, of a
prefill slice ``harness/sala.py:prefill_scope_ms``, as they are.

The program (``determined_clone_tpu/models/glm_moe_dsa.py``) names the
scopes ``dsa_index`` (the indexer's projections, scores and top-k),
``mla_attn`` (gather of the chosen latents, absorbed products, softmax; the
slice form too) and ``kv_cache`` inside ``attn``, ``moe_route`` (router,
top-k, sort) and ``moe_experts`` (the products a tile of pairs at a time,
and the combine) inside ``mlp``. A decode step's spans carry ``kv_rows``
(positions cached and scored by an indexer) and ``selected_rows`` (positions
attended, ``min(length, index_topk)`` a row), summed over the step's rows
from their lengths; its ``decode_commit`` span, which opens after the
read-back, carries besides what only the device knew: ``expert_pairs``
(token-expert pairs that fell to held experts, over the sparse layers) and
``expert_hits`` (held experts, of sparse layers x held, that got a pair).
Where a trace or a span has none of this, every function here returns None
or nothing, and nothing raises.
"""
from __future__ import annotations

import bisect
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from benchmarks.harness import device, eva, scopes

STEP_SPAN = scopes.STEP_SPAN["serve"]
COMMIT_SPAN = "decode_commit"
STEP_ARGS = ("kv_rows", "selected_rows", "expert_pairs", "expert_hits")


def _steps(ctx: Dict[str, Any]) -> List[Tuple[float, float, Dict[str, Any]]]:
    """(start, seconds, args) of the window's decode steps, in order, each
    with the args of the ``decode_commit`` that followed it; only the steps
    that carry every one of ``STEP_ARGS``."""
    commits = [(end - d, a) for end, d, a
               in scopes.span_seconds(ctx, COMMIT_SPAN)]
    starts = [s for s, _ in commits]
    out = []
    for end, d, a in scopes.span_seconds(ctx, STEP_SPAN):
        i = bisect.bisect_left(starts, end - 1e-4)
        merged = {**a, **(commits[i][1] if i < len(commits) else {})}
        if all(k in merged for k in STEP_ARGS):
            out.append((end - d, d, merged))
    return out


def window_steps(ctx: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The args of the window's decode steps."""
    return [a for _, _, a in _steps(ctx)] if ctx["kind"] == "serve" else []


def traced_steps(ctx: Dict[str, Any], parsed: scopes.Parsed
                 ) -> List[Dict[str, Any]]:
    """The args of the decode steps that the trace holds: the run of the
    tracer's records whose durations lie closest to the trace's
    annotations (which carry no args), as ``eva.traced_steps`` matches
    them. All the window's steps where the trace has more steps than
    records."""
    traced = [e - s for s, e in sorted(parsed.spans(STEP_SPAN))]
    recorded = _steps(ctx)
    n, m = len(traced), len(recorded)
    if 0 < n <= m:
        first = min(range(m - n + 1), key=lambda k: sum(
            abs(recorded[k + i][1] - traced[i]) for i in range(n)))
        recorded = recorded[first:first + n]
    return [a for _, _, a in recorded]


def mla_step_bytes(a: Dict[str, Any], config: Dict[str, Any],
                   itemsize: int = 2) -> float:
    """Bytes a decode step has to read of the cache: one indexer key of
    ``index_head_dim`` values for every cached position in every ``full``
    layer, and one latent of ``kv_lora_rank + qk_rope_head_dim`` values (the
    useful columns, not the row's padding) for every attended position in
    every layer."""
    full = list(config["indexer_types"]).count("full")
    latent = int(config["kv_lora_rank"]) + int(config["qk_rope_head_dim"])
    return itemsize * (
        a["kv_rows"] * int(config["index_head_dim"]) * full
        + a["selected_rows"] * latent * len(config["indexer_types"]))


def experts_step_bytes(a: Dict[str, Any], config: Dict[str, Any],
                       itemsize: int = 2) -> float:
    """Bytes a decode step has to read of the routed experts: the three
    matrices ``hidden_size x moe_intermediate_size`` of every held expert
    that got a pair, and of no other."""
    return itemsize * a["expert_hits"] * 3 * int(config["hidden_size"]) \
        * int(config["moe_intermediate_size"])


def pairs_per_expert(ctx: Dict[str, Any]) -> Optional[float]:
    """Token-expert pairs a held expert got in a decode step, averaged over
    the window's steps and over all held experts of all sparse layers."""
    steps = window_steps(ctx)
    config = ctx["cell"].config
    if not steps or "mlp_layer_types" not in config:
        return None
    held = list(config["mlp_layer_types"]).count("sparse") \
        * int(config["n_routed_experts"])
    return sum(a["expert_pairs"] for a in steps) / len(steps) / held


def hbm_share(ctx: Dict[str, Any], names: Sequence[str],
              needed: Callable[[Dict[str, Any], Dict[str, Any]], float]
              ) -> Optional[float]:
    """100 x the least time the chip's memory could take for ``needed(args
    of a traced step, config)`` bytes a step, over the device time a step
    spends under the scopes ``names``."""
    parsed = scopes.for_cell(ctx) if ctx["kind"] == "serve" else None
    if parsed is None:
        return None
    seconds = eva.scope_step_seconds(parsed, names)
    steps = traced_steps(ctx, parsed)
    config = ctx["cell"].config
    if not seconds or not steps or "indexer_types" not in config:
        return None
    import jax

    peak = device.PEAKS.get(jax.devices()[0].device_kind)
    if peak is None:  # no entry in the peak table: nothing to hold it to
        return None
    per_step = sum(needed(a, config) for a in steps) / len(steps)
    return 100.0 * per_step / peak["hbm_bytes_per_s"] / seconds
