"""What the Trinity (``afmoe``) readers share (``layer_metrics/
decode_window_attn_*``, ``decode_full_attn_*``, ``prefill_window_attn_*``,
``prefill_full_attn_*``, ``window_attn_hbm_roofline_pct``,
``full_attn_hbm_roofline_pct``, ``window_attended_rows_pct``,
``afmoe_experts_hbm_roofline_pct``, ``afmoe_pairs_per_expert``; PR 47): the
bytes a decode step has to read, from what the program says of it and this
configuration's own key names (``layer_types``, ``sliding_window``,
``num_key_value_heads``, ``num_dense_layers``, ``num_experts``: the readers
of the other expert families key on ``linear_attn_config`` and
``indexer_types`` and return None here, as these do there). Device time of
a decode step under a scope is ``harness/eva.py:scope_step_ms``, of a
prefill slice ``harness/sala.py:prefill_scope_ms``, each as it is.

The program (``determined_clone_tpu/models/afmoe.py``) names the scopes
``window_attn`` (a sliding layer: the ring's blocks read through the slot's
table from the window's first position to the row's last, scores, online
softmax, the output gate; a slice's passes) and ``full_attn`` (the full
layer: the same over a row's blocks to its length) and ``kv_cache`` (the K
and V rows written) inside ``attn``, ``moe_route`` and ``moe_experts``
inside ``mlp``. A decode step's spans carry ``kv_rows`` (rows cached and
read in the full layer: every row's length) and ``window_rows`` (rows read
in one sliding layer: ``min(length, sliding_window)`` a row), summed over
the step's rows; its ``decode_commit`` span carries ``expert_pairs`` and
``expert_hits`` from the device; a ``serving_prefill`` span carries
``tokens``, ``window_key_rows`` and ``full_key_rows``. Where a trace or a
span has none of this, every function here returns None or nothing, and
nothing raises.
"""
from __future__ import annotations

import bisect
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from benchmarks.harness import device, eva, scopes

STEP_SPAN = scopes.STEP_SPAN["serve"]
COMMIT_SPAN = "decode_commit"
STEP_ARGS = ("kv_rows", "window_rows", "expert_pairs", "expert_hits")


def layers(config: Dict[str, Any]) -> Optional[Tuple[int, int, int]]:
    """(sliding layers, full layers, expert layers) held, or None of a
    configuration of another family."""
    kinds = config.get("layer_types")
    if kinds is None or "sliding_window" not in config:
        return None
    return (list(kinds).count("sliding_attention"),
            list(kinds).count("full_attention"),
            int(config["num_hidden_layers"])
            - int(config["num_dense_layers"]))


def row_bytes(config: Dict[str, Any], itemsize: int = 2) -> int:
    """A position's K row and V row in one layer: the KV heads of
    ``head_dim`` side by side, twice."""
    return 2 * int(config["num_key_value_heads"]) * int(config["head_dim"]) \
        * itemsize


def _steps(ctx: Dict[str, Any]) -> List[Tuple[float, float, Dict[str, Any]]]:
    """(start, seconds, args) of the window's decode steps, in order, each
    with the args of the ``decode_commit`` that followed it
    (``harness/glm.py:_steps``, with this family's args); only the steps
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


def window_step_bytes(a: Dict[str, Any], config: Dict[str, Any]) -> float:
    """Bytes a decode step has to read of the sliding layers' rings:
    ``min(length, sliding_window)`` K and V rows a served row, in every
    sliding layer."""
    return float(a["window_rows"] * layers(config)[0] * row_bytes(config))


def full_step_bytes(a: Dict[str, Any], config: Dict[str, Any]) -> float:
    """Bytes a decode step has to read of the full layers' blocks: every
    cached position's K and V row of every served row."""
    return float(a["kv_rows"] * layers(config)[1] * row_bytes(config))


def experts_step_bytes(a: Dict[str, Any], config: Dict[str, Any],
                       itemsize: int = 2) -> float:
    """Bytes a decode step has to read of the routed experts: the three
    matrices ``hidden_size x moe_intermediate_size`` of every held expert
    that got a pair, and of no other."""
    return float(itemsize * a["expert_hits"] * 3 * int(config["hidden_size"])
                 * int(config["moe_intermediate_size"]))


def attended_rows_pct(ctx: Dict[str, Any]) -> Optional[float]:
    """100 x the cache rows the window's decode steps had to read (a
    window's in every sliding layer, all in every full one) over what a
    uniform cache's read would be (all in every layer)."""
    config = ctx["cell"].config
    steps = window_steps(ctx)
    if not steps or layers(config) is None:
        return None
    sliding, full, _ = layers(config)
    window = sum(a["window_rows"] for a in steps)
    whole = sum(a["kv_rows"] for a in steps)
    if not whole:
        return None
    return 100.0 * (sliding * window + full * whole) \
        / ((sliding + full) * whole)


def pairs_per_expert(ctx: Dict[str, Any]) -> Optional[float]:
    """Token-expert pairs a held expert got in a decode step, averaged over
    the window's steps and over all held experts of all expert layers."""
    config = ctx["cell"].config
    steps = window_steps(ctx)
    if not steps or layers(config) is None:
        return None
    held = layers(config)[2] * int(config["num_experts"])
    return sum(a["expert_pairs"] for a in steps) / len(steps) / held


def hbm_share(ctx: Dict[str, Any], names: Sequence[str],
              needed: Callable[[Dict[str, Any], Dict[str, Any]], float]
              ) -> Optional[float]:
    """100 x the least time the chip's memory could take for ``needed(args
    of a traced step, config)`` bytes a step, over the device time a step
    spends under the scopes ``names``."""
    parsed = scopes.for_cell(ctx) if ctx["kind"] == "serve" else None
    config = ctx["cell"].config
    if parsed is None or layers(config) is None:
        return None
    seconds = eva.scope_step_seconds(parsed, names)
    steps = traced_steps(ctx, parsed)
    import jax

    peak = device.PEAKS.get(jax.devices()[0].device_kind)
    if not seconds or not steps or peak is None:
        return None
    per_step = sum(needed(a, config) for a in steps) / len(steps)
    return 100.0 * per_step / peak["hbm_bytes_per_s"] / seconds
