"""What the paged-attention readers share
(``layer_metrics/decode_paged_attn_device_ms``,
``paged_attn_hbm_roofline_pct``; PR 32): the cache rows the program says
its decode steps attended, and the bytes those rows are. Device time of a
decode step under a scope is ``harness/eva.py:scope_step_seconds``, and the
bytes of a row ``eva.attended_cache_bytes``, as they are.

The program (``determined_clone_tpu/models/gpt.py``) names the scope
``paged_attn`` (inside ``attn``) around the kernel that attends a decode
step's rows through their block tables (``ops/paged_attention.py``), and
gives its ``serving_decode_step`` spans the args ``kv_rows`` (the rows the
step's queries attend: the live rows' real context lengths, summed) and
``table_rows`` (batch bucket x table width x block: what a read of whole
tables moves). Where a trace or a span has none of this, as the parent of
PR 32 has not, every function here returns None and nothing raises.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmarks.harness import device, eva, scopes

STEP_SPAN = scopes.STEP_SPAN["serve"]
ROW_ARGS = ("kv_rows", "table_rows")
SCOPE = "paged_attn"


def traced_steps(ctx: Dict[str, Any], parsed: scopes.Parsed
                 ) -> List[Dict[str, Any]]:
    """The args of the decode steps that the trace holds: the run of the
    tracer's records whose durations lie closest to the trace's
    annotations (which carry no args), as ``eva.traced_steps`` matches
    them. All the window's steps where the trace has more steps than
    records."""
    traced = [e - s for s, e in sorted(parsed.spans(STEP_SPAN))]
    recorded = sorted((end - d, d, a) for end, d, a
                      in scopes.span_seconds(ctx, STEP_SPAN))
    n, m = len(traced), len(recorded)
    if 0 < n <= m:
        first = min(range(m - n + 1), key=lambda k: sum(
            abs(recorded[k + i][1] - traced[i]) for i in range(n)))
        recorded = recorded[first:first + n]
    return [a for _, _, a in recorded if all(k in a for k in ROW_ARGS)]


def attended_bytes(kv_rows: float, config: Dict[str, Any]) -> float:
    """Bytes a decode step has to read to attend ``kv_rows`` cache rows: a
    K and a V row of ``n_embd`` bfloat16 values (the useful columns, not
    the pool row's padding) in each of ``n_layer`` layers."""
    return eva.attended_cache_bytes(kv_rows, int(config["n_embd"]),
                                    int(config["n_layer"]))


def hbm_share(ctx: Dict[str, Any]) -> Optional[float]:
    """100 x the least time the chip's memory could take to read the rows
    a traced decode step attended, over the device time a step spends
    under ``paged_attn``."""
    parsed = scopes.for_cell(ctx) if ctx["kind"] == "serve" else None
    if parsed is None:
        return None
    seconds = eva.scope_step_seconds(parsed, (SCOPE,))
    steps = traced_steps(ctx, parsed)
    config = ctx["cell"].config
    if not seconds or not steps or "n_embd" not in config:
        return None
    import jax

    peak = device.PEAKS.get(jax.devices()[0].device_kind)
    if peak is None:  # no entry in the peak table: nothing to hold it to
        return None
    needed = sum(attended_bytes(a["kv_rows"], config) for a in steps) \
        / len(steps)
    return 100.0 * needed / peak["hbm_bytes_per_s"] / seconds
