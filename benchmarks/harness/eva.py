"""What the EVA readers share (``layer_metrics/decode_eva_*``,
``eva_summary_rows_pct``, ``eva_attn_hbm_roofline_pct``; PR 27): device
time of a decode step under a scope that ``harness/scopes.py``'s fixed
vocabulary does not hold, the cache rows the program says its decode steps
attended, and the bytes those rows are.

The program (``determined_clone_tpu/models/evabyte.py``) names the scopes
``eva_summarize`` (pooling a completed chunk's summary and writing it) and
``eva_attn`` (the joint softmax and the weighted sums) inside ``attn``, and
gives its ``serving_decode_step`` spans the args ``window_rows`` and
``summary_rows``: the rows the step's queries attend at the sequences' real
lengths, summed over its rows. Where a trace or a span has none of this,
every function here returns None and nothing raises.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

from benchmarks.harness import scopes

STEP_SPAN = scopes.STEP_SPAN["serve"]
ROW_ARGS = ("window_rows", "summary_rows")


def on_path(path: str, names: Sequence[str]) -> bool:
    """Whether one of ``names`` is a scope on an operation's ``tf_op``
    path (``<op_name>:<op type>``), under any transform's wrapper."""
    for part in (path.rpartition(":")[0] or path).split("/"):
        while True:
            inner = scopes._WRAPPER.match(part)
            if inner is None:
                break
            part = inner.group(1)
        if part in names:
            return True
    return False


def scope_step_seconds(parsed: scopes.Parsed, names: Sequence[str]
                       ) -> Optional[float]:
    """Device seconds per decode step of the operations with one of
    ``names`` on their scope path: self times (``scopes._self_times``)
    inside the programs that ran within a ``serving_decode_step``
    annotation, over the executions of the step program, averaged over the
    chips — as ``scopes.reduce_scopes`` counts its buckets. None where the
    trace has no such step or no such scope."""
    spans = sorted(parsed.spans(STEP_SPAN))
    if not spans or not parsed.ops:
        return None
    total, chips, found = 0.0, 0, False
    for chip, ops in parsed.ops.items():
        stepped = sorted((s, s + d, n) for n, s, d
                         in parsed.modules.get(chip, ())
                         if scopes._inside(spans, s + d / 2))
        if not stepped:
            continue
        by_program: Dict[str, List[float]] = {}
        for s, e, n in stepped:
            by_program.setdefault(n, []).append(e - s)
        steps = len(max(by_program.values(), key=sum))
        windows = [(s, e) for s, e, _ in stepped]
        meta = parsed.op_meta[chip]
        wanted: Dict[int, bool] = {}
        seconds = 0.0
        for key, self_s in scopes._self_times(
                [o for o in ops if scopes._inside(windows, o[0])]):
            if key not in wanted:
                wanted[key] = on_path(meta.get(key, ("", ""))[1], names)
            if wanted[key]:
                seconds += self_s
                found = True
        total += seconds / steps
        chips += 1
    return total / chips if chips and found else None


def scope_step_ms(ctx: Dict[str, Any], *names: str) -> Optional[float]:
    parsed = scopes.for_cell(ctx) if ctx["kind"] == "serve" else None
    seconds = scope_step_seconds(parsed, names) if parsed is not None \
        else None
    return None if seconds is None else 1e3 * seconds


def window_steps(ctx: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The args of the window's decode steps that carry row counts."""
    return [a for _, _, a in scopes.span_seconds(ctx, STEP_SPAN)
            if all(k in a for k in ROW_ARGS)]


def traced_steps(ctx: Dict[str, Any], parsed: scopes.Parsed
                 ) -> List[Dict[str, Any]]:
    """The args of the decode steps that the trace holds. The tracer's
    records (``ctx["spans"]``, host clock, the whole window) and the
    trace's annotations (no args) are of the same spans, so the run of
    records whose durations lie closest to the annotations' is theirs.
    All the window's steps where the trace has more steps than records."""
    traced = [e - s for s, e in sorted(parsed.spans(STEP_SPAN))]
    recorded = sorted((end - d, d, a) for end, d, a
                      in scopes.span_seconds(ctx, STEP_SPAN))
    n, m = len(traced), len(recorded)
    first = 0
    if 0 < n <= m:
        first = min(range(m - n + 1), key=lambda k: sum(
            abs(recorded[k + i][1] - traced[i]) for i in range(n)))
        recorded = recorded[first:first + n]
    return [a for _, _, a in recorded if all(k in a for k in ROW_ARGS)]


def attended_cache_bytes(rows: float, row_width: int, n_layers: int,
                         itemsize: int = 2) -> float:
    """Bytes of cache a decode step has to read to attend ``rows`` rows
    (window and summary rows alike): a K row and a V row of ``row_width``
    values in every layer."""
    return rows * 2 * row_width * itemsize * n_layers
