"""What the MiniCPM-SALA readers share (``layer_metrics/decode_sparse_*``,
``decode_lightning_device_ms``, ``prefill_lightning_device_ms``,
``sparse_selected_rows_pct``, ``sparse_attn_hbm_roofline_pct``,
``lightning_state_hbm_roofline_pct``; PR 31): device time of a prefill
slice under a scope, the row counts the program says its decode steps read,
and the bytes those are. Device time of a decode step under a scope is
``harness/eva.py:scope_step_ms``, as it is.

The program (``determined_clone_tpu/models/minicpm_sala.py``) names the
scopes ``sparse_select`` (compressed keys, scores, choice), ``sparse_attn``
(gather of the chosen blocks, softmax) and ``lightning`` (state read, decay,
update, read-out; the slice form too) inside ``attn``, and gives its
``serving_decode_step`` spans the args ``kv_rows`` (rows cached in a sparse
layer), ``selected_rows`` (rows of the blocks the step's queries attend) and
``state_slots`` (states read and written), each summed over the step's rows
at the sequences' real lengths. Where a trace or a span has none of this,
every function here returns None and nothing raises.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

from benchmarks.harness import eva, scopes

STEP_SPAN = scopes.STEP_SPAN["serve"]
PREFILL_SPAN = "serving_prefill"
ROW_ARGS = ("kv_rows", "selected_rows", "state_slots")


def scope_span_seconds(parsed: scopes.Parsed, span: str,
                       names: Sequence[str]) -> Optional[float]:
    """Device seconds per span of that name of the operations with one of
    ``names`` on their scope path: self times inside the programs that ran
    within such a span, over the spans that hold a program, averaged over
    the chips. (A prefill slice is one program a span, of whichever
    bucket.) None where the trace has no such span or no such scope."""
    spans = sorted(parsed.spans(span))
    if not spans or not parsed.ops:
        return None
    total, chips, found = 0.0, 0, False
    for chip, ops in parsed.ops.items():
        windows = sorted((s, s + d) for _, s, d
                         in parsed.modules.get(chip, ())
                         if scopes._inside(spans, s + d / 2))
        held = sum(1 for lo, hi in spans
                   if any(lo <= (s + e) / 2 <= hi for s, e in windows))
        if not held:
            continue
        meta = parsed.op_meta[chip]
        wanted: Dict[int, bool] = {}
        seconds = 0.0
        for key, self_s in scopes._self_times(
                [o for o in ops if scopes._inside(windows, o[0])]):
            if key not in wanted:
                wanted[key] = eva.on_path(meta.get(key, ("", ""))[1], names)
            if wanted[key]:
                seconds += self_s
                found = True
        total += seconds / held
        chips += 1
    return total / chips if chips and found else None


def prefill_scope_ms(ctx: Dict[str, Any], *names: str) -> Optional[float]:
    parsed = scopes.for_cell(ctx) if ctx["kind"] == "serve" else None
    seconds = scope_span_seconds(parsed, PREFILL_SPAN, names) \
        if parsed is not None else None
    return None if seconds is None else 1e3 * seconds


def _counted(steps: Sequence[Dict[str, Any]]) -> List[Dict[str, Any]]:
    return [a for a in steps if all(k in a for k in ROW_ARGS)]


def window_steps(ctx: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The args of the window's decode steps that carry the row counts."""
    return _counted([a for _, _, a in scopes.span_seconds(ctx, STEP_SPAN)])


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
    return _counted([a for _, _, a in recorded])


def _sparse_layers(config: Dict[str, Any]) -> int:
    return list(config["mixer_types"]).count("minicpm4")


def sparse_step_bytes(kv_rows: float, selected_rows: float,
                      config: Dict[str, Any], itemsize: int = 2) -> float:
    """Bytes a decode step has to read in the sparse layers: one compressed
    key per ``kernel_stride`` cached positions to score, and the K and the
    V row of every selected position, each ``num_key_value_heads *
    head_dim`` values, in every sparse layer."""
    width = int(config["num_key_value_heads"]) * int(config["head_dim"])
    stride = int(config["sparse_config"]["kernel_stride"])
    return (kv_rows / stride + 2 * selected_rows) * width * itemsize \
        * _sparse_layers(config)


def state_step_bytes(state_slots: float, config: Dict[str, Any],
                     itemsize: int = 4) -> float:
    """Bytes a decode step has to move in the lightning layers: every
    row's state ``[lightning_nh, d, d]`` float32 read and written, in every
    lightning layer."""
    d = int(config["lightning_head_dim"])
    layers = list(config["mixer_types"]).count("lightning-attn")
    return 2 * state_slots * int(config["lightning_nh"]) * d * d \
        * itemsize * layers


def hbm_share(ctx: Dict[str, Any], names: Sequence[str], needed) -> Optional[float]:
    """100 x the least time the chip's memory could take for ``needed(args
    of a traced step)`` bytes a step, over the device time a step spends
    under the scopes ``names``."""
    parsed = scopes.for_cell(ctx) if ctx["kind"] == "serve" else None
    if parsed is None:
        return None
    seconds = eva.scope_step_seconds(parsed, names)
    steps = traced_steps(ctx, parsed)
    config = ctx["cell"].config
    if not seconds or not steps or "mixer_types" not in config:
        return None
    import jax

    from benchmarks.harness import device

    peak = device.PEAKS.get(jax.devices()[0].device_kind)
    if peak is None:  # no entry in the peak table: nothing to hold it to
        return None
    per_step = sum(needed(a, config) for a in steps) / len(steps)
    return 100.0 * per_step / peak["hbm_bytes_per_s"] / seconds
