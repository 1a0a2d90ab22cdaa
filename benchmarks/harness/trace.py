"""From a ``jax.profiler`` trace to numbers: device busy time, time per
operation, collective time, and the idle gaps named by what the host was
doing. Later PRs cannot change this file, so every PR's numbers are computed
the same way; ``benchmarks/tests/test_trace.py`` checks it on a small trace
recorded on the chip.

What a v5e trace looks like (read by hand, PR 23; PERF.md section 3):
planes ``/device:TPU:<n>`` hold the lines ``XLA Modules`` (one event per
executed program), ``XLA Ops`` (one event per operation inside it, which is
what "an operation ran on the device" means here; a ``while`` is one event
and the operations of its body are events inside it, so busy time is a
union and the ranking of operations leaves the containers out) and
``Async XLA Ops``; plane
``/host:CPU`` holds one line per host thread, named after the thread, with
the ``TraceAnnotation`` events. All times are nanoseconds from the start of
the trace, one clock for every plane.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SYNC_MARK = "bench.clock_sync"
# gaps between consecutive operations of one program are launch overhead on
# the device, not the host's doing: they are summed under one name
SHORT_GAP_S = 50e-6
SHORT_GAP_NAME = "between_ops_under_50us"
COLLECTIVE_WORDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")

Event = Tuple[str, float, float]      # name, start (s), duration (s)
Span = Tuple[str, float, float]       # name, start (s), duration (s)


class TraceWindow:
    """A profiler trace of a few seconds of the steady window, with Python
    call tracing off (only device events and ``TraceAnnotation``s)."""

    def __init__(self, directory: str) -> None:
        self.directory = directory
        self.sync_monotonic: Optional[float] = None
        self.started_at: Optional[float] = None
        self.stopped_at: Optional[float] = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.started_at = time.monotonic()
        # one annotation whose start is known on time.monotonic: it places
        # the program's spans (host clock) on the trace's clock
        self.sync_monotonic = time.monotonic()
        with jax.profiler.TraceAnnotation(SYNC_MARK):
            pass

    def stop(self) -> None:
        import jax

        self.stopped_at = time.monotonic()
        jax.profiler.stop_trace()

    @property
    def running(self) -> bool:
        return self.started_at is not None and self.stopped_at is None

    def load(self) -> Dict[str, Dict[str, List[Event]]]:
        (path,) = glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb"))
        return load_xplane(path)


def load_xplane(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """{plane name: {line name: [(event name, start s, duration s)]}}."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, e.start_ns / 1e9, e.duration_ns / 1e9)
                for e in line.events)
    return planes


def merge(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Union of (start, end) intervals as a sorted list of disjoint ones."""
    out: List[Tuple[float, float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


CONTAINER_OPS = ("while", "conditional", "call")


def parse_op(text: str) -> Tuple[str, str, str]:
    """(result name, opcode, short name) of an ``XLA Ops`` event, whose name
    is the operation's whole HLO line:
    ``%fusion.5 = bf16[8,128]{1,0:T(8,128)} fusion(...), kind=kLoop``.
    The short name keeps the opcode, the result's name, its shape without
    layouts and a custom call's target."""
    lhs, sep, rest = text.partition(" = ")
    if not sep:
        return text, "", text[:120]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shape, tail = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        shape, _, tail = rest.partition(" ")
    opcode = tail.partition("(")[0].strip()
    shape = re.sub(r"\{[^}]*\}", "", shape)
    short = f"{opcode} {lhs.lstrip('%')} {shape[:60]}"
    target = re.search(r'custom_call_target="([^"]+)"', tail)
    if target:
        short += f" {target.group(1)}"
    return lhs.lstrip("%"), opcode, short


def is_collective(opcode: str) -> bool:
    return opcode.startswith(COLLECTIVE_WORDS)


class _Cover:
    """Which span covers a time: the innermost (shortest) of the spans that
    started before it, looking back over at most ``LOOK_BACK`` of them."""

    LOOK_BACK = 64

    def __init__(self, spans: Sequence[Span]) -> None:
        self.spans = sorted(spans, key=lambda s: s[1])
        self.starts = [s[1] for s in self.spans]

    def at(self, t: float) -> Optional[str]:
        import bisect

        hi = bisect.bisect_right(self.starts, t)
        best: Optional[Span] = None
        for span in self.spans[max(0, hi - self.LOOK_BACK):hi]:
            if t <= span[1] + span[2] and (best is None
                                           or span[2] < best[2]):
                best = span
        return best[0] if best else None


def reduce_trace(planes: Dict[str, Dict[str, List[Event]]], *,
                 program_spans: Sequence[Span] = (),
                 sync_monotonic: Optional[float] = None,
                 top: int = 10) -> Dict[str, Any]:
    """The numbers the per-layer metrics and the result line read.

    ``program_spans`` are (name, start on time.monotonic, seconds); they and
    the harness's own annotations name the idle gaps. The traced window is
    from the first to the last device operation over all chips.
    """
    device_ops = {name: lines.get(OPS_LINE, [])
                  for name, lines in planes.items()
                  if name.startswith(DEVICE_PLANE)
                  and name[len(DEVICE_PLANE):].isdigit()}
    device_ops = {k: v for k, v in device_ops.items() if v}
    if not device_ops:
        return {"chips": 0, "busy_s": 0.0, "window_s": 0.0}
    t0 = min(e[1] for ops in device_ops.values() for e in ops)
    t1 = max(e[1] + e[2] for ops in device_ops.values() for e in ops)

    busy: List[float] = []
    collective: List[float] = []
    by_op: Dict[str, float] = {}
    for ops in device_ops.values():
        merged = merge((s, s + d) for _, s, d in ops)
        busy.append(sum(e - s for s, e in merged))
        parsed = {n: parse_op(n) for n in {e[0] for e in ops}}
        collective.append(sum(e - s for s, e in merge(
            (s, s + d) for n, s, d in ops if is_collective(parsed[n][1]))))
        for n, _, d in ops:
            _, opcode, short = parsed[n]
            if opcode not in CONTAINER_OPS:  # their bodies' ops are events
                by_op[short] = by_op.get(short, 0.0) + d
    n_chips = len(device_ops)

    # host side: the harness's annotations, and the program's spans moved
    # onto the trace's clock through the sync mark
    host: List[Span] = []
    sync_trace: Optional[float] = None
    for line in planes.get(HOST_PLANE, {}).values():
        for n, s, d in line:
            if n == SYNC_MARK:
                sync_trace = s
            elif n.startswith("bench."):
                host.append((n, s, d))
    if sync_trace is not None and sync_monotonic is not None:
        shift = sync_trace - sync_monotonic
        host.extend((n, s + shift, d) for n, s, d in program_spans)

    first = sorted(device_ops)[0]
    merged = merge((s, s + d) for _, s, d in device_ops[first])
    cover = _Cover(host)
    gaps: Dict[str, float] = {}
    for (_, end), (start, _) in zip(merged, merged[1:]):
        if start - end < SHORT_GAP_S:
            name = SHORT_GAP_NAME
        else:
            name = cover.at((end + start) / 2) or "unattributed"
        gaps[name] = gaps.get(name, 0.0) + (start - end)

    def ranked(d: Dict[str, float]) -> List[List[Any]]:
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:top]

    return {
        "chips": n_chips,
        "window_s": t1 - t0,
        "busy_s": sum(busy) / n_chips,
        "collective_s": sum(collective) / n_chips,
        "device_ops": ranked({k: v / n_chips for k, v in by_op.items()}),
        "idle_gaps": ranked(gaps),
    }
