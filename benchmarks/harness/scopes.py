"""From a ``jax.profiler`` trace to device time per named scope of the step
program, and to the host's phases on the trace's own clock.

``trace.reduce_trace`` (the accepted reducer, not edited) ranks operations
under the compiler's names and places the program's spans through one sync
mark. This module reads what the program itself put into the trace since
PR 25 (PERF.md section 3):

- every operation's scope path (``jax.named_scope`` in ``models/gpt.py`` and
  ``training/train_step.py``) from the ``tf_op`` statistic of its event
  metadata, e.g. ``jit(step_fn)/transpose(jvp())/while/body/closed_call/
  checkpoint/rematted_computation/attn/flash_fwd/...``. The outermost name
  of the vocabulary on the path is the operation's bucket, so the buckets
  partition the step; ``kv_cache``, the ``flash_fwd`` kernel and the
  compiler's copies of the KV pool are reported beside them as parts;
- the program's spans as ``TraceAnnotation`` events of the ``/host:CPU``
  plane (``telemetry/spans.py``), on the clock the device events are on.

``jax.profiler.ProfileData`` does not expose event metadata statistics, so
the ``.xplane.pb`` is parsed here with ``google.protobuf`` against the few
messages of ``xplane.proto`` (tsl/profiler/protobuf), declared below. The
file is parsed once per process and path. Where the trace holds no such
annotation or scope (a parent commit, a CPU trace), every reader returns
None and nothing raises.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmarks.harness import trace
from benchmarks.harness.spec import BENCH_DIR

BUCKETS = ("embed", "attn", "kv_cache", "mlp", "logits", "optimizer")
UNSCOPED = "unscoped"
KERNEL = "flash_fwd"
# transforms wrap the scope they differentiate or transpose:
# transpose(jvp(attn)) is the backward pass of attn and counts as attn
_WRAPPER = re.compile(r"^(?:jvp|transpose|vmap|remat|checkpoint|"
                      r"custom_jvp|custom_vjp)\((.*)\)$")
# the compiler's copies and slices of the KV pool carry no scope
_COPY_OPCODES = ("copy", "copy-start", "copy-done", "dynamic-slice",
                 "dynamic-update-slice")
_COPY_WORDS = ("copy", "dynamic-slice", "dynamic-update-slice")
MODULES_LINE = "XLA Modules"
STEP_SPAN = {"train": "train_dispatch", "serve": "serving_decode_step"}

Interval = Tuple[float, float]


# ---------------------------------------------------------------------------
# the file
# ---------------------------------------------------------------------------

_XSPACE: Any = None


def _xspace_class() -> Any:
    """The ``XSpace`` message class, from a descriptor built here (field
    numbers of tsl/profiler/protobuf/xplane.proto; maps as their repeated
    key/value entries, which is what they are on the wire)."""
    global _XSPACE
    if _XSPACE is not None:
        return _XSPACE
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")

    def message(name: str, *fields: Tuple[str, int, int, str, bool]) -> None:
        m = fd.message_type.add(name=name)
        for fname, number, ftype, type_name, repeated in fields:
            f = m.field.add(
                name=fname, number=number, type=ftype,
                label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)
            if type_name:
                f.type_name = ".bench_xplane." + type_name

    i64, u64, s, b, d, msg = (F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING,
                              F.TYPE_BYTES, F.TYPE_DOUBLE, F.TYPE_MESSAGE)
    message("XStat", ("metadata_id", 1, i64, "", False),
            ("double_value", 2, d, "", False),
            ("uint64_value", 3, u64, "", False),
            ("int64_value", 4, i64, "", False),
            ("str_value", 5, s, "", False),
            ("bytes_value", 6, b, "", False),
            ("ref_value", 7, u64, "", False))
    message("XEvent", ("metadata_id", 1, i64, "", False),
            ("offset_ps", 2, i64, "", False),
            ("duration_ps", 3, i64, "", False),
            ("stats", 4, msg, "XStat", True),
            ("num_occurrences", 5, i64, "", False))
    message("XLine", ("id", 1, i64, "", False), ("name", 2, s, "", False),
            ("timestamp_ns", 3, i64, "", False),
            ("events", 4, msg, "XEvent", True),
            ("duration_ps", 9, i64, "", False),
            ("display_id", 10, i64, "", False),
            ("display_name", 11, s, "", False))
    message("XEventMetadata", ("id", 1, i64, "", False),
            ("name", 2, s, "", False), ("metadata", 3, b, "", False),
            ("display_name", 4, s, "", False),
            ("stats", 5, msg, "XStat", True),
            ("child_id", 6, i64, "", True))
    message("XStatMetadata", ("id", 1, i64, "", False),
            ("name", 2, s, "", False), ("description", 3, s, "", False))
    message("EventMetadataEntry", ("key", 1, i64, "", False),
            ("value", 2, msg, "XEventMetadata", False))
    message("StatMetadataEntry", ("key", 1, i64, "", False),
            ("value", 2, msg, "XStatMetadata", False))
    message("XPlane", ("id", 1, i64, "", False), ("name", 2, s, "", False),
            ("lines", 3, msg, "XLine", True),
            ("event_metadata", 4, msg, "EventMetadataEntry", True),
            ("stat_metadata", 5, msg, "StatMetadataEntry", True),
            ("stats", 6, msg, "XStat", True))
    message("XSpace", ("planes", 1, msg, "XPlane", True))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    _XSPACE = message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))
    return _XSPACE


class Parsed:
    """What the readers need of one ``.xplane.pb``: per chip the executed
    programs and the operations (with the metadata each refers to), and
    the host's annotations. Times are seconds from the start of the trace,
    as ``trace.load_xplane`` gives them."""

    def __init__(self, path: str) -> None:
        with open(path, "rb") as f:
            space = _xspace_class().FromString(f.read())
        # chip -> [(program name, start, duration)]
        self.modules: Dict[str, List[trace.Event]] = {}
        # chip -> [(start, end, metadata id)], sorted by start, longest first
        self.ops: Dict[str, List[Tuple[float, float, int]]] = {}
        # chip -> {metadata id: (the op's HLO line, its scope path)}
        self.op_meta: Dict[str, Dict[int, Tuple[str, str]]] = {}
        self.host: List[trace.Event] = []
        # step span -> reduce_scopes(...) of this trace (``reduced``)
        self.reductions: Dict[str, Optional[Dict[str, Any]]] = {}
        for plane in space.planes:
            names = {e.key: e.value.name for e in plane.event_metadata}
            if plane.name == trace.HOST_PLANE:
                for line in plane.lines:
                    self.host.extend(self._events(line, names))
            elif (plane.name.startswith(trace.DEVICE_PLANE)
                  and plane.name[len(trace.DEVICE_PLANE):].isdigit()):
                self._device_plane(plane, names)
        self.host.sort(key=lambda e: e[1])

    @staticmethod
    def _events(line: Any, names: Dict[int, str]) -> List[trace.Event]:
        t0 = line.timestamp_ns * 1e-9
        return [(names.get(e.metadata_id, ""), t0 + e.offset_ps * 1e-12,
                 e.duration_ps * 1e-12) for e in line.events]

    def _device_plane(self, plane: Any, names: Dict[int, str]) -> None:
        tf_op = {e.key for e in plane.stat_metadata
                 if e.value.name == "tf_op"}
        strings = {e.key: e.value.name for e in plane.stat_metadata}
        for line in plane.lines:
            if line.name == MODULES_LINE:
                self.modules[plane.name] = self._events(line, names)
            elif line.name == trace.OPS_LINE and line.events:
                t0 = line.timestamp_ns * 1e-9
                ops = [(t0 + e.offset_ps * 1e-12,
                        t0 + (e.offset_ps + e.duration_ps) * 1e-12,
                        e.metadata_id) for e in line.events]
                ops.sort(key=lambda o: (o[0], -o[1]))
                self.ops[plane.name] = ops
        meta: Dict[int, Tuple[str, str]] = {}
        for entry in plane.event_metadata:
            path = ""
            for stat in entry.value.stats:
                if stat.metadata_id in tf_op:
                    path = stat.str_value or strings.get(stat.ref_value, "")
            meta[entry.key] = (entry.value.name, path)
        self.op_meta[plane.name] = meta

    def spans(self, name: str) -> List[Interval]:
        """The (start, end) of the host's annotations of that name."""
        return [(s, s + d) for n, s, d in self.host if n == name]


_PARSED: Dict[Tuple[str, float], Parsed] = {}


def load(path: str) -> Parsed:
    """Parse once per file (and per change of it)."""
    key = (os.path.abspath(path), os.path.getmtime(path))
    if key not in _PARSED:
        _PARSED.clear()  # a process reads one trace at a time
        _PARSED[key] = Parsed(path)
    return _PARSED[key]


def trace_path(cell_name: str) -> Optional[str]:
    """The trace that ``harness/train.py`` / ``serve.py`` wrote for the
    cell, where there is exactly one."""
    found = glob.glob(os.path.join(BENCH_DIR, ".trace", cell_name, "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return found[0] if len(found) == 1 else None


# ---------------------------------------------------------------------------
# scopes
# ---------------------------------------------------------------------------

def scope_names(path: str) -> List[str]:
    """The vocabulary's names on an operation's scope path, outermost
    first. ``tf_op`` is ``<op_name>:<op type>``."""
    found = []
    for part in (path.rpartition(":")[0] or path).split("/"):
        while True:
            inner = _WRAPPER.match(part)
            if inner is None:
                break
            part = inner.group(1)
        if part in BUCKETS or part == KERNEL:
            found.append(part)
    return found


def pool_shapes(config: Dict[str, Any]) -> List[str]:
    """The KV pool's dimensions and those of one layer's slice of it (as
    the scan carries it, as a block holds it, and flattened over blocks as
    ``_block_paged`` scatters into it), as they stand in an HLO shape
    (``[24,2048,16,16,64]``), from the cell's configuration file."""
    s = config.get("serving")
    if not s:
        return []
    block = int(s["kv_block_size"])
    blocks = int(s["kv_blocks"]) or int(s["max_batch"]) * max(
        1, -(-int(config["n_positions"]) // block))
    heads = int(config["n_head"])
    head = f"{heads},{int(config['n_embd']) // heads}]"
    tail = f"{blocks},{block},{head}"
    return [f"[{int(config['n_layer'])},{tail}", f"[1,{tail}", f"[{tail}",
            f"[{blocks * block},{head}"]


def _is_pool_copy(opcode: str, name: str, short: str,
                  shapes: Sequence[str]) -> bool:
    if not (opcode in _COPY_OPCODES
            or (opcode == "fusion" and any(w in name for w in _COPY_WORDS))):
        return False
    return any(shape in short for shape in shapes)


def _self_times(ops: Sequence[Tuple[float, float, int]]
                ) -> List[Tuple[int, float]]:
    """(metadata id, seconds of the event not covered by events nested in
    it). A ``while`` is one event with its body's operations inside it, so
    the self times add up to the union of the intervals. ``ops`` are sorted
    by start, the longer first."""
    out: List[Tuple[int, float]] = []
    stack: List[List[Any]] = []  # [end, metadata id, seconds of children]

    def close() -> None:
        end, key, inner, start = stack.pop()
        out.append((key, max(0.0, end - start - inner)))

    for start, end, key in ops:
        while stack and start >= stack[-1][0]:
            close()
        if stack:
            stack[-1][2] += min(end, stack[-1][0]) - start
        stack.append([end, key, 0.0, start])
    while stack:
        close()
    return out


def _inside(spans: Sequence[Interval], t: float) -> bool:
    i = bisect.bisect_right(spans, (t, float("inf"))) - 1
    return i >= 0 and spans[i][0] <= t <= spans[i][1]


def reduce_scopes(parsed: Parsed, step_span: str,
                  shapes: Sequence[str] = (), top: int = 12
                  ) -> Optional[Dict[str, Any]]:
    """Device seconds per bucket per executed step program, averaged over
    the chips. A step is every program on the ``XLA Modules`` line that
    runs inside a ``step_span`` annotation of the host; the step program is
    the one among them with the most device time, and ``steps`` counts its
    executions. None where the trace has no such annotation or no device
    plane."""
    spans = sorted(parsed.spans(step_span))
    if not spans or not parsed.ops:
        return None
    buckets = {name: 0.0 for name in BUCKETS + (UNSCOPED,)}
    parts = {KERNEL: 0.0, "kv_cache": 0.0, "pool_copy": 0.0}
    unscoped_ops: Dict[str, float] = {}  # the pool's copies kept apart
    pool_ops: Dict[str, float] = {}
    busy = step_module = steps_total = 0.0
    chips = 0
    for chip, ops in parsed.ops.items():
        stepped = sorted((s, s + d, n) for n, s, d
                         in parsed.modules.get(chip, ())
                         if _inside(spans, s + d / 2))
        if not stepped:
            continue
        by_program: Dict[str, List[float]] = {}
        for s, e, n in stepped:
            by_program.setdefault(n, []).append(e - s)
        main = max(by_program.values(), key=sum)
        steps = len(main)
        windows = [(s, e) for s, e, _ in stepped]
        meta = parsed.op_meta[chip]
        # metadata id -> (short name, bucket, the parts it also counts in)
        placed: Dict[int, Tuple[str, str, List[str]]] = {}
        chips += 1
        steps_total += steps
        step_module += sum(main) / steps
        for key, seconds in _self_times(
                [o for o in ops if _inside(windows, o[0])]):
            if key not in placed:
                line, path = meta.get(key, ("", ""))
                name, opcode, short = trace.parse_op(line)
                names = scope_names(path)
                bucket = next((n for n in names if n in BUCKETS), UNSCOPED)
                also = [KERNEL] if name.startswith(KERNEL) else []
                if "kv_cache" in names:
                    also.append("kv_cache")
                if bucket == UNSCOPED and _is_pool_copy(opcode, name, short,
                                                        shapes):
                    also.append("pool_copy")
                placed[key] = (short, bucket, also)
            short, bucket, also = placed[key]
            per_step = seconds / steps
            buckets[bucket] += per_step
            busy += per_step
            for part in also:
                parts[part] += per_step
            if bucket == UNSCOPED:
                ranked = pool_ops if "pool_copy" in also else unscoped_ops
                ranked[short] = ranked.get(short, 0.0) + per_step
    if not chips:
        return None
    def largest(ops: Dict[str, float]) -> List[List[Any]]:
        return [[k, v / chips] for k, v
                in sorted(ops.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "steps": steps_total / chips, "chips": chips,
        "busy_s": busy / chips, "step_program_s": step_module / chips,
        "buckets": {k: v / chips for k, v in buckets.items()},
        "parts": {k: v / chips for k, v in parts.items()},
        "unscoped_ops": largest(unscoped_ops),
        "pool_copy_ops": largest(pool_ops),
    }


# ---------------------------------------------------------------------------
# the host's phases on the trace's clock
# ---------------------------------------------------------------------------

def decode_host_idle(parsed: Parsed) -> Optional[Dict[str, float]]:
    """Seconds in which the device ran nothing inside the
    ``engine_iteration``s that hold a decode step, and the number of those
    steps; averaged over the chips."""
    steps = sorted(parsed.spans("serving_decode_step"))
    iterations = []
    for s, e in parsed.spans("engine_iteration"):
        i = bisect.bisect_left(steps, (s, s))  # the first step from s on
        if i < len(steps) and steps[i][1] <= e:
            iterations.append((s, e))
    if not iterations or not parsed.ops:
        return None
    idle = 0.0
    for ops in parsed.ops.values():
        busy = trace.merge((s, e) for s, e, _ in ops)
        starts = [s for s, _ in busy]
        for s, e in iterations:
            covered = 0.0
            i = max(0, bisect.bisect_right(starts, s) - 1)
            while i < len(busy) and busy[i][0] < e:
                covered += max(0.0, min(e, busy[i][1]) - max(s, busy[i][0]))
                i += 1
            idle += (e - s) - covered
    return {"idle_s": idle / len(parsed.ops), "steps": float(len(iterations))}


# ---------------------------------------------------------------------------
# what the layer metrics call
# ---------------------------------------------------------------------------

def for_cell(ctx: Dict[str, Any]) -> Optional[Parsed]:
    """The parsed trace of the run that ``ctx`` describes, or None."""
    path = trace_path(ctx["cell"].name)
    if path is None:
        return None
    try:
        return load(path)
    except Exception as e:  # noqa: BLE001 - a trace that cannot be read
        print(f"# scopes: cannot read {path}: {e!r}", file=sys.stderr)
        return None


def reduced(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """``reduce_scopes`` of the cell's trace, computed once per trace."""
    parsed = for_cell(ctx)
    if parsed is None:
        return None
    span = STEP_SPAN[ctx["kind"]]
    if span not in parsed.reductions:
        try:
            parsed.reductions[span] = reduce_scopes(
                parsed, span, pool_shapes(ctx["cell"].config))
        except Exception as e:  # noqa: BLE001 - a trace of another shape
            print(f"# scopes: cannot reduce the trace: {e!r}",
                  file=sys.stderr)
            parsed.reductions[span] = None
    return parsed.reductions[span]


def step_ms(ctx: Dict[str, Any], kind: str, *keys: str) -> Optional[float]:
    """Milliseconds of a step of ``kind`` cells under ``keys`` of the
    reduction (``"buckets", "attn"``; ``"parts", "kv_cache"``;
    ``"step_program_s"``). None in cells of the other kind and where
    nothing is read."""
    r: Any = reduced(ctx) if ctx["kind"] == kind else None
    if r is None:
        return None
    for key in keys:
        r = r[key]
    return 1e3 * r


def span_seconds(ctx: Dict[str, Any], name: str) -> List[Tuple[float, float,
                                                                 Dict]]:
    """(end, seconds, args) of the program's spans of that name inside the
    window, by end time (host clock: differences only are used)."""
    return sorted((s + d, d, a) for n, s, d, a in ctx.get("spans", ())
                  if n == name)
