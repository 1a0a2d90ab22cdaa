"""XLA-level telemetry: explicit compile capture and step-time anomalies.

The telemetry stack so far watches the *Python* side of the hot loop —
spans time dispatches, ``xla_compiles_total`` counts cache growth — but
the compiled program itself stayed a black box: compile time was
invisible. This module opens the box via JAX's AOT path
(``cost_analysis()`` FLOPs are recorded per program but feed no MFU: on
TPU they leave the scan body and custom calls out, docs/observability.md):

- :func:`aot_compile` replaces a jitted callable's first-call implicit
  compile with an explicit ``lower()`` / ``compile()`` whose wall time is
  measured, whose lowered StableHLO text is fingerprinted (sha256), and whose
  ``cost_analysis()`` FLOPs/bytes become per-program metrics. The returned
  callable runs the AOT executable (no double compile) and falls back to
  the original jit wrapper on argument-shape mismatch.
- :class:`StepTimeAnomalyDetector` — a rolling median/MAD detector over
  dispatch durations. MAD (median absolute deviation) is robust to the
  very outliers it hunts: a straggler step moves a mean-based z-score's
  own baseline, but barely moves the median. Anomalies increment
  ``step_time_anomalies_total`` and are kept as bounded events for the
  flight recorder / cluster summary.

Everything degrades to no-ops: a backend without AOT or cost analysis
returns the original callable and ``None`` — telemetry must never fail
training.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import logging
import statistics
import time
from typing import Any, Callable, Deque, Dict, Optional, Tuple

logger = logging.getLogger(__name__)

# 1.4826 * MAD estimates the standard deviation for normal data; the
# detector's threshold is expressed in these robust sigmas.
MAD_SIGMA_SCALE = 1.4826


@dataclasses.dataclass
class CompileRecord:
    """What one explicit lower()/compile() observed."""

    program: str
    fingerprint: str          # sha256 hex of the lowered StableHLO text
    lower_seconds: float
    compile_seconds: float
    flops: Optional[float] = None          # compiled.cost_analysis()
    bytes_accessed: Optional[float] = None
    # compiled.memory_analysis(): what the executable will hold live
    argument_bytes: Optional[float] = None
    output_bytes: Optional[float] = None
    temp_bytes: Optional[float] = None
    # post-SPMD collective accounting (telemetry/collectives.py); None
    # when the compiled HLO text was unavailable or mesh-less
    collectives: Optional[Any] = None
    comm_fraction: Optional[float] = None

    def as_dict(self) -> Dict[str, Any]:
        out = {k: v for k, v in dataclasses.asdict(self).items()
               if v is not None and k != "collectives"}
        if self.collectives is not None:
            out["collectives"] = self.collectives.as_dict()
        return out


def _cost_analysis(compiled: Any) -> Tuple[Optional[float], Optional[float]]:
    """(flops, bytes_accessed) from ``compiled.cost_analysis()``.

    jax returns a dict on newer versions and a one-element list of dicts
    on older ones (0.4.x); a backend without cost modeling returns
    None/empty — map all of it to (None, None) rather than raising.
    """
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None, None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None, None
    flops = ca.get("flops")
    byts = ca.get("bytes accessed")
    return (float(flops) if flops is not None else None,
            float(byts) if byts is not None else None)


def _memory_analysis(compiled: Any) -> Dict[str, float]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for field, key in (("argument_size_in_bytes", "argument_bytes"),
                       ("output_size_in_bytes", "output_bytes"),
                       ("temp_size_in_bytes", "temp_bytes")):
        v = getattr(ma, field, None)
        if v is not None:
            out[key] = float(v)
    return out


def fingerprint_stablehlo(text: str) -> str:
    """sha256 of the lowered program text: the program's identity in
    the ``{program, fingerprint}`` labels of the compile metrics."""
    return hashlib.sha256(text.encode("utf-8")).hexdigest()


def _dynamic_positions(example_args: Tuple[Any, ...],
                       lowered: Any) -> Optional[Tuple[int, ...]]:
    """Which positions of ``example_args`` the *compiled* executable
    expects.

    ``jax.jit(..., static_argnums=...)`` burns static arguments into the
    program: ``Compiled.__call__`` must be invoked with the dynamic
    arguments ONLY (passing the statics raises the input-pytree
    TypeError). The jit wrapper does not expose its static argnums, so
    recover them from the lowering itself: ``lowered.args_info`` lists
    the dynamic arguments in order, each a pytree of avals. Align the
    example arguments against it left-to-right — an argument whose tree
    structure and leaf shapes match the next dynamic slot consumes it,
    anything else was static. A static that happens to mimic the next
    dynamic slot exactly would mis-align, but the AOT call wrapper falls
    back to the jit cache on any argument mismatch, so the worst case is
    the old (uncached) behavior, never a wrong answer.

    Returns None when every argument is dynamic (the common no-statics
    case: skip the pruning on the hot path).
    """
    import jax

    info = lowered.args_info
    if isinstance(info, tuple) and len(info) == 2 and isinstance(
            info[1], dict):
        info = info[0]  # (args, kwargs) form
    slots = [jax.tree_util.tree_flatten(a) for a in info]
    if len(slots) == len(example_args):
        return None

    def _matches(arg: Any, slot: Tuple[Any, Any]) -> bool:
        leaves, treedef = slot
        try:
            got, got_def = jax.tree_util.tree_flatten(arg)
        except Exception:
            return False
        if got_def != treedef or len(got) != len(leaves):
            return False
        for g, want in zip(got, leaves):
            aval = getattr(want, "aval", None) or getattr(
                want, "_aval", None)
            want_shape = getattr(aval, "shape", None)
            if want_shape is None:
                continue
            got_shape = getattr(g, "shape", None)
            if got_shape is None:
                if isinstance(g, (bool, int, float, complex)):
                    got_shape = ()
                else:
                    return False
            if tuple(got_shape) != tuple(want_shape):
                return False
        return True

    out = []
    slot_i = 0
    for pos, arg in enumerate(example_args):
        if slot_i < len(slots) and _matches(arg, slots[slot_i]):
            out.append(pos)
            slot_i += 1
    if slot_i != len(slots):  # alignment failed: let the wrapper fall back
        return None
    return tuple(out)


def aot_compile(
    fn: Callable[..., Any],
    example_args: Tuple[Any, ...],
    *,
    program: str = "train_step",
    registry: Optional[Any] = None,
    tracer: Optional[Any] = None,
    mesh: Optional[Any] = None,
) -> Tuple[Callable[..., Any], Optional[CompileRecord]]:
    """Explicitly lower + compile a jitted callable, capturing telemetry.

    Returns ``(callable, record)``. On success the callable runs the AOT
    executable for matching argument shapes (so the measured compile is
    the one that actually executes — no second implicit compile) and
    falls back to ``fn`` on shape mismatch (e.g. a remainder batch), which
    then compiles through the normal jit cache where ``wrap_jit`` counts
    it as a retrace. On any AOT failure — backend without ``lower``,
    donation quirk, cost-model gap — the original ``fn`` comes back
    unwrapped with ``record=None``: capture is an observer, never a
    dependency.

    ``example_args`` only contribute shapes/dtypes/shardings; nothing
    executes during lowering.

    With ``mesh`` (a ``jax.sharding.Mesh`` or an ``{axis: size}`` mapping)
    the *compiled* — post-SPMD-partitioner — HLO text is additionally
    parsed for collectives (telemetry/collectives.py): op counts and byte
    volumes per mesh axis land on the record and, with a registry, as
    ``xla_collective_*`` gauges plus an analytic comm-vs-compute fraction.
    The lowered StableHLO has none of this (collectives are *inserted* by
    partitioning), which is why the capture reads ``compiled.as_text()``.
    """
    try:
        t0 = time.perf_counter()
        lowered = fn.lower(*example_args)
        text = lowered.as_text()
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        flops, bytes_accessed = _cost_analysis(compiled)
        record = CompileRecord(
            program=program,
            fingerprint=fingerprint_stablehlo(text),
            lower_seconds=t1 - t0,
            compile_seconds=t2 - t1,
            flops=flops,
            bytes_accessed=bytes_accessed,
            **_memory_analysis(compiled),
        )
    except Exception as exc:  # noqa: BLE001 - capture must never fail training
        # training goes on with the implicit compile, but a lost capture
        # must be seen: no compile record, no measured-FLOPs MFU
        logger.warning("aot compile capture failed for %s: %r",
                       program, exc)
        return fn, None

    if mesh is not None:
        try:
            from determined_clone_tpu.telemetry import (
                collectives as coll_mod,
            )
            from determined_clone_tpu.telemetry import flops as flops_mod

            summary = coll_mod.parse_hlo_collectives(
                compiled.as_text(), mesh=mesh)
            record.collectives = summary
            # both read the device the process actually has; a kind with
            # no published numbers yields None and so no fraction
            bw, _bw_label = flops_mod.interconnect_bandwidth_estimate()
            peak, _peak_label = flops_mod.peak_flops_estimate()
            # cost_analysis() describes the per-device partitioned module
            # and the parser's byte volumes are per-shard payloads, so
            # both sides of the fraction are per-device quantities
            record.comm_fraction = coll_mod.comm_compute_fraction(
                summary, record.flops,
                interconnect_bytes_per_s=bw,
                peak_flops_per_s=peak)
            if registry is not None:
                coll_mod.export_collectives(
                    summary, registry, program=program,
                    fingerprint=record.fingerprint[:16],
                    comm_fraction=record.comm_fraction)
        except Exception as exc:  # noqa: BLE001 - observer, never a dependency
            logger.debug("collective accounting unavailable for %s: %r",
                         program, exc)

    export_compile_record(record, registry=registry, tracer=tracer,
                          start=t0)

    # jit statics are burned into the program: Compiled.__call__ takes
    # the dynamic arguments only, so prune the static positions (None
    # means everything was dynamic)
    try:
        dynamic = _dynamic_positions(example_args, lowered)
    except Exception:  # noqa: BLE001 - alignment is best-effort
        dynamic = None

    def call(*args: Any, **kwargs: Any) -> Any:
        try:
            if kwargs or (dynamic is not None
                          and len(args) != len(example_args)):
                return fn(*args, **kwargs)
            if dynamic is not None:
                return compiled(*(args[i] for i in dynamic))
            return compiled(*args)
        except (TypeError, ValueError):
            # argument shapes differ from the captured program (remainder
            # batch, dtype change): the jit cache handles it — raised
            # before any buffer is consumed, so donation state is intact
            return fn(*args, **kwargs)

    call.__name__ = f"aot_{program}"
    probe = getattr(fn, "_cache_size", None)
    if probe is not None:
        call._cache_size = probe
    call._compile_record = record
    return call, record


def export_compile_record(record: CompileRecord, *,
                          registry: Optional[Any] = None,
                          tracer: Optional[Any] = None,
                          start: Optional[float] = None) -> None:
    """Land one compile capture in the metric registry + span stream.

    Families are keyed by ``{program, fingerprint}`` labels — two rounds
    (or two legs) that compiled the *same* fingerprint should report the
    same ``xla_program_flops``, and a fingerprint change between rounds is
    itself the signal (the program changed, not just the timing).
    """
    if registry is not None:
        labels = {"program": record.program,
                  "fingerprint": record.fingerprint[:16]}
        # the AOT capture replaces the implicit first-call compile that
        # wrap_jit would have counted, so count it here (same family)
        registry.counter(
            "xla_compiles_total",
            "jitted-program compilations observed (first calls + retraces)"
        ).inc()
        registry.gauge(
            "xla_compile_seconds",
            "explicit lower+compile wall time per program",
            labels=labels).set(record.lower_seconds + record.compile_seconds)
        if record.flops is not None:
            registry.gauge(
                "xla_program_flops",
                "per-execution FLOPs from compiled.cost_analysis()",
                labels=labels).set(record.flops)
        if record.bytes_accessed is not None:
            registry.gauge(
                "xla_program_bytes_accessed",
                "per-execution bytes accessed from cost_analysis()",
                labels=labels).set(record.bytes_accessed)
        if record.temp_bytes is not None:
            registry.gauge(
                "xla_program_temp_bytes",
                "executable scratch memory from memory_analysis()",
                labels=labels).set(record.temp_bytes)
    if tracer is not None:
        tracer.record_span(
            "xla_compile",
            start if start is not None else time.perf_counter(),
            record.lower_seconds + record.compile_seconds,
            program=record.program, fingerprint=record.fingerprint[:16],
            explicit=True)


class StepTimeAnomalyDetector:
    """Rolling median/MAD detector over dispatch durations.

    A step is anomalous when it exceeds
    ``median + threshold * max(1.4826 * MAD, rel_floor * median)`` —
    the floor keeps a near-constant baseline (MAD ≈ 0 on an idle CPU
    mesh) from flagging scheduler jitter as stragglers. Only the slow
    side fires: fast steps (remainder dispatches of a fused program) are
    not a problem worth paging about.

    The window holds *pre-anomaly* history: an anomalous duration is NOT
    fed back into the window, so one straggler can't raise the baseline
    and mask the next one (detect-then-admit would do exactly that).
    Warmup (``min_samples``) covers compile + cache-warm steps.
    """

    def __init__(self, registry: Optional[Any] = None, *,
                 tracer: Optional[Any] = None,
                 window: int = 64, threshold: float = 5.0,
                 min_samples: int = 16, rel_floor: float = 0.05,
                 max_events: int = 256) -> None:
        self._registry = registry
        self._tracer = tracer
        self.window: Deque[float] = collections.deque(maxlen=int(window))
        self.threshold = float(threshold)
        self.min_samples = int(min_samples)
        self.rel_floor = float(rel_floor)
        self.events: Deque[Dict[str, Any]] = collections.deque(
            maxlen=int(max_events))
        self.anomalies = 0
        self._seen = 0
        self._counter = (registry.counter(
            "step_time_anomalies_total",
            "train dispatches flagged by the rolling median/MAD detector")
            if registry is not None else None)

    def observe(self, duration_s: float) -> bool:
        """Feed one dispatch duration; True when flagged anomalous."""
        duration_s = float(duration_s)
        self._seen += 1
        if len(self.window) < self.min_samples:
            self.window.append(duration_s)
            return False
        med = statistics.median(self.window)
        mad = statistics.median(abs(x - med) for x in self.window)
        sigma = max(MAD_SIGMA_SCALE * mad, self.rel_floor * med)
        limit = med + self.threshold * sigma
        if duration_s <= limit:
            self.window.append(duration_s)
            return False
        self.anomalies += 1
        if self._counter is not None:
            self._counter.inc()
        event = {
            "duration_s": round(duration_s, 6),
            "median_s": round(med, 6),
            "mad_s": round(mad, 6),
            "limit_s": round(limit, 6),
            "step_index": self._seen,
        }
        self.events.append(event)
        if self._tracer is not None:
            self._tracer.instant("step_time_anomaly", **event)
        return True

    def summary(self) -> Dict[str, Any]:
        return {
            "anomalies": self.anomalies,
            "window_len": len(self.window),
            "recent_events": list(self.events)[-8:],
        }


__all__ = [
    "CompileRecord",
    "StepTimeAnomalyDetector",
    "aot_compile",
    "export_compile_record",
    "fingerprint_stablehlo",
]
