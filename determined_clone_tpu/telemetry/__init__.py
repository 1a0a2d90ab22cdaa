"""Trial-side telemetry: spans, a metrics registry, Chrome-trace export.

The observability layer the async hot loop (PR 1) needs: with a prefetch
producer thread and fused multi-step dispatch, "where did the wall-clock
go" is no longer answerable from logs. This package provides

- :class:`Tracer` / spans — nested, thread-safe, monotonic-clock timing of
  the trainer loop end to end (``docs/observability.md`` has the taxonomy);
- :class:`MetricsRegistry` — Counter/Gauge/Histogram (streaming p50/p95/p99)
  fed by the trainer, prefetcher, and ProfilerAgent, exposed as Prometheus
  text via ``dump()`` and shipped to the master over the profiler channel;
- Chrome trace-event export — a per-trial ``trace.json`` that loads in
  Perfetto with thread lanes for the consumer loop, prefetch producer, and
  profiler threads (``dct trace export`` converts master-shipped spans).

Opt-in via the experiment config's ``observability: {enabled: true}`` block
(or ``DCT_OBSERVABILITY=1``); disabled (the default) it creates no threads
and the trainer's hot loop stays byte-identical (the instrumentation wraps
the step callables and the feeder only when enabled).
"""
from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, Optional

from determined_clone_tpu.telemetry.chrome_trace import (
    chrome_trace_events,
    spans_from_profiler_samples,
    stitch_chrome_trace,
    to_chrome_trace,
    validate_chrome_trace,
    write_chrome_trace,
)
from determined_clone_tpu.telemetry.collectives import (
    CollectiveSummary,
    comm_compute_fraction,
    export_collectives,
    parse_hlo_collectives,
)
from determined_clone_tpu.telemetry.flight import (
    FlightRecorder,
    RequestArchive,
    flight_summary,
    flight_to_chrome_trace,
    read_flight,
    read_request_archive,
    request_archive_summary,
    request_chrome_trace,
    request_records,
)
from determined_clone_tpu.telemetry.goodput import (
    CATEGORIES as GOODPUT_CATEGORIES,
    GoodputJournal,
    GoodputLedger,
    check_conservation,
    format_goodput,
    merge_goodput,
    read_goodput,
)
from determined_clone_tpu.telemetry.mesh import (
    MeshStragglerDetector,
    device_lane_records,
    per_device_completion_seconds,
)
from determined_clone_tpu.telemetry.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    parse_prometheus_text,
)
from determined_clone_tpu.telemetry.rules import (
    AlertRule,
    RuleEngine,
    format_alerts,
    stock_slo_rules,
)
from determined_clone_tpu.telemetry.slo import (
    SLOEngine,
    format_slo,
)
from determined_clone_tpu.telemetry.spans import (
    NULL_SPAN,
    Span,
    Tracer,
    null_span,
)
from determined_clone_tpu.telemetry.tsdb import (
    TSDBScraper,
    TimeSeriesDB,
)

__all__ = [
    "AlertRule", "CollectiveSummary", "Counter", "FlightRecorder",
    "GOODPUT_CATEGORIES", "Gauge", "GoodputJournal", "GoodputLedger",
    "Histogram", "MeshStragglerDetector",
    "MetricsRegistry", "NULL_SPAN", "RequestArchive", "RuleEngine",
    "SLOEngine", "Span", "TSDBScraper", "Telemetry", "TimeSeriesDB",
    "Tracer", "check_conservation", "chrome_trace_events",
    "comm_compute_fraction", "device_lane_records", "export_collectives",
    "flight_summary", "flight_to_chrome_trace", "format_alerts",
    "format_goodput", "format_slo", "merge_goodput", "null_span",
    "parse_hlo_collectives",
    "parse_prometheus_text", "per_device_completion_seconds",
    "read_flight", "read_goodput", "read_request_archive",
    "request_archive_summary", "request_chrome_trace", "request_records",
    "spans_from_profiler_samples", "stitch_chrome_trace", "stock_slo_rules",
    "telemetry_from_config", "to_chrome_trace", "validate_chrome_trace",
    "write_chrome_trace",
]


class _TracedFeeder:
    """Wraps a device feeder so each consumer pull is a ``dataload_wait``
    span + histogram observation. Only constructed when telemetry is
    enabled — the disabled hot loop consumes the raw feeder."""

    def __init__(self, feed: Any, telemetry: "Telemetry") -> None:
        self._feed = feed
        self._span = telemetry.tracer.span
        self._hist = telemetry.registry.histogram(
            "dataload_wait_seconds",
            "consumer-visible input stall per pull (overlap residue)")

    def __iter__(self) -> "_TracedFeeder":
        return self

    def __next__(self) -> Any:
        t0 = time.perf_counter()
        with self._span("dataload_wait"):
            batch = next(self._feed)
        self._hist.observe(time.perf_counter() - t0)
        return batch

    # trainer-facing surface of DevicePrefetcher / SyncDeviceFeeder
    def take_queue_wait(self) -> float:
        return self._feed.take_queue_wait()

    def take_host_time(self) -> float:
        return self._feed.take_host_time()

    def close(self, timeout: float = 5.0) -> None:
        self._feed.close(timeout)


class Telemetry:
    """Facade bundling one Tracer + one MetricsRegistry per trial."""

    def __init__(self, *, enabled: bool = True, max_events: int = 200_000,
                 ship_spans: bool = False, ship_metrics: bool = True,
                 trace_path: Optional[str] = None,
                 trace_id: Optional[str] = None,
                 process_name: Optional[str] = None) -> None:
        self.enabled = enabled
        self.ship_spans = ship_spans
        self.ship_metrics = ship_metrics
        self.trace_path = trace_path
        self.tracer = Tracer(enabled=enabled, max_events=max_events,
                             trace_id=trace_id, process_name=process_name)
        self.registry = MetricsRegistry()
        self._ship_cursor = 0
        # crash black box (attach_flight) + anomaly-detector tuning the
        # trainer reads off this facade; both set by telemetry_from_config
        self.flight: Optional[FlightRecorder] = None
        self.anomaly_window = 64
        self.anomaly_threshold = 5.0
        self.anomaly_min_samples = 16
        # wall-clock attribution (docs/observability.md goodput section):
        # the ledger rides the tracer sink hook, so every finished span is
        # bucketed with no extra work on the hot path
        self.goodput: Optional[GoodputLedger] = None
        if enabled:
            self.goodput = GoodputLedger(registry=self.registry)
            self.tracer.add_sink(self.goodput.observe_span)

    @property
    def trace_id(self) -> Optional[str]:
        return self.tracer.trace_id

    @property
    def process_name(self) -> Optional[str]:
        return self.tracer.process_name

    def set_identity(self, *, trace_id: Optional[str] = None,
                     process_name: Optional[str] = None) -> None:
        """Late-bind the cross-component trace identity. The runner (or
        ``exec/trial.py``) knows the experiment's trace_id and the
        process's lane name only after the telemetry object exists, so
        identity is settable — shipped span records pick it up from here
        on (already-shipped records keep whatever they went out with)."""
        if trace_id is not None:
            self.tracer.trace_id = trace_id
        if process_name is not None:
            self.tracer.process_name = process_name
        if self.flight is not None:
            self.flight.set_identity(trace_id=self.tracer.trace_id,
                                     process=self.tracer.process_name)
        if self.goodput is not None and trace_id is not None:
            self.goodput.set_identity(trace_id=trace_id)

    def attach_flight(self, recorder: FlightRecorder) -> None:
        """Wire the flight recorder: it becomes a tracer sink (every
        finished span hits disk) and inherits this trial's identity so
        ``dct debug flight`` can stitch the ring into the same trace as
        the master-shipped spans."""
        self.flight = recorder
        recorder.set_identity(
            wall_epoch=self.tracer.wall_epoch,
            trace_id=self.tracer.trace_id,
            process=self.tracer.process_name,
            pid=os.getpid())
        self.tracer.add_sink(recorder.record_span)

    def close(self) -> None:
        """Flush durable state (flight segment, goodput journal) on clean
        shutdown."""
        if self.goodput is not None:
            self.goodput.close()
        if self.flight is not None:
            self.flight.close()

    # -- instrumentation hooks ---------------------------------------------

    def wrap_jit(self, name: str, fn: Callable[..., Any], *,
                 sync: Optional[Callable[[Any], Any]] = None,
                 observe: Optional[Callable[[float], None]] = None,
                 ) -> Callable[..., Any]:
        """Wrap a jitted callable: every call is a ``name`` span feeding a
        ``{name}_seconds`` histogram, and XLA compiles are detected and
        recorded as ``xla_compile`` spans.

        Detection uses the jitted function's compilation-cache size when
        available (each growth = one trace+compile, so *re*traces — e.g. a
        new batch shape — are caught too), falling back to first-call
        timing otherwise.

        ``sync`` (e.g. ``jax.block_until_ready``) is applied to the output
        *inside* the span: under async dispatch the bare call returns after
        enqueue, so without a sync the span would time Python dispatch
        overhead, not device compute. This is the tracing observer effect
        (docs/observability.md) — dispatch pipelining is traded for
        attributable timings while telemetry is on.

        ``observe`` receives each steady-state duration (seconds) —
        compile calls are excluded, so an anomaly detector's baseline is
        not poisoned by the one legitimate 1000x outlier.
        """
        if not self.enabled:
            return fn
        tracer = self.tracer
        hist = self.registry.histogram(
            f"{name}_seconds", f"duration of each {name} call")
        compiles = self.registry.counter(
            "xla_compiles_total",
            "jitted-program compilations observed (first calls + retraces)")
        cache_size = getattr(fn, "_cache_size", None)
        state = {"calls": 0}

        def wrapped(*args: Any, **kwargs: Any) -> Any:
            before = cache_size() if cache_size is not None else None
            first = state["calls"] == 0
            state["calls"] += 1
            t0 = time.perf_counter()
            with tracer.span(name) as sp:
                out = fn(*args, **kwargs)
                if sync is not None:
                    sync(out)
            dt = time.perf_counter() - t0
            hist.observe(dt)
            compiled = (cache_size() > before if before is not None
                        else first)
            if compiled:
                sp.set(compiled=True)
                compiles.inc()
                tracer.record_span("xla_compile", t0, dt, program=name)
            elif observe is not None:
                observe(dt)
            return out

        wrapped.__name__ = f"traced_{name}"
        if cache_size is not None:
            # keep the probe reachable through the wrapper so retrace
            # counting (train_step.program_cache_size) still works
            wrapped._cache_size = cache_size
        return wrapped

    def wrap_feeder(self, feed: Any) -> Any:
        """Wrap a device feeder in ``dataload_wait`` accounting."""
        if not self.enabled:
            return feed
        return _TracedFeeder(feed, self)

    def compile_count(self) -> int:
        return int(self.registry.counter("xla_compiles_total").value)

    # -- shipping + export --------------------------------------------------

    def publish(self, profiler: Any,
                batches_trained: Optional[int] = None) -> None:
        """Feed the profiler channel one registry snapshot (group
        ``telemetry``) and, when ``ship_spans``, the span records finished
        since the last publish (group ``span``). Called at the trainer's
        chunk boundary, so shipping is batched and off the hot path."""
        if not self.enabled:
            return
        if self.goodput is not None:
            # land the wall-clock account in the registry *before* the
            # snapshot below, so both the flight recorder and the shipped
            # sample carry goodput_* gauges; also journals a durable line
            self.goodput.publish_metrics()
        if self.flight is not None:
            # the black box gets a snapshot even when no profiler channel
            # is wired (unit tests, stripped-down subprocesses)
            self.flight.record_metrics(self.registry.snapshot(),
                                       batches_trained=batches_trained)
        if profiler is None:
            return
        now = time.time()
        if self.ship_metrics:
            sample: Dict[str, Any] = {
                "time": now, "group": "telemetry",
                "metrics": self.registry.snapshot(),
            }
            if batches_trained is not None:
                sample["batches_trained"] = int(batches_trained)
            profiler.record(sample)
        if self.ship_spans:
            new, self._ship_cursor = self.tracer.drain_since(
                self._ship_cursor)
            # identity + clock anchor ride every shipped record so the
            # master can stitch lanes from different processes into one
            # trace (ts_us is relative to each tracer's private epoch;
            # wall_epoch aligns them)
            ident: Dict[str, Any] = {"wall_epoch": self.tracer.wall_epoch}
            if self.tracer.trace_id:
                ident["trace_id"] = self.tracer.trace_id
            if self.tracer.process_name:
                ident["process"] = self.tracer.process_name
            for rec in new:
                profiler.record(
                    {"time": now, "group": "span", **ident, **rec})

    def export_chrome_trace(self, path: Optional[str] = None) -> str:
        path = path or self.trace_path or "trace.json"
        return write_chrome_trace(
            path, self.tracer.events(),
            other_data={
                "wall_epoch": self.tracer.wall_epoch,
                "events_dropped": self.tracer.dropped,
                "span_summary": self.tracer.span_summary(),
            })

    def span_summary(self) -> Dict[str, Dict[str, float]]:
        return self.tracer.span_summary()


def telemetry_from_config(config: Any) -> Optional[Telemetry]:
    """Build from an experiment config's ``observability:`` block.

    Accepts an :class:`ExperimentConfig` (reads ``.observability``) or a raw
    config dict. Returns None when disabled — callers keep a no-telemetry
    fast path instead of threading a disabled object through the hot loop.
    ``DCT_OBSERVABILITY=1`` force-enables, mirroring ``DCT_PROFILING``.
    """
    # hard off-switch, beating every force-enable below: CI lanes use it
    # to prove the suite (and the goodput tests in particular) skip
    # cleanly when the telemetry plane is compiled out of a run
    if os.environ.get("DCT_TELEMETRY_DISABLED") == "1":
        return None
    obs = getattr(config, "observability", None)
    if obs is None and isinstance(config, dict):
        from determined_clone_tpu.config.experiment import ObservabilityConfig

        try:
            obs = ObservabilityConfig.from_dict(
                config.get("observability") or {})
        except Exception:
            obs = ObservabilityConfig()
    enabled = bool(obs is not None and obs.enabled)
    if os.environ.get("DCT_OBSERVABILITY") == "1":
        enabled = True
    # the flight recorder needs the tracer, so a flight dir (config or the
    # DCT_FLIGHT_DIR escape hatch the chaos harness uses) implies enabled
    flight_dir = os.environ.get("DCT_FLIGHT_DIR") or (
        obs.flight_dir if obs is not None else None)
    if flight_dir:
        enabled = True
    # same contract for the goodput journal: a journal dir implies enabled
    # (the chaos harness points restart legs at one shared directory)
    goodput_dir = os.environ.get("DCT_GOODPUT_DIR") or (
        getattr(obs, "goodput_dir", None) if obs is not None else None)
    if goodput_dir:
        enabled = True
    if not enabled:
        return None
    if obs is None:
        from determined_clone_tpu.config.experiment import ObservabilityConfig

        obs = ObservabilityConfig()
    tel = Telemetry(
        enabled=True,
        max_events=obs.max_events,
        ship_spans=obs.ship_spans,
        ship_metrics=obs.ship_metrics,
        trace_path=obs.trace_path,
        # cross-component stitching: the experiment submitter exports its
        # trace id through the trial env (runner.py / exec/trial.py), so
        # every component of one experiment shares one trace
        trace_id=os.environ.get("DCT_TRACE_ID") or None,
    )
    tel.anomaly_window = obs.anomaly_window
    tel.anomaly_threshold = obs.anomaly_threshold
    tel.anomaly_min_samples = obs.anomaly_min_samples
    if flight_dir:
        tel.attach_flight(FlightRecorder(
            flight_dir,
            segment_events=obs.flight_segment_events,
            max_segments=obs.flight_segments,
            registry=tel.registry))
    if goodput_dir and tel.goodput is not None:
        tel.goodput.attach_journal(goodput_dir)
    if tel.goodput is not None:
        # PR 7 lifecycle timestamps: the master's submitted_at→scheduled_at
        # wait for this leg, exported by the runner so the trial's ledger
        # can book scheduler time it never saw (it wasn't alive yet)
        queue_wait = os.environ.get("DCT_QUEUE_WAIT_S")
        if queue_wait:
            try:
                # pre_wall: the queue wait happened before this process
                # was born, so it extends the accountable wall-clock
                tel.goodput.note("queue_wait", float(queue_wait),
                                 pre_wall=True)
            except (TypeError, ValueError):
                pass
    return tel
