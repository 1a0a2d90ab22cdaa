"""Mesh observability: per-device lanes + cross-device straggler detection.

PR 8's :class:`~determined_clone_tpu.telemetry.xla.StepTimeAnomalyDetector`
watches ONE duration stream — the host-side dispatch — so a straggling
*device* hides inside the gang's collective: every device waits at the
next all-reduce for the slowest one, and the host only sees the (uniform)
gang time. This module gives each device its own observable identity:

- :func:`per_device_completion_seconds` blocks on a sharded output's
  per-device shards in turn, yielding each device's completion time for
  the dispatch — coarse (host-observed, includes the block ordering) but
  real, and exactly the skew signal a simulated
  ``--xla_force_host_platform_device_count`` mesh can produce;
- :func:`device_lane_records` turns those durations into span records
  that carry a ``device`` key, which ``stitch_chrome_trace`` maps to one
  Chrome *process lane per device* — the mesh becomes visible in
  Perfetto the way trials and serving replicas already are;
- :class:`MeshStragglerDetector` generalizes the rolling median/MAD
  detector across the device dimension: per dispatch window the slowest
  device is compared against the *device median* of that same window, so
  a globally slow step (input stall — everyone slow) does not page, but
  one device holding the gang back does. At most ONE device is flagged
  per window (the slowest), incrementing
  ``mesh_straggler_events_total{device=...}``.
"""
from __future__ import annotations

import collections
import statistics
import time
from typing import Any, Deque, Dict, List, Optional

from determined_clone_tpu.telemetry.xla import MAD_SIGMA_SCALE


def per_device_completion_seconds(outputs: Any, t0: float
                                  ) -> Dict[str, float]:
    """Host-observed completion time per device for one dispatch.

    Picks the first sharded leaf of ``outputs`` that has addressable
    shards on more than one device and blocks on each shard's data,
    recording ``perf_counter() - t0`` as that device's completion time.
    Devices finish in execution order, so the readings are cumulative
    host time — a lower bound on skew, not a profile. Empty dict when
    nothing is multi-device (single-device runs have no mesh story)."""
    try:
        import jax

        leaves = jax.tree.leaves(outputs)
    except Exception:
        return {}
    for leaf in leaves:
        shards = getattr(leaf, "addressable_shards", None)
        if not shards or len(shards) < 2:
            continue
        out: Dict[str, float] = {}
        try:
            for shard in shards:
                dev = shard.device
                shard.data.block_until_ready()
                key = f"{dev.platform}:{dev.id}"
                if key not in out:
                    out[key] = time.perf_counter() - t0
            return out
        except Exception:
            return {}
    return {}


def device_lane_records(durations: Dict[str, float], *,
                        start_s: float, wall_epoch: Optional[float] = None,
                        step_index: int = 0,
                        name: str = "device_step") -> List[Dict[str, Any]]:
    """Span records (Tracer/event shape) for one dispatch, one per device.

    Each record carries ``device`` + a ``device:<id>`` process label, so
    ``stitch_chrome_trace`` gives every device its own lane; ``tid``/
    ``tname`` pin a single "steps" thread inside it."""
    records = []
    for dev, dur in sorted(durations.items()):
        rec: Dict[str, Any] = {
            "group": "span",
            "name": name,
            "ts_us": start_s * 1e6,
            "dur_us": max(0.0, float(dur)) * 1e6,
            "tid": 1,
            "tname": "steps",
            "device": dev,
            "process": f"device:{dev}",
            "args": {"device": dev, "step_index": step_index},
        }
        if wall_epoch is not None:
            rec["wall_epoch"] = float(wall_epoch)
        records.append(rec)
    return records


class MeshStragglerDetector:
    """Cross-device slowest-vs-median straggler detection per dispatch.

    ``observe`` takes one dispatch window's per-device durations. The
    baseline is the *median device* of the same window — cross-sectional,
    not temporal — so a step that is slow for everyone (data stall,
    checkpoint pause) flags nobody, while one device exceeding
    ``median + threshold * max(1.4826 * MAD, rel_floor * median)`` flags
    exactly that device (only the slowest; its followers are waiting on
    the same collective, not independently slow). Flagged events
    increment ``mesh_straggler_events_total{device=...}`` and land in a
    bounded event ring for the flight recorder / cluster summary.
    """

    def __init__(self, registry: Optional[Any] = None, *,
                 tracer: Optional[Any] = None,
                 threshold: float = 4.0, rel_floor: float = 0.25,
                 min_devices: int = 2, max_events: int = 256) -> None:
        self._registry = registry
        self._tracer = tracer
        self.threshold = float(threshold)
        self.rel_floor = float(rel_floor)
        self.min_devices = int(min_devices)
        self.events: Deque[Dict[str, Any]] = collections.deque(
            maxlen=int(max_events))
        self.windows = 0
        self.stragglers = 0
        self.by_device: Dict[str, int] = {}

    def observe(self, durations: Dict[str, float]) -> Optional[str]:
        """Feed one dispatch window; returns the flagged device or None."""
        self.windows += 1
        if len(durations) < self.min_devices:
            return None
        values = [float(v) for v in durations.values()]
        med = statistics.median(values)
        mad = statistics.median(abs(v - med) for v in values)
        sigma = max(MAD_SIGMA_SCALE * mad, self.rel_floor * med)
        limit = med + self.threshold * sigma
        slowest_dev = max(durations, key=lambda d: durations[d])
        slowest = float(durations[slowest_dev])
        if self._registry is not None:
            for dev, dur in durations.items():
                self._registry.gauge(
                    "mesh_device_step_seconds",
                    "per-device completion time of the last dispatch",
                    labels={"device": dev}).set(float(dur))
        if slowest <= limit:
            return None
        self.stragglers += 1
        self.by_device[slowest_dev] = self.by_device.get(slowest_dev, 0) + 1
        if self._registry is not None:
            self._registry.counter(
                "mesh_straggler_events_total",
                "dispatch windows where one device straggled past the "
                "cross-device median/MAD limit",
                labels={"device": slowest_dev}).inc()
        event = {
            "device": slowest_dev,
            "duration_s": round(slowest, 6),
            "median_s": round(med, 6),
            "mad_s": round(mad, 6),
            "limit_s": round(limit, 6),
            "window_index": self.windows,
        }
        self.events.append(event)
        if self._tracer is not None:
            self._tracer.instant("mesh_straggler", **event)
        return slowest_dev

    def summary(self) -> Dict[str, Any]:
        return {
            "windows": self.windows,
            "stragglers": self.stragglers,
            "by_device": dict(sorted(self.by_device.items())),
            "recent_events": list(self.events)[-8:],
        }


__all__ = [
    "MeshStragglerDetector",
    "device_lane_records",
    "per_device_completion_seconds",
]
