"""The device this run is on: the check that it is the chip the cell asks
for, the benchmark's own table of peaks, compile counting and peak memory.

A run off the chip fails here. There is no CPU fallback and no flag that
allows one.
"""
from __future__ import annotations

import os
from typing import Any, Dict

from benchmarks.harness.spec import BENCH_DIR

# Peaks of one chip, keyed by ``jax.devices()[0].device_kind``. A kind that
# is not here is an error, never a default.
# Source: Google Cloud documentation, "TPU v5e" system architecture page
# (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}

CACHE_DIR = os.path.join(BENCH_DIR, ".jax_cache")


class NoChip(SystemExit):
    """Raised (as a non-zero exit, before any result line) when the run is
    not on the chips its cell asks for."""


def configure_compile_cache() -> str:
    """JAX's persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else a fixed directory inside the checkout. Every program is kept,
    whatever it took to compile. Must run before the first compile."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


def require_chips(n_chips: int) -> Dict[str, Any]:
    """The ``device`` block of the result line, or a non-zero exit."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"benchmark: no TPU (JAX reports platform "
                     f"{dev.platform!r}); it runs on nothing else")
    if len(devices) != n_chips:
        raise NoChip(f"benchmark: the cell asks for {n_chips} chip(s), JAX "
                     f"reports {len(devices)}")
    if dev.device_kind not in PEAKS:
        raise NoChip(f"benchmark: device kind {dev.device_kind!r} is not in "
                     f"the benchmark's peak table (harness/device.py)")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def peak_memory_bytes() -> int:
    """Peak bytes on the fullest chip, as the allocator reports: the high-
    water mark of live buffers (``peak_bytes_in_use``: parameters, state,
    KV pools, batches) plus that of the region the runtime reserves for the
    programs' temporaries (``peak_bytes_reserved``), which the first does
    not count (PERF.md, section 7)."""
    import jax

    def peak(d: Any) -> int:
        m = d.memory_stats() or {}
        return int(m.get("peak_bytes_in_use", 0)
                   + m.get("peak_bytes_reserved", 0))

    return max(peak(d) for d in jax.devices())


class CompileCounter:
    """Counts XLA compile requests and persistent-cache hits through JAX's
    own monitoring events: requests - hits = cold compiles. (After
    ``chip_smoke.py``'s counter.)"""

    REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
    HITS = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        import jax.monitoring

        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **kw: Any) -> None:
        if name == self.REQUESTS:
            self.requests += 1
        elif name == self.HITS:
            self.hits += 1

    def snapshot(self) -> Dict[str, int]:
        return {"compile_requests": self.requests, "cache_hits": self.hits,
                "cold_compiles": self.requests - self.hits}
