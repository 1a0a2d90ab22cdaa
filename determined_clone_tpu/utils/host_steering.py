"""Steer JAX onto a virtual multi-device CPU host platform.

Used by tests/conftest.py and __graft_entry__.dryrun_multichip: code
that wants an n-"chip" mesh on one box
(the reference's "artificial slots" trick,
agent/internal/detect/detect.go:39-56, recast as XLA host devices) sets the
platform and the forced device count here.

Must be called before any JAX backend initializes (before the first
``jax.devices()``-like call); importing jax beforehand is fine.
"""
from __future__ import annotations

import os
import re


def steer_to_host_cpu(n_devices: int = 8) -> None:
    """Force the CPU platform with ``n_devices`` virtual devices."""
    flags = os.environ.get("XLA_FLAGS", "")
    flag = f"--xla_force_host_platform_device_count={n_devices}"
    if "--xla_force_host_platform_device_count" in flags:
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", flag,
                       flags)
    else:
        flags = f"{flags} {flag}".strip()
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        import jax

        # Effective as long as no backend has initialized yet; if one has,
        # callers observe the actual device list and report the mismatch.
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
