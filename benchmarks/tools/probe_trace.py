#!/usr/bin/env python3
"""Record a small profiler trace on the chip and print what is in it: which
planes and lines exist and how events are named. The trace it writes is the
one kept beside ``benchmarks/tests/test_trace.py``.

    python benchmarks/tools/probe_trace.py <output directory>
"""
from __future__ import annotations

import collections
import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import device, trace  # noqa: E402


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    out = sys.argv[1]
    os.makedirs(out, exist_ok=True)
    n = len(jax.devices())
    device.require_chips(n)
    mesh = Mesh(np.asarray(jax.devices()), ("chips",))
    x = jax.device_put(jnp.ones((n * 512, 1024), jnp.bfloat16),
                       NamedSharding(mesh, P("chips")))
    w = jax.device_put(jnp.ones((1024, 1024), jnp.bfloat16) / 1024,
                       NamedSharding(mesh, P()))

    @jax.jit
    def step(x, w):
        y = jnp.tanh(x @ w) @ w
        # a reduction over every chip's rows: a collective where n > 1
        return y - jnp.mean(y.astype(jnp.float32)).astype(y.dtype)

    step(x, w).block_until_ready()
    window = trace.TraceWindow(os.path.join(out, "probe"))
    window.start()
    for _ in range(6):
        with jax.profiler.TraceAnnotation("bench.probe_step"):
            x = step(x, w)
            x.block_until_ready()
        with jax.profiler.TraceAnnotation("bench.probe_sleep"):
            time.sleep(0.002)
    window.stop()
    (path,) = glob.glob(os.path.join(out, "probe", "plugins", "profile", "*",
                                     "*.xplane.pb"))
    shutil.copy(path, os.path.join(out, f"probe{n}.xplane.pb"))
    print("xplane bytes", os.path.getsize(path))
    planes = trace.load_xplane(path)
    for pname, lines in planes.items():
        print("PLANE", pname)
        for lname, events in lines.items():
            names = collections.Counter(e[0] for e in events)
            print(f"  LINE {lname!r}: {len(events)} events; "
                  f"{names.most_common(6)}")
    print(trace.reduce_trace(planes, sync_monotonic=window.sync_monotonic))
    return 0


if __name__ == "__main__":
    sys.exit(main())
