"""The comparisons that decide ``correct``. Each number compared has a
limit of its own, in the cell's file (``workloads/<cell>.json``,
``limits``); every run prints each number beside its limit. PERF.md gives
the readings every limit was set from.
"""
from __future__ import annotations

import json
import math
import statistics
from typing import Any, Dict, List, Sequence

import numpy as np


def leaf_norms(tree: Any) -> Any:
    """L2 norm of every leaf in ``jax.tree.leaves`` order, as one device
    vector (the benchmark's own arithmetic on the program's state)."""
    import jax
    import jax.numpy as jnp

    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


PROBES = 16


def leaf_projections(tree: Any, key: Any, k: int = PROBES) -> Any:
    """<leaf, r_j> for k seeded directions r_j of +-1 per leaf, as one
    device array [leaves, k]. Two gradients' projections on the same
    directions give the norm of their difference without the two ever being
    held together: E <d, r>^2 = |d|^2. A sign is the low bit of a hash
    (murmur3's finaliser) of the element's index and a seeded salt: one
    fused pass over the leaf per direction, the same on any sharding. (Random
    draws per element took 20 s a side at 355 M parameters on the chip.)"""
    import jax
    import jax.numpy as jnp

    leaves = jax.tree.leaves(tree)
    salts = jax.random.bits(key, (len(leaves), k), jnp.uint32)
    rows = []
    for i, x in enumerate(leaves):
        x32 = x.astype(jnp.float32)
        index = jnp.zeros(x.shape, jnp.uint32)
        for axis, size in enumerate(x.shape):
            index = index * jnp.uint32(size) + jax.lax.broadcasted_iota(
                jnp.uint32, x.shape, axis)

        def one(salt, x32=x32, index=index):
            h = index ^ salt
            h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
            h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
            h = h ^ (h >> 16)
            return jnp.sum(jnp.where(h & 1, x32, -x32))

        rows.append(jax.lax.map(one, salts[i]))
    return jnp.stack(rows)


def worst_leaf_difference(program: Any, reference: Any,
                          reference_norms: Sequence[float]) -> float:
    """The widest estimated |g_program - g_reference| of a leaf, from the
    two sides' projections [leaves, k], against the reference's norm of that
    leaf or of its median leaf, whichever is larger."""
    d = np.asarray(program, np.float64) - np.asarray(reference, np.float64)
    diff = np.sqrt(np.mean(d * d, axis=1))
    ref = np.asarray(reference_norms, np.float64)
    rel = diff / np.maximum(ref, statistics.median(ref.tolist()))
    print(f"# widest difference: leaf {int(np.argmax(rel))} of {len(rel)}",
          flush=True)
    return float(np.max(rel))


def worst_leaf_gap(program: Sequence[float], reference: Sequence[float]
                   ) -> float:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of its median
    leaf, whichever is larger (some leaves' gradients are all but zero)."""
    prog = np.asarray(program, np.float64)
    ref = np.asarray(reference, np.float64)
    if prog.shape != ref.shape:
        raise ValueError(f"{prog.shape} leaves against {ref.shape}")
    floor = statistics.median(ref.tolist())
    gaps = np.abs(prog - ref) / np.maximum(ref, floor)
    print(f"# worst leaf {int(np.argmax(gaps))} of {len(gaps)}: program "
          f"{prog[np.argmax(gaps)]:.6g} reference {ref[np.argmax(gaps)]:.6g} "
          f"median leaf {floor:.6g}", flush=True)
    return float(np.max(gaps))


def worst_loss_gap(program: Sequence[float], reference: Sequence[float]
                   ) -> float:
    """The widest relative gap between the per-step losses."""
    return max(abs(p - r) / abs(r) for p, r in zip(program, reference))


def widest_logit_gap(logits: np.ndarray, tokens: Sequence[int]) -> float:
    """By how much the chosen tokens trail the best logit of their rows, at
    worst. logits [n, V] float32, tokens [n]."""
    rows = np.arange(len(tokens))
    return float(np.max(logits.max(axis=-1)
                        - logits[rows, np.asarray(tokens)]))


class Verdict:
    """Collects (number, limit) pairs; ``correct`` is their conjunction."""

    def __init__(self, limits: Dict[str, float]) -> None:
        self.limits = limits
        self.rows: List[Dict[str, Any]] = []

    def compare(self, name: str, value: float) -> None:
        """``value`` must be finite and at most the cell's limit of that
        name."""
        limit = self.limits[name]
        ok = math.isfinite(value) and value <= limit
        self._row(name, value, limit, ok)

    def require(self, name: str, ok: bool, detail: Any = None) -> None:
        """A yes-or-no condition (exact comparisons have the limit 0)."""
        self._row(name, 0.0 if ok else 1.0, 0.0, bool(ok), detail)

    def _row(self, name: str, value: float, limit: float, ok: bool,
             detail: Any = None) -> None:
        row = {"check": name, "value": value, "limit": limit, "ok": ok}
        if detail is not None:
            row["detail"] = detail
        self.rows.append(row)
        print(json.dumps(row), flush=True)

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)


def spread_over(devices: Sequence[Any], shapes: Any) -> Any:
    """A plain placement for the reference on several chips: every leaf cut
    along its longest axis that the chip count divides (none: replicated).
    Not the program's sharding rules; only somewhere to put 25 GB."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.asarray(devices), ("chips",))
    n = len(devices)

    def place(leaf: Any) -> Any:
        axes = [i for i, size in enumerate(leaf.shape) if size % n == 0]
        if not axes or n == 1:
            return NamedSharding(mesh, PartitionSpec())
        longest = max(axes, key=lambda i: leaf.shape[i])
        spec = [None] * len(leaf.shape)
        spec[longest] = "chips"
        return NamedSharding(mesh, PartitionSpec(*spec))

    return jax.tree.map(place, shapes)
