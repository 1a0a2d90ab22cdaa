"""Collective accounting: what the partitioner inserted between devices.

The AOT capture (telemetry/xla.py) fingerprints the *lowered* StableHLO —
the program the user wrote. The collectives live one stage later: GSPMD
inserts all-reduce / all-gather / reduce-scatter / all-to-all during SPMD
partitioning, so they only appear in the **compiled** HLO
(``compiled.as_text()``). This module parses that text into a structured
:class:`CollectiveSummary`:

- every collective op is counted and its payload sized from the result
  shape (the per-participant shard bytes — the number a cost model
  multiplies by the ring/latency factor);
- each op's ``replica_groups`` are matched against the mesh's logical
  axis structure, so a reduce is attributed to ``dp`` (or ``dp+fsdp`` for
  a grouped batch reduction), not to an opaque device list. Groups
  reference *logical* partition ids — positions in the flattened mesh
  device array — so the matching is mesh-order independent. Both HLO
  syntaxes are understood: explicit ``{{0,1},{2,3}}`` lists and the iota
  form ``[2,4]<=[4,2]T(1,0)``;
- the sorted (kind, axis, count, bytes) tuples hash into a
  **collective-structure fingerprint**: two rounds that compiled the same
  communication pattern share it, and drift on an unchanged program
  fingerprint means the partitioner changed its mind;
- :func:`comm_compute_fraction` turns total collective bytes plus the
  program's cost-analysis FLOPs into an analytic comm-vs-compute
  fraction: ``comm_s / (comm_s + compute_s)`` with
  ``comm_s = bytes / interconnect_bw`` and ``compute_s = flops / peak``.
  Both denominators carry provenance labels (telemetry/flops.py) — an
  assumed-bandwidth fraction must never masquerade as a measured one.

Everything degrades to no-ops: unparsable text yields an empty summary,
and an op whose groups match no axis subset is attributed to ``"other"``
rather than dropped — the byte count stays conserved.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import re
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

# HLO collective opcodes we account for. The async pairs
# (all-reduce-start / all-reduce-done) describe ONE transfer; only the
# -start (or the sync form) is counted.
COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")

# dtype token -> bytes per element. Anything unrecognized falls back to
# parsing the trailing bit-width (f8e4m3 -> 1, s4 -> 1 rounded up).
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?P<result>.*?)\s+"
    r"(?P<kind>" + "|".join(COLLECTIVE_KINDS) + r")"
    r"(?P<async>-start|-done)?\(")
_SHAPE_RE = re.compile(r"(?P<dtype>[a-z][a-z0-9]*)\[(?P<dims>[0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=(\{\{[^}]*(?:\},\{[^}]*)*\}\}|\{\})")
_IOTA_RE = re.compile(
    r"replica_groups=\[(?P<ng>\d+),(?P<gs>\d+)\]"
    r"<=\[(?P<dims>[\d,]+)\](?:T\((?P<perm>[\d,]+)\))?")
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")


def _dtype_bytes(token: str) -> int:
    size = _DTYPE_BYTES.get(token)
    if size is not None:
        return size
    m = re.search(r"(\d+)$", token)
    if m:
        return max(1, int(m.group(1)) // 8)
    return 4


def _shape_bytes(segment: str) -> float:
    """Total bytes of every shape token in an HLO result segment (handles
    tuple results of variadic all-reduces)."""
    total = 0.0
    for m in _SHAPE_RE.finditer(segment):
        n = 1
        dims = m.group("dims")
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _dtype_bytes(m.group("dtype"))
    return total


def _parse_explicit_groups(text: str) -> Optional[List[List[int]]]:
    m = _GROUPS_RE.search(text)
    if not m:
        return None
    body = m.group(1)
    if body == "{}":
        return []
    groups = []
    for grp in re.findall(r"\{([\d,]+)\}", body):
        groups.append([int(x) for x in grp.split(",")])
    return groups or None


def _parse_iota_groups(text: str) -> Optional[List[List[int]]]:
    """Expand the iota replica-group form ``[ng,gs]<=[dims]T(perm)``:
    ids = arange(prod(dims)).reshape(dims).transpose(perm).ravel(),
    then split into ng groups of gs."""
    m = _IOTA_RE.search(text)
    if not m:
        return None
    ng, gs = int(m.group("ng")), int(m.group("gs"))
    dims = [int(x) for x in m.group("dims").split(",")]
    perm = ([int(x) for x in m.group("perm").split(",")]
            if m.group("perm") else list(range(len(dims))))
    try:
        import numpy as np

        ids = np.arange(int(np.prod(dims))).reshape(dims)
        flat = ids.transpose(perm).ravel()
        return flat.reshape(ng, gs).tolist()
    except Exception:
        return None


def parse_replica_groups(line: str) -> Optional[List[List[int]]]:
    """Replica groups of one HLO op line, in either syntax; ``[]`` means
    "one group of everyone", None means the attribute is absent."""
    groups = _parse_explicit_groups(line)
    if groups is not None:
        return groups
    return _parse_iota_groups(line)


def _parse_permute_pairs(line: str) -> Optional[List[Tuple[int, int]]]:
    m = _PAIRS_RE.search(line)
    if not m:
        return None
    return [(int(a), int(b))
            for a, b in re.findall(r"\{(\d+),(\d+)\}", m.group(1))]


def mesh_axis_sizes(mesh: Any) -> Dict[str, int]:
    """``{axis: size}`` from a jax Mesh (or pass a dict through)."""
    if isinstance(mesh, Mapping):
        return {str(k): int(v) for k, v in mesh.items()}
    try:
        return {str(k): int(v) for k, v in dict(mesh.shape).items()}
    except Exception:
        return {}


def _axis_group_table(axis_sizes: Dict[str, int]
                      ) -> List[Tuple[str, frozenset]]:
    """(label, canonical group-set) for every subset of the mesh's
    non-trivial axes, over LOGICAL partition ids (positions in the
    flattened mesh device array — what replica_groups reference)."""
    try:
        import numpy as np
    except Exception:
        return []
    axes = [a for a, s in axis_sizes.items() if s > 1]
    if not axes:
        return []
    order = list(axis_sizes)
    shape = [axis_sizes[a] for a in order]
    ids = np.arange(int(np.prod(shape))).reshape(shape)
    table: List[Tuple[str, frozenset]] = []
    for mask in range(1, 1 << len(axes)):
        subset = [a for i, a in enumerate(axes) if mask & (1 << i)]
        keep = [i for i, a in enumerate(order) if a not in subset]
        vary = [i for i, a in enumerate(order) if a in subset]
        moved = np.transpose(ids, keep + vary)
        group_size = int(np.prod([shape[i] for i in vary]))
        groups = moved.reshape(-1, group_size)
        canon = frozenset(frozenset(int(x) for x in g) for g in groups)
        table.append(("+".join(a for a in order if a in subset), canon))
    return table


def _attribute_axis(groups: Optional[List[List[int]]],
                    table: List[Tuple[str, frozenset]],
                    n_partitions: int) -> str:
    """Label an op's replica groups with the mesh axis (or axis combo)
    they span. ``[]``/None means all partitions — the full-mesh combo."""
    if not table:
        return "other"
    if not groups:  # {} or absent: one group of everyone
        groups = [list(range(n_partitions))]
    canon = frozenset(frozenset(g) for g in groups)
    for label, axis_canon in table:
        if canon == axis_canon:
            return label
    return "other"


def _attribute_permute_axis(pairs: List[Tuple[int, int]],
                            table: List[Tuple[str, frozenset]]) -> str:
    """A collective-permute has source→target pairs, not groups: attribute
    it to the (unique, smallest) axis whose groups contain every pair —
    a ring shift along ``sp`` stays inside each ``sp`` group."""
    if not pairs:
        return "other"
    best: Optional[Tuple[int, str]] = None
    for label, canon in table:
        ok = all(any(s in g and t in g for g in canon) for s, t in pairs)
        if ok:
            width = sum(len(g) for g in canon) // max(1, len(canon))
            if best is None or width < best[0]:
                best = (width, label)
    return best[1] if best else "other"


@dataclasses.dataclass
class CollectiveSummary:
    """Counts and byte volumes of a compiled program's collectives,
    keyed ``{kind: {axis: {"count": n, "bytes": b}}}``."""

    ops: Dict[str, Dict[str, Dict[str, float]]] = dataclasses.field(
        default_factory=dict)
    n_partitions: int = 1

    def add(self, kind: str, axis: str, op_bytes: float) -> None:
        slot = self.ops.setdefault(kind, {}).setdefault(
            axis, {"count": 0, "bytes": 0.0})
        slot["count"] += 1
        slot["bytes"] += float(op_bytes)

    @property
    def total_ops(self) -> int:
        return int(sum(s["count"] for by_axis in self.ops.values()
                       for s in by_axis.values()))

    @property
    def total_bytes(self) -> float:
        return float(sum(s["bytes"] for by_axis in self.ops.values()
                         for s in by_axis.values()))

    def count(self, kind: str, axis: Optional[str] = None) -> int:
        by_axis = self.ops.get(kind, {})
        if axis is not None:
            return int(by_axis.get(axis, {}).get("count", 0))
        return int(sum(s["count"] for s in by_axis.values()))

    def bytes(self, kind: str, axis: Optional[str] = None) -> float:
        by_axis = self.ops.get(kind, {})
        if axis is not None:
            return float(by_axis.get(axis, {}).get("bytes", 0.0))
        return float(sum(s["bytes"] for s in by_axis.values()))

    def fingerprint(self) -> str:
        """sha256 over the sorted (kind, axis, count, bytes) structure —
        stable across runs that compiled the same communication pattern,
        different the moment the partitioner changes it."""
        rows = sorted(
            (kind, axis, int(s["count"]), int(s["bytes"]))
            for kind, by_axis in self.ops.items()
            for axis, s in by_axis.items())
        blob = json.dumps(rows, separators=(",", ":"))
        return hashlib.sha256(blob.encode("utf-8")).hexdigest()

    def as_dict(self) -> Dict[str, Any]:
        return {
            "n_partitions": self.n_partitions,
            "total_ops": self.total_ops,
            "total_bytes": self.total_bytes,
            "fingerprint": self.fingerprint()[:16],
            "ops": {k: {a: dict(s) for a, s in by_axis.items()}
                    for k, by_axis in self.ops.items()},
        }


def parse_hlo_collectives(hlo_text: str, mesh: Any = None
                          ) -> CollectiveSummary:
    """Parse compiled (post-SPMD) HLO text into a collective summary.

    ``mesh`` is a jax Mesh or an ``{axis: size}`` dict; without one, every
    op lands on axis ``"other"`` (counts/bytes still conserved). Each op
    definition is counted once — a collective inside a while body is one
    structural op, not one per iteration (this is the *structure*
    fingerprint, not an execution trace).
    """
    axis_sizes = mesh_axis_sizes(mesh) if mesh is not None else {}
    n_partitions = 1
    for s in axis_sizes.values():
        n_partitions *= max(1, s)
    table = _axis_group_table(axis_sizes)
    summary = CollectiveSummary(n_partitions=n_partitions)
    for line in hlo_text.splitlines():
        m = _OP_RE.match(line)
        if m is None or m.group("async") == "-done":
            continue
        kind = m.group("kind")
        op_bytes = _shape_bytes(m.group("result"))
        if kind == "collective-permute":
            pairs = _parse_permute_pairs(line)
            axis = (_attribute_permute_axis(pairs, table)
                    if pairs else "other")
        else:
            axis = _attribute_axis(parse_replica_groups(line), table,
                                   n_partitions)
        summary.add(kind, axis, op_bytes)
    return summary


def comm_compute_fraction(
        summary: CollectiveSummary, flops: Optional[float], *,
        interconnect_bytes_per_s: Optional[float],
        peak_flops_per_s: Optional[float]) -> Optional[float]:
    """Analytic comm-vs-compute fraction of one program execution:
    ``comm_s / (comm_s + compute_s)``. None when the program's FLOPs are
    unknown (no cost analysis) or the device has no published peaks — a
    fraction with a made-up term would be worse than no fraction."""
    if flops is None or flops <= 0:
        return None
    if (interconnect_bytes_per_s or 0) <= 0 or (peak_flops_per_s or 0) <= 0:
        return None
    comm_s = summary.total_bytes / interconnect_bytes_per_s
    compute_s = flops / peak_flops_per_s
    if comm_s + compute_s <= 0:
        return 0.0
    return comm_s / (comm_s + compute_s)


def export_collectives(summary: CollectiveSummary, registry: Any, *,
                       program: str, fingerprint: str = "",
                       comm_fraction: Optional[float] = None) -> None:
    """Land a summary in the metric registry: one labeled gauge child per
    (kind, axis) — gauges, not counters, because they describe the
    compiled program's static structure (latest compile wins), not an
    accumulating event stream."""
    if registry is None:
        return
    for kind, by_axis in summary.ops.items():
        for axis, s in by_axis.items():
            labels = {"kind": kind, "axis": axis, "program": program}
            registry.gauge(
                "xla_collective_ops_total",
                "collective ops in the compiled program, by kind and "
                "mesh axis", labels=labels).set(s["count"])
            registry.gauge(
                "xla_collective_bytes",
                "per-shard payload bytes of the compiled program's "
                "collectives, by kind and mesh axis",
                labels=labels).set(s["bytes"])
    if comm_fraction is not None:
        registry.gauge(
            "xla_comm_compute_fraction",
            "analytic comm/(comm+compute) time fraction per program",
            labels={"program": program,
                    "fingerprint": (fingerprint or "")[:16]},
        ).set(comm_fraction)


__all__ = [
    "COLLECTIVE_KINDS",
    "CollectiveSummary",
    "comm_compute_fraction",
    "export_collectives",
    "mesh_axis_sizes",
    "parse_hlo_collectives",
    "parse_replica_groups",
]
