"""Finding a cell's files by name.

``BENCHMARK.json`` (repo root) lists cells, configurations and metrics; what
belongs to one of them sits in a file of its own under ``benchmarks/``:

    workloads/<cell>.json   configs/<config>.json   traffic/<mix>.json
    layer_metrics/<metric>.py   adapters/<family>.py   reference/<name>.py

A later PR adds files and ``BENCHMARK.json`` entries and edits none. The
tests keep a tiny configuration, mixes and cells of their own under
``tests/data``, found the same way through ``roots``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List, Optional, Sequence

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)
MANIFEST = os.path.join(REPO_ROOT, "BENCHMARK.json")


class SpecError(Exception):
    """A cell, file or key the benchmark was asked for does not exist."""


def _find(kind: str, name: str, ext: str, roots: Sequence[str]) -> str:
    for root in roots:
        path = os.path.join(root, kind, name + ext)
        if os.path.isfile(path):
            return path
    raise SpecError(f"no {kind}/{name}{ext} under {list(roots)}")


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, roots: Sequence[str] = (BENCH_DIR,)
                ) -> Any:
    """Import ``<kind>/<name>.py`` by path (names may hold dots)."""
    path = _find(kind, name, ".py", roots)
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    mesh: Dict[str, int]
    limits: Dict[str, float]
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[str]
    per_layer: List[str]
    roots: Sequence[str]

    @property
    def kind(self) -> str:
        return self.traffic["kind"]

    def adapter(self) -> Any:
        return load_module("adapters", self.config["adapter"], self.roots)

    def reference(self) -> Any:
        return load_module("reference", self.adapter().REFERENCE, self.roots)


def _metrics_for(entries: List[Dict[str, Any]], cell: str) -> List[str]:
    return [m["name"] for m in entries
            if "workloads" not in m or cell in m["workloads"]]


def load_cell(name: str, roots: Sequence[str] = (BENCH_DIR,),
              manifest: Optional[Dict[str, Any]] = None) -> Cell:
    """The cell's own file, its configuration and its traffic mix, plus the
    metric names the manifest gives it. ``manifest`` defaults to
    ``BENCHMARK.json``; a cell the manifest does not list (the tests' tiny
    ones) names its metrics in its own file."""
    w = _load_json(_find("workloads", name, ".json", roots))
    if manifest is None:
        manifest = _load_json(MANIFEST)
    listed = {c["name"] for c in manifest.get("workloads", [])}
    if name in listed:
        end_to_end = _metrics_for(manifest["end_to_end"], name)
        per_layer = _metrics_for(manifest["per_layer"], name)
    else:
        end_to_end, per_layer = w["end_to_end"], w["per_layer"]
    return Cell(
        name=name, chips=int(w["chips"]), mesh=dict(w.get("mesh", {})),
        limits={k: float(v) for k, v in w["limits"].items()},
        config_name=w["config"],
        config=_load_json(_find("configs", w["config"], ".json", roots)),
        traffic_name=w["traffic"],
        traffic=_load_json(_find("traffic", w["traffic"], ".json", roots)),
        end_to_end=list(end_to_end), per_layer=list(per_layer), roots=roots)
