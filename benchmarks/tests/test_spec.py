"""BENCHMARK.json and the files it names agree."""
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import spec
from benchmarks.tests.conftest import REPO_ROOT


@pytest.fixture(scope="module")
def manifest():
    with open(spec.MANIFEST) as f:
        return json.load(f)


def test_every_cell_config_and_mix_has_its_file(manifest):
    for w in manifest["workloads"]:
        cell = spec.load_cell(w["name"], manifest=manifest)
        assert (cell.config_name, cell.traffic_name, cell.chips) == (
            w["config"], w["traffic"], w["chips"])
        assert cell.kind in ("train", "serve")
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        assert cell.per_layer and cell.limits
    for c in manifest["configs"]:
        with open(os.path.join(REPO_ROOT, c["file"])) as f:
            held = json.load(f)
        assert held["source"] == c["source"]
        assert held["reduced"] == c["reduced"]


def test_every_layer_metric_has_a_reader_that_agrees(manifest):
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        reader = spec.load_module("layer_metrics", m["name"])
        assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"])
        assert m["moves"] in e2e
        assert callable(reader.read)


def test_run_refuses_to_run_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "gpt2-medium.train", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=REPO_ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"correct"' not in proc.stdout
