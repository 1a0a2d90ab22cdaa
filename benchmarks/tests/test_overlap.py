"""The reader of ``decode_overlap_pct`` (PR 34) on hand-made span records:
the share of the window's ``serving_decode_step`` spans whose ``overlapped``
is 1; nothing where no span carries the arg (the parent's program), where
the window holds no decode step, or in a training cell.
"""
import json

import pytest

from benchmarks.harness import spec

NAME = "decode_overlap_pct"
CELLS = ("gpt2-medium.serve-closed", "gpt2-xl.serve-closed",
         "evabyte-6.5b.serve-doc-closed", "minicpm-sala-9b.serve-long-closed",
         "glm-5.2.serve-agent-closed")  # every serve cell (PR 39)


def _read(spans, kind="serve"):
    return spec.load_module("layer_metrics", NAME).read(
        {"kind": kind, "spans": spans})


def _steps(flags, **more):
    return [("serving_decode_step", 100.0 + i, 0.003,
             dict({"batch": 32, "rows": 20, "overlapped": o}, **more))
            for i, o in enumerate(flags)]


@pytest.mark.parametrize("flags,share", [
    ([1, 1, 1, 1], 100.0), ([0, 0, 0], 0.0), ([0, 1, 1, 0, 1, 1, 1, 1], 75.0)],
    ids=["all", "none", "drained_twice"])
def test_share_of_the_steps_dispatched_ahead(flags, share):
    other = [("decode_commit", 100.0, 0.001, {"rows": 20, "overlapped": 1}),
             ("serving_prefill", 101.0, 0.01, {"batch": 1, "length": 128})]
    assert _read(_steps(flags) + other) == pytest.approx(share)


@pytest.mark.parametrize("spans,kind", [
    ([], "serve"),
    ([("serving_decode_step", 100.0, 0.003, {"batch": 32, "rows": 20})],
     "serve"),
    ([("serving_prefill", 100.0, 0.01, {"batch": 1})], "serve"),
    (_steps([1, 1]), "train")],
    ids=["no_spans", "the_parents_spans", "no_decode_step", "a_train_cell"])
def test_nothing_to_read_is_none_not_an_error(spans, kind):
    assert _read(spans, kind) is None


def test_the_manifest_lists_the_reader_as_it_describes_itself():
    with open(spec.MANIFEST) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    reader = spec.load_module("layer_metrics", NAME)
    assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"])
    assert entry["better"] == "higher"
    assert tuple(entry["workloads"]) == CELLS
    assert manifest["per_layer"][-1] is entry  # appended, nothing moved
    for cell in entry["workloads"]:
        assert NAME in spec.load_cell(cell, manifest=manifest).per_layer
