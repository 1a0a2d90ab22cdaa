"""The scope reader (``harness/scopes.py``) and the per-layer metrics built
on it: on a trace recorded on a v5e chip with the program's scopes and span
annotations in it (``data/probe2.xplane.pb`` + ``data/probe2.spans.json``:
``tools/probe_scopes.py``, three train steps and two paged decode steps at a
small width), on made-up events, and in a rehearsal of the tiny CPU cells
that name the new metrics."""
import json
import os
import time

import pytest

from benchmarks.harness import scopes, serve, spec, trace, train
from benchmarks.tests.conftest import DATA

PROBE = os.path.join(DATA, "probe2.xplane.pb")
# the probe's pool: 2 layers x 64 blocks x 16 positions x 8 heads x 64
PROBE_POOL = ["[2,64,16,8,64]", "[1,64,16,8,64]", "[64,16,8,64]",
              "[1024,8,64]"]
ROOTS = (DATA, spec.BENCH_DIR)


@pytest.fixture(scope="module")
def parsed():
    return scopes.load(PROBE)


@pytest.fixture(scope="module")
def recorded_spans():
    with open(os.path.join(DATA, "probe2.spans.json")) as f:
        return json.load(f)


def test_probe_is_small_and_parsed_once(parsed):
    assert os.path.getsize(PROBE) < 1_000_000
    assert scopes.load(PROBE) is parsed


def test_buckets_partition_the_busy_time_of_the_recorded_trace(parsed):
    """Both step programs together are all that ran: their buckets add up
    to ``reduce_trace``'s busy time within 1 %."""
    busy = trace.reduce_trace(trace.load_xplane(PROBE))["busy_s"]
    total = 0.0
    for span in ("train_dispatch", "serving_decode_step"):
        r = scopes.reduce_scopes(parsed, span, PROBE_POOL)
        assert sum(r["buckets"].values()) == pytest.approx(r["busy_s"],
                                                           rel=1e-9)
        total += r["busy_s"] * r["steps"]
    assert total == pytest.approx(busy, rel=0.01)


def test_train_step_of_the_recorded_trace(parsed):
    r = scopes.reduce_scopes(parsed, "train_dispatch")
    assert r["chips"] == 1 and r["steps"] == 3
    b = r["buckets"]
    for name in ("embed", "attn", "mlp", "logits", "optimizer"):
        assert b[name] > 0, name
    assert b["kv_cache"] == 0.0
    # the kernel is found by its name, inside attn, in the forward and in
    # remat's second forward
    assert 0 < r["parts"]["flash_fwd"] < b["attn"]
    kernel_events = {line for line, _ in parsed.op_meta[
        "/device:TPU:0"].values() if line.startswith("%flash_fwd")}
    assert len(kernel_events) == 2
    assert b["unscoped"] < 0.25 * r["busy_s"]
    # the program's own duration on XLA Modules covers its operations
    assert r["busy_s"] <= r["step_program_s"] < 1.2 * r["busy_s"]


def test_decode_step_of_the_recorded_trace(parsed):
    r = scopes.reduce_scopes(parsed, "serving_decode_step", PROBE_POOL)
    assert r["steps"] == 2
    b, parts = r["buckets"], r["parts"]
    assert b["attn"] > 0 and b["mlp"] > 0 and b["optimizer"] == 0.0
    assert 0 < parts["kv_cache"] <= b["attn"]
    assert b["kv_cache"] == 0.0  # always inside attn: a part, not a bucket
    assert parts["pool_copy"] <= b["unscoped"]
    idle = scopes.decode_host_idle(parsed)
    assert idle["steps"] == 2
    # each iteration sleeps 1 ms in decode_commit with the device idle
    assert idle["idle_s"] / idle["steps"] > 1e-3


def test_annotation_and_sync_mark_place_a_span_alike(parsed, recorded_spans):
    """The accepted reducer places the program's spans through one sync
    mark; the new readers read the spans' own annotations. On the recorded
    trace the two placements of every span differ by under 200 us."""
    (sync_trace,) = [s for n, s, _ in parsed.host if n == trace.SYNC_MARK]
    shift = sync_trace - recorded_spans["sync_monotonic"]
    checked = 0
    for name in ("train_dispatch", "engine_iteration", "decode_readback"):
        by_mark = sorted(s + shift for n, s, _ in recorded_spans["spans"]
                         if n == name)
        by_annotation = sorted(s for s, _ in parsed.spans(name))
        assert len(by_mark) == len(by_annotation) > 0
        for a, b in zip(by_mark, by_annotation):
            assert abs(a - b) < 200e-6, (name, a - b)
            checked += 1
    assert checked == 7


# -- made-up events ----------------------------------------------------------

@pytest.mark.parametrize("path,names", [
    ("jit(step_fn)/jvp()/while/body/closed_call/attn/dot_general:", ["attn"]),
    ("jit(step_fn)/transpose(jvp(logits))/mul:", ["logits"]),
    ("jit(f)/while/body/closed_call/attn/kv_cache/gather:",
     ["attn", "kv_cache"]),
    ("jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attn/flash_fwd/pallas_call:",
     ["attn", "flash_fwd"]),
    ("jit(step_fn)/optimizer/jit(_where)/select_n:", ["optimizer"]),
    ("jit(attn)/while/body/dynamic_update_slice:", []),  # a jit's name is
    ("", []),                                            # not a scope
])
def test_scope_names_on_a_path(path, names):
    assert scopes.scope_names(path) == names


def test_self_times_add_up_to_the_union():
    # a while from 0 to 10 with two operations inside, then one after it
    ops = [(0.0, 10.0, 1), (1.0, 4.0, 2), (5.0, 9.0, 3), (12.0, 13.0, 4)]
    assert scopes._self_times(ops) == [(2, 3.0), (3, 4.0), (1, 3.0),
                                       (4, 1.0)]
    assert sum(t for _, t in scopes._self_times(ops)) == 11.0


def test_pool_shapes_and_copies():
    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "gpt2-medium.json")) as f:
        shapes = scopes.pool_shapes(json.load(f))
    assert shapes == ["[24,2048,16,16,64]", "[1,2048,16,16,64]",
                      "[2048,16,16,64]", "[32768,16,64]"]
    is_copy = scopes._is_pool_copy
    assert is_copy("copy", "copy.37", "copy copy.37 bf16[1,2048,16,16,64]",
                   shapes)
    assert is_copy("fusion", "constant_dynamic-update-slice_fusion.4",
                   "fusion constant_dynamic-update-slice_fusion.4 "
                   "bf16[24,2048,16,16,64]", shapes)
    assert not is_copy("fusion", "fusion.191",
                       "fusion fusion.191 bf16[1,2048,16,16,64]", shapes)
    assert not is_copy("copy", "copy.1", "copy copy.1 bf16[24,1024,4096]",
                       shapes)
    assert scopes.pool_shapes({"n_layer": 2}) == []


def _made_up(with_spans=True):
    """One chip, two executions of a step program and one of another
    program outside any step span."""
    p = scopes.Parsed.__new__(scopes.Parsed)
    chip = "/device:TPU:0"
    p.modules = {chip: [("jit_step(1)", 1.0, 1.0), ("jit_step(1)", 3.0, 1.0),
                        ("jit_other(2)", 5.0, 1.0)]}
    p.op_meta = {chip: {
        1: ("%w.1 = (s32[]) while((s32[]) %t), body=%b", "jit(step)/while:"),
        2: ("%f.2 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop",
            "jit(step)/while/body/attn/kv_cache/gather:"),
        3: ("%flash_fwd.3 = f32[8]{0} custom-call(f32[8]{0} %x), "
            'custom_call_target="tpu_custom_call"',
            "jit(step)/while/body/attn/flash_fwd/pallas_call:"),
        4: ("%copy.4 = f32[2,4,2,2,2]{4,3,2,1,0} copy(f32[2,4,2,2,2] %p)",
            ""),
        5: ("%a.5 = f32[8]{0} add(f32[8]{0} %x, f32[8]{0} %y)",
            "jit(other)/mlp/add:"),
    }}
    ops = []
    for t in (1.0, 3.0):
        ops += [(t, t + 0.8, 1), (t + 0.1, t + 0.3, 2), (t + 0.3, t + 0.6, 3),
                (t + 0.8, t + 0.9, 4)]
    ops.append((5.0, 5.5, 5))
    p.ops = {chip: sorted(ops, key=lambda o: (o[0], -o[1]))}
    p.host = [("train_dispatch", 0.9, 1.2), ("train_dispatch", 2.9, 1.2)] \
        if with_spans else []
    return p


def test_reduce_scopes_on_made_up_events():
    r = scopes.reduce_scopes(_made_up(), "train_dispatch", ["[2,4,2,2,2]"])
    assert r["steps"] == 2 and r["chips"] == 1
    assert r["step_program_s"] == pytest.approx(1.0)
    b = r["buckets"]
    # the while keeps only what its body does not cover; the program that
    # ran outside the step spans is not counted
    assert b["attn"] == pytest.approx(0.5)
    assert b["unscoped"] == pytest.approx(0.3 + 0.1)
    assert b["mlp"] == 0.0
    assert r["busy_s"] == pytest.approx(0.9)
    assert r["parts"] == {"flash_fwd": pytest.approx(0.3),
                          "kv_cache": pytest.approx(0.2),
                          "pool_copy": pytest.approx(0.1)}
    assert [k.split()[1] for k, _ in r["unscoped_ops"]] == ["w.1"]
    assert r["pool_copy_ops"] == [["copy copy.4 f32[2,4,2,2,2]",
                                   pytest.approx(0.1)]]


def test_nothing_to_read_is_none_not_an_error():
    # a parent commit's trace: device events, but no span annotations
    assert scopes.reduce_scopes(_made_up(with_spans=False),
                                "train_dispatch") is None
    assert scopes.decode_host_idle(_made_up()) is None
    # a cell that was never traced
    cell = spec.load_cell("tiny.train-scopes", roots=ROOTS)
    cell.name = "no-such-cell"
    assert scopes.reduced({"cell": cell, "kind": "train"}) is None


# -- the tiny cells that name the new metrics -------------------------------

@pytest.mark.parametrize("module,cell_name", [
    (train, "tiny.train-scopes"), (serve, "tiny.serve-scopes")])
def test_rehearsal_reads_every_new_metric(module, cell_name):
    """A whole traced run on the CPU: each new reader gives a number or
    None (a CPU trace has no device plane) and none raises."""
    cell = spec.load_cell(cell_name, roots=ROOTS)
    r = module.run(cell, 2 ** 31 + 25, 2.0, True, time.monotonic(),
                   {"kind": "TPU v5 lite"})
    assert r["correct"], [c for c in r["checks"] if not c["ok"]]
    ctx = r["layer_context"]
    read = {n: spec.load_module("layer_metrics", n).read(ctx)
            for n in cell.per_layer}
    assert all(v is None or v >= 0 for v in read.values()), read
    # the CPU trace holds the spans' annotations, on the trace's clock
    parsed = scopes.for_cell(ctx)
    assert parsed.spans(scopes.STEP_SPAN[cell.kind])
    device = [n for n in read if n.endswith("_device_ms")]
    assert device and all(read[n] is None for n in device)
    if cell.kind == "serve":
        assert read["token_gap_p99_ms"] > 0
        assert 0 < read["prefill_time_pct"] < 100
        assert read["decode_host_ms"] is None
        phases = {"admit", "engine_iteration", "decode_prepare",
                  "decode_dispatch", "decode_readback", "decode_commit"}
        assert phases <= {s[0] for s in ctx["spans"]}
    else:
        assert read["collective_time_pct"] is None
