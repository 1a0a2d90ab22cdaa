"""The EvaByte family through the benchmark (PR 27): a tiny configuration,
mix and cell under ``data/`` (files only; the published window of 2048 and
chunk of 16, which the reference is written for, at toy widths) run through
``harness/serve.py`` on the CPU; the same run with the chunk summaries left
out of the program, and the fp8 control, each come out as not correct; and
a traced run yields every per-layer metric the real cell lists, the new
readers among them.

The tiny cell's limit was set as the real one is (PERF.md, section 2), from
readings at the tiny size on the CPU (PR 27): the program's largest
``served_token_logit_gap`` over six seeds 0.0147, the fp8 control's
smallest over three 0.279 (seeds 1, 2, 3, 2147483653, 2147483675, 4000000123; the
control on the first, second and fourth).
"""
import json
import time

import jax
import pytest

from benchmarks.harness import device, eva, scopes, serve, spec
from benchmarks.tests.conftest import DATA

ROOTS = (DATA, spec.BENCH_DIR)
FAKE_DEVICE = {"kind": "TPU v5 lite"}  # only the peak table is looked up
CELL = "tiny.serve-doc"
REAL_CELL = "evabyte-6.5b.serve-doc-closed"


def _run(seed=2 ** 31 + 27, seconds=3.0, traced=False, **kw):
    cell = spec.load_cell(CELL, roots=ROOTS)
    return cell, serve.run(cell, seed, seconds, traced, time.monotonic(),
                           dict(FAKE_DEVICE), **kw)


def _failed(result):
    return [c["check"] for c in result["checks"] if not c["ok"]]


@pytest.fixture(scope="module")
def traced():
    return _run(traced=True)


def test_tiny_cell_lists_what_the_real_cell_lists():
    with open(spec.MANIFEST) as f:
        manifest = json.load(f)
    real = spec.load_cell(REAL_CELL, manifest=manifest)
    tiny = spec.load_cell(CELL, roots=ROOTS)
    assert tiny.per_layer == real.per_layer
    assert tiny.end_to_end == real.end_to_end == ["setup_s",
                                                  "serve_tokens_per_s"]
    for name in ("window_size", "chunk_size", "num_pred_heads",
                 "vocab_size", "rope_theta", "rms_norm_eps"):
        assert tiny.config[name] == real.config[name], name


def test_tiny_cell_runs_and_is_correct(traced):
    cell, r = traced
    assert r["correct"], _failed(r)
    assert r["attempted"] >= 4 and r["failed"] == 0
    assert r["values"]["serve_tokens_per_s"] > 0
    ctx = r["layer_context"]
    read = {n: spec.load_module("layer_metrics", n).read(ctx)
            for n in cell.per_layer}
    # what the program's spans and counters give is read on the CPU too
    assert read["decode_step_ms"] > 0 and read["tpot_p50_ms"] > 0
    assert 0 < read["prefill_time_pct"] < 100
    # every prompt is past one window: a fair share of rows are summaries
    assert 5 < read["eva_summary_rows_pct"] < 95
    steps = eva.window_steps(ctx)
    assert steps and all(a["summary_rows"] >= 128 * a["rows"]
                         for a in steps)
    # a CPU trace has no device plane: nothing to read, nothing raised
    for name in ("decode_eva_attn_device_ms", "eva_attn_hbm_roofline_pct",
                 "decode_eva_summarize_device_ms", "decode_attn_device_ms"):
        assert read[name] is None


def _made_up_trace(n_steps=3):
    """One chip; ``n_steps`` decode programs, each inside a
    ``serving_decode_step`` inside an ``engine_iteration``, with operations
    under every scope the EvaByte program names."""
    p = scopes.Parsed.__new__(scopes.Parsed)
    chip = "/device:TPU:0"
    body = "jit(forward_paged)/while/body/closed_call/"
    p.op_meta = {chip: {
        1: ("%w.1 = (s32[]) while((s32[]) %t), body=%b",
            "jit(forward_paged)/while:"),
        2: ("%f.2 = bf16[8,3072,64]{2,1,0} fusion(bf16[8]{0} %x), kind=kLoop",
            body + "attn/kv_cache/gather:"),
        3: ("%f.3 = f32[8,4,3072]{2,1,0} fusion(bf16[8]{0} %x), kind=kOutput",
            body + "attn/eva_attn/dot_general:"),
        4: ("%f.4 = bf16[8,1,64]{2,1,0} fusion(bf16[8]{0} %x), kind=kLoop",
            body + "attn/eva_summarize/reduce_sum:"),
        5: ("%f.5 = bf16[1024,64]{1,0} fusion(bf16[8]{0} %x), kind=kLoop",
            body + "attn/eva_summarize/kv_cache/scatter:"),
        6: ("%f.6 = f32[8,1,64]{2,1,0} fusion(bf16[8]{0} %x), kind=kOutput",
            body + "mlp/dot_general:"),
        7: ("%c.7 = f32[8,64]{1,0} copy(f32[8,64]{0,1} %x)", ""),
        8: ("%f.8 = f32[8,1,64]{2,1,0} fusion(bf16[8]{0} %x), kind=kOutput",
            body + "attn/dot_general:"),
    }}
    p.modules = {chip: []}
    p.host, ops = [], []
    for i in range(n_steps):
        t = 1.0 + i
        p.host += [("engine_iteration", t - 0.05, 0.9),
                   ("serving_decode_step", t - 0.02, 0.8)]
        p.modules[chip].append(("jit_forward_paged(1)", t, 0.7))
        ops += [(t, t + 0.6, 1), (t + 0.0, t + 0.2, 2), (t + 0.2, t + 0.3, 3),
                (t + 0.3, t + 0.32, 4), (t + 0.32, t + 0.35, 5),
                (t + 0.35, t + 0.5, 6), (t + 0.5, t + 0.55, 8),
                (t + 0.6, t + 0.65, 7)]
    p.ops = {chip: sorted(ops, key=lambda o: (o[0], -o[1]))}
    p.reductions = {}
    return p


def test_a_traced_run_yields_every_metric_the_cell_lists(traced,
                                                         monkeypatch):
    """With device events under the program's scopes in the trace (made
    up: the CPU records none), every per-layer metric of the cell reads a
    number — ``scopes.pool_shapes`` included, which reads GPT-2's key names
    from any configuration with a ``serving`` block and whose KeyError
    ``scopes.reduced`` would swallow, every scope metric with it."""
    cell, r = traced
    assert scopes.pool_shapes(cell.config) == []
    real = spec.load_cell(REAL_CELL)
    assert scopes.pool_shapes(real.config) == []
    with pytest.raises(KeyError):  # the trap, were the block named serving
        scopes.pool_shapes({"serving": real.config["serving_sizes"]})

    parsed = _made_up_trace()
    monkeypatch.setattr(scopes, "for_cell", lambda ctx: parsed)
    monkeypatch.setitem(device.PEAKS, jax.devices()[0].device_kind,
                        {"hbm_bytes_per_s": 1e6})
    ctx = dict(r["layer_context"], memory_peak_bytes=13e9,
               trace={"chips": 1, "busy_s": 2.0, "window_s": 3.0})
    read = {n: spec.load_module("layer_metrics", n).read(ctx)
            for n in cell.per_layer}
    assert all(v is not None for v in read.values()), read
    assert read["decode_eva_attn_device_ms"] == pytest.approx(100.0)
    assert read["decode_eva_summarize_device_ms"] == pytest.approx(50.0)
    # kv_cache: the gather and the summary's scatter; attn holds them all
    assert read["decode_kv_cache_device_ms"] == pytest.approx(230.0)
    assert read["decode_attn_device_ms"] == pytest.approx(400.0)
    assert read["decode_mlp_device_ms"] == pytest.approx(150.0)
    assert read["decode_unscoped_device_ms"] == pytest.approx(100.0)
    assert read["peak_hbm_gb.serve"] == 13.0
    # bytes of the three traced steps' rows over kv_cache + eva_attn time
    steps = eva.traced_steps(ctx, parsed)
    assert len(steps) == 3
    rows = sum(a["window_rows"] + a["summary_rows"] for a in steps) / 3
    needed = eva.attended_cache_bytes(rows, 64, 2)
    assert needed == rows * 2 * 64 * 2 * 2
    assert read["eva_attn_hbm_roofline_pct"] == pytest.approx(
        100.0 * needed / 1e6 / 0.33)


def test_readers_find_nothing_in_a_trace_without_the_scopes(traced,
                                                            monkeypatch):
    """A program that lacks the family (the parent commit) or a cell of
    another family: the new readers return None and raise nothing."""
    cell, r = traced
    parsed = _made_up_trace()
    parsed.op_meta["/device:TPU:0"] = {
        k: (line, path.replace("eva_", "other_"))
        for k, (line, path) in parsed.op_meta["/device:TPU:0"].items()}
    monkeypatch.setattr(scopes, "for_cell", lambda ctx: parsed)
    ctx = dict(r["layer_context"])
    ctx["spans"] = [(n, s, d, {k: v for k, v in a.items()
                               if not k.endswith("_rows")})
                    for n, s, d, a in ctx["spans"]]
    for name in ("decode_eva_attn_device_ms", "eva_summary_rows_pct",
                 "decode_eva_summarize_device_ms",
                 "eva_attn_hbm_roofline_pct"):
        assert spec.load_module("layer_metrics", name).read(ctx) is None


def test_traced_steps_are_matched_by_their_durations():
    parsed = scopes.Parsed.__new__(scopes.Parsed)
    parsed.host = [("serving_decode_step", 10.0, 0.030),
                   ("serving_decode_step", 10.1, 0.050)]
    args = [{"window_rows": i, "summary_rows": 0} for i in range(5)]
    durations = [0.041, 0.020, 0.0301, 0.0502, 0.041]
    ctx = {"spans": [("serving_decode_step", 100.0 + i, d, args[i])
                     for i, d in enumerate(durations)]}
    assert eva.traced_steps(ctx, parsed) == args[2:4]


def test_program_without_the_summaries_is_not_correct(monkeypatch):
    """The windows alone, as a model without EVA's second kind of state
    would attend: every prompt is past one window, so the served bytes are
    no longer the reference's."""
    from determined_clone_tpu.models import evabyte

    real = evabyte.eva_mask

    def window_only(cfg, positions, n_window_slots, n_summary_slots):
        mask = real(cfg, positions, n_window_slots, n_summary_slots)
        return mask.at[..., n_window_slots:].set(False)

    monkeypatch.setattr(evabyte, "eva_mask", window_only)
    jax.clear_caches()  # the engine's programs were traced with the mask
    try:
        _, r = _run()
    finally:
        jax.clear_caches()
    assert not r["correct"]
    assert _failed(r) == ["served_token_logit_gap"]


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 5])
def test_control_in_fp8_is_not_correct(seed):
    cell, r = _run(seed=seed, control="fp8")
    gap = r["control"]["served_token_logit_gap"]
    assert gap > cell.limits["served_token_logit_gap"], gap
    assert r["correct"], _failed(r)
