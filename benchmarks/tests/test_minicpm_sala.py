"""The MiniCPM-SALA family through the benchmark (PR 31): a tiny
configuration, mix and cell under ``data/`` (files only; the published
kernel 32, stride 16 and block 64 at toy widths, with top-k 4, window 64
and dense_len 256 so that prompts of 300-640 tokens select) run through
``harness/serve.py`` on the CPU; the same run with the selection left out
of the program, with the state not carried between slices, and the fp8
control, each come out as not correct; and a traced run yields every
per-layer metric the real cell lists, the new readers among them.

``harness/serve.py`` hands the reference no constants, so the reference's
defaults are the real cell's; the tiny cell's are bound here
(``reference()`` below), in the test and not through an option of the
harness.

The tiny cell's limit was set as the real one is (PERF.md, section 2), from
readings at the tiny size on the CPU (PR 31): the program's largest
``served_token_logit_gap`` over six seeds 0.00168, the fp8 control's
smallest over the same six 0.0258 (seeds 1, 2, 3, 2147483653, 2147483675,
4000000123).
"""
import dataclasses
import functools
import json
import time
import types

import jax
import jax.numpy as jnp
import pytest

from benchmarks.harness import device, sala, scopes, serve, spec
from benchmarks.tests.conftest import DATA

ROOTS = (DATA, spec.BENCH_DIR)
FAKE_DEVICE = {"kind": "TPU v5 lite"}  # only the peak table is looked up
CELL = "tiny.serve-long"
REAL_CELL = "minicpm-sala-9b.serve-long-closed"
NEW_READERS = ("decode_sparse_select_device_ms",
               "decode_sparse_attn_device_ms", "decode_lightning_device_ms",
               "prefill_lightning_device_ms", "sparse_selected_rows_pct",
               "sparse_attn_hbm_roofline_pct",
               "lightning_state_hbm_roofline_pct")


def reference(cell):
    """The cell's reference with the tiny cell's constants bound."""
    ref = spec.load_module("reference", cell.adapter().REFERENCE, cell.roots)
    c, s = cell.config, cell.config["sparse_config"]
    return types.SimpleNamespace(teacher_forced_logits=functools.partial(
        ref.teacher_forced_logits, mixers=tuple(c["mixer_types"]),
        dim_model_base=c["dim_model_base"],
        published_layers=c["published_num_hidden_layers"], topk=s["topk"],
        window=s["window_size"], dense_len=s["dense_len"]))


@pytest.fixture(autouse=True)
def tiny_constants(monkeypatch):
    monkeypatch.setattr(spec.Cell, "reference", reference)


def _run(seed=2 ** 31 + 31, seconds=3.0, traced=False, **kw):
    cell = spec.load_cell(CELL, roots=ROOTS)
    return cell, serve.run(cell, seed, seconds, traced, time.monotonic(),
                           dict(FAKE_DEVICE), **kw)


def _failed(result):
    return [c["check"] for c in result["checks"] if not c["ok"]]


@pytest.fixture(scope="module")
def traced():
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spec.Cell, "reference", reference)
        return _run(traced=True)


def test_tiny_cell_lists_what_the_real_cell_lists():
    with open(spec.MANIFEST) as f:
        manifest = json.load(f)
    real = spec.load_cell(REAL_CELL, manifest=manifest)
    tiny = spec.load_cell(CELL, roots=ROOTS)
    assert tiny.per_layer == real.per_layer
    assert set(NEW_READERS) <= set(real.per_layer)
    assert tiny.end_to_end == real.end_to_end == ["setup_s",
                                                  "serve_tokens_per_s"]
    for name in ("scale_emb", "scale_depth", "rope_theta", "rms_norm_eps",
                 "published_num_hidden_layers", "first_published_layer"):
        assert tiny.config[name] == real.config[name], name
    for name in ("kernel_size", "kernel_stride", "block_size"):
        assert tiny.config["sparse_config"][name] \
            == real.config["sparse_config"][name], name
    # the real file: the catalog's numbers but for what reduced names
    assert real.config["reduced"] == ["num_hidden_layers", "mixer_types",
                                      "max_position_embeddings"]
    assert real.config["mixer_types"] \
        == real.config["published_mixer_types"][9:17]
    assert (real.config["hidden_size"], real.config["num_attention_heads"],
            real.config["num_key_value_heads"], real.config["head_dim"],
            real.config["intermediate_size"], real.config["vocab_size"]
            ) == (4096, 32, 2, 128, 16384, 73448)


def test_tiny_cell_runs_and_is_correct(traced):
    cell, r = traced
    assert r["correct"], _failed(r)
    assert r["attempted"] >= 4 and r["failed"] == 0
    ctx = r["layer_context"]
    read = {n: spec.load_module("layer_metrics", n).read(ctx)
            for n in cell.per_layer}
    assert read["decode_step_ms"] > 0 and read["tpot_p50_ms"] > 0
    assert 0 < read["prefill_time_pct"] < 100
    # every prompt is past dense_len: 4 blocks of the 5-11 a sequence has
    assert 30 < read["sparse_selected_rows_pct"] < 80
    steps = sala.window_steps(ctx)
    assert steps and all(a["state_slots"] == a["rows"]
                         and a["selected_rows"] < a["kv_rows"]
                         for a in steps)
    # a CPU trace has no device plane: nothing to read, nothing raised
    for name in NEW_READERS:
        if name != "sparse_selected_rows_pct":
            assert read[name] is None, name


def _made_up_trace(n_steps=3):
    """One chip; ``n_steps`` decode programs, each inside a
    ``serving_decode_step``, and one prefill program inside a
    ``serving_prefill``, with operations under every scope the program
    names."""
    p = scopes.Parsed.__new__(scopes.Parsed)
    chip = "/device:TPU:0"
    body = "jit(forward_paged)/while/body/closed_call/"
    fusion = "%f = f32[8,64]{1,0} fusion(bf16[8]{0} %x), kind=kLoop"
    p.op_meta = {chip: {
        1: ("%w.1 = (s32[]) while((s32[]) %t), body=%b",
            "jit(forward_paged)/while:"),
        2: (fusion, body + "attn/kv_cache/scatter:"),
        3: (fusion, body + "attn/sparse_select/dot_general:"),
        4: (fusion, body + "attn/sparse_select/kv_cache/gather:"),
        5: (fusion, body + "attn/sparse_attn/gather:"),
        6: (fusion, body + "attn/lightning/mul:"),
        7: (fusion, body + "attn/lightning/kv_cache/scatter:"),
        8: (fusion, body + "mlp/dot_general:"),
        9: ("%c.9 = f32[8,64]{1,0} copy(f32[8,64]{0,1} %x)", ""),
    }}
    p.modules = {chip: []}
    p.host, ops = [], []
    for i in range(n_steps):
        t = 1.0 + i
        p.host += [("engine_iteration", t - 0.05, 0.9),
                   ("serving_decode_step", t - 0.02, 0.8)]
        p.modules[chip].append(("jit_forward_paged(1)", t, 0.7))
        ops += [(t, t + 0.6, 1), (t, t + 0.05, 2), (t + 0.05, t + 0.15, 3),
                (t + 0.15, t + 0.2, 4), (t + 0.2, t + 0.3, 5),
                (t + 0.3, t + 0.34, 6), (t + 0.34, t + 0.36, 7),
                (t + 0.36, t + 0.55, 8), (t + 0.6, t + 0.65, 9)]
    t = 0.1
    p.host += [("serving_prefill", t - 0.01, 0.8)]
    p.modules[chip].append(("jit_forward_paged(2)", t, 0.7))
    ops += [(t, t + 0.6, 1), (t, t + 0.25, 6), (t + 0.25, t + 0.5, 8)]
    p.ops = {chip: sorted(ops, key=lambda o: (o[0], -o[1]))}
    p.reductions = {}
    return p


def test_a_traced_run_yields_every_metric_the_cell_lists(traced,
                                                         monkeypatch):
    """With device events under the program's scopes in the trace (made
    up: the CPU records none), every per-layer metric of the cell reads a
    number, and the two shares are the counted bytes over the scopes'
    time."""
    cell, r = traced
    real = spec.load_cell(REAL_CELL)
    assert scopes.pool_shapes(cell.config) == []
    assert scopes.pool_shapes(real.config) == []
    parsed = _made_up_trace()
    monkeypatch.setattr(scopes, "for_cell", lambda ctx: parsed)
    monkeypatch.setitem(device.PEAKS, jax.devices()[0].device_kind,
                        {"hbm_bytes_per_s": 1e6})
    ctx = dict(r["layer_context"], memory_peak_bytes=7e9,
               trace={"chips": 1, "busy_s": 2.0, "window_s": 3.0})
    read = {n: spec.load_module("layer_metrics", n).read(ctx)
            for n in cell.per_layer}
    assert all(v is not None for v in read.values()), read
    assert read["decode_sparse_select_device_ms"] == pytest.approx(150.0)
    assert read["decode_sparse_attn_device_ms"] == pytest.approx(100.0)
    assert read["decode_lightning_device_ms"] == pytest.approx(60.0)
    assert read["prefill_lightning_device_ms"] == pytest.approx(250.0)
    assert read["decode_kv_cache_device_ms"] == pytest.approx(120.0)
    assert read["decode_attn_device_ms"] == pytest.approx(360.0)
    assert read["decode_mlp_device_ms"] == pytest.approx(190.0)
    steps = sala.traced_steps(ctx, parsed)
    assert len(steps) == 3
    cached = sum(a["kv_rows"] for a in steps) / 3
    chosen = sum(a["selected_rows"] for a in steps) / 3
    slots = sum(a["state_slots"] for a in steps) / 3
    # tiny: 2 sparse layers of 2 x 16 values a row, 2 lightning layers of
    # 4 heads of 16 x 16
    assert sala.sparse_step_bytes(cached, chosen, cell.config) \
        == (cached / 16 + 2 * chosen) * 32 * 2 * 2
    assert sala.state_step_bytes(slots, cell.config) \
        == 2 * slots * 4 * 16 * 16 * 4 * 2
    assert read["sparse_attn_hbm_roofline_pct"] == pytest.approx(
        100.0 * sala.sparse_step_bytes(cached, chosen, cell.config)
        / 1e6 / 0.25)
    assert read["lightning_state_hbm_roofline_pct"] == pytest.approx(
        100.0 * sala.state_step_bytes(slots, cell.config) / 1e6 / 0.06)


def test_readers_find_nothing_in_a_trace_without_the_scopes(traced,
                                                            monkeypatch):
    """A program that lacks the family (the parent commit) or a cell of
    another family: the new readers return None and raise nothing."""
    cell, r = traced
    parsed = _made_up_trace()
    parsed.op_meta["/device:TPU:0"] = {
        k: (line, path.replace("sparse_", "other_").replace("lightning",
                                                            "other"))
        for k, (line, path) in parsed.op_meta["/device:TPU:0"].items()}
    monkeypatch.setattr(scopes, "for_cell", lambda ctx: parsed)
    ctx = dict(r["layer_context"])
    ctx["spans"] = [(n, s, d, {k: v for k, v in a.items()
                               if k not in sala.ROW_ARGS})
                    for n, s, d, a in ctx["spans"]]
    for name in NEW_READERS:
        assert spec.load_module("layer_metrics", name).read(ctx) is None
    gpt = dict(ctx, cell=spec.load_cell("gpt2-xl.serve-closed"))
    for name in NEW_READERS:
        assert spec.load_module("layer_metrics", name).read(gpt) is None


def test_readers_know_the_bytes_a_step_has_to_move():
    """From the real cell's configuration: 2 sparse layers of 256-wide
    bfloat16 rows, a compressed key per 16 positions; 6 lightning layers of
    [32, 128, 128] float32 states, once in and once out."""
    real = spec.load_cell(REAL_CELL)
    assert sala.sparse_step_bytes(16000, 4096, real.config) \
        == (1000 + 8192) * 256 * 2 * 2
    assert sala.state_step_bytes(8, real.config) \
        == 2 * 8 * 32 * 128 * 128 * 4 * 6
    ctx = {"kind": "serve", "cell": real, "spans": []}
    assert sala.window_steps(ctx) == []
    assert spec.load_module("layer_metrics",
                            "sparse_selected_rows_pct").read(ctx) is None


def test_traced_steps_are_matched_by_their_durations():
    parsed = scopes.Parsed.__new__(scopes.Parsed)
    parsed.host = [("serving_decode_step", 10.0, 0.030),
                   ("serving_decode_step", 10.1, 0.050)]
    args = [{"kv_rows": i, "selected_rows": i, "state_slots": 1}
            for i in range(5)]
    durations = [0.041, 0.020, 0.0301, 0.0502, 0.041]
    ctx = {"spans": [("serving_decode_step", 100.0 + i, d, args[i])
                     for i, d in enumerate(durations)]}
    assert sala.traced_steps(ctx, parsed) == args[2:4]
    ctx["spans"][2] = ("serving_decode_step", 102.0, 0.0301, {"rows": 1})
    assert sala.traced_steps(ctx, parsed) == args[3:4]


def _altered_run():
    jax.clear_caches()  # the engine's programs were traced as they were
    try:
        return _run()[1]
    finally:
        jax.clear_caches()


def test_program_without_the_selection_is_not_correct(monkeypatch):
    """Every cached block attended past ``dense_len`` too, as a model with
    plain grouped-query attention would: every prompt is past it, so the
    served tokens are no longer the reference's."""
    from determined_clone_tpu.ops import sparse_attention

    real = sparse_attention.sparse_select

    def dense(q, kc, positions, token_mask, sp):
        return real(q, kc, positions, token_mask,
                    dataclasses.replace(sp, dense_len=1 << 20))

    from determined_clone_tpu.models import minicpm_sala

    monkeypatch.setattr(minicpm_sala, "sparse_select", dense)
    r = _altered_run()
    assert not r["correct"]
    assert _failed(r) == ["served_token_logit_gap"]


def test_program_that_drops_the_state_between_slices_is_not_correct(
        monkeypatch):
    """Every prefill slice and decode step starts its lightning layers
    from a zero state, as if a sequence's state were not carried."""
    from determined_clone_tpu.models import minicpm_sala

    real = minicpm_sala._lightning_layer
    monkeypatch.setattr(
        minicpm_sala, "_lightning_layer",
        lambda *a: real(*a[:-1], jnp.ones_like(a[-1])))
    r = _altered_run()
    assert not r["correct"]
    assert _failed(r) == ["served_token_logit_gap"]


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 5])
def test_control_in_fp8_is_not_correct(seed):
    cell, r = _run(seed=seed, control="fp8")
    gap = r["control"]["served_token_logit_gap"]
    assert gap > 2 * cell.limits["served_token_logit_gap"], gap
    assert r["correct"], _failed(r)
