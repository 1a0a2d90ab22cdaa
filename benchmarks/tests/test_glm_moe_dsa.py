"""The GLM-5.2 family through the benchmark (PR 33): a tiny configuration,
mix and cell under ``data/`` (files only; the real cell's five layers,
dense + ``full``, three expert layers that are ``shared`` and an expert
layer that is ``full``, at toy widths, with 16 experts of which experts
4..7 are held, 4 a token, and ``index_topk`` 32 so that prompts of 300-640
tokens select) run through ``harness/serve.py`` on the CPU; the same run
with the selection left out of the program, with the gates normalised over
the held experts only, and the fp8 control, each come out as not correct;
and a traced run yields every per-layer metric the real cell lists, the
new readers among them.

``harness/serve.py`` hands the reference no constants, so the reference's
defaults are the real cell's; the tiny cell's are bound here
(``reference()`` below), in the test and not through an option of the
harness.

The tiny cell computes in float32 (``tiny-glm.json`` says why), so its
program's ``served_token_logit_gap`` reads 0.0 on every seed tried (every
served token is the reference's first; seeds 1, 2, 3, 2147483653,
2147483675, 4000000123; my CPU runs, PR 33) where the fp8 control's over the
same six reads 2.73 at the least (2.73 5.20 5.35 4.16 2.75 3.48: at 16
experts and toy widths fp8 flips routings): the limit 0.006 is a hundred
times what the float32 serving tests hold a logit to.
"""
import functools
import json
import time
import types

import jax
import pytest

from benchmarks.harness import device, glm, scopes, serve, spec
from benchmarks.tests.conftest import DATA

ROOTS = (DATA, spec.BENCH_DIR)
FAKE_DEVICE = {"kind": "TPU v5 lite"}  # only the peak table is looked up
CELL = "tiny.serve-agent"
REAL_CELL = "glm-5.2.serve-agent-closed"
NEW_READERS = ("decode_dsa_index_device_ms", "decode_mla_attn_device_ms",
               "decode_moe_route_device_ms", "decode_moe_experts_device_ms",
               "prefill_dsa_index_device_ms", "prefill_mla_attn_device_ms",
               "dsa_selected_rows_pct", "moe_pairs_per_expert",
               "mla_attn_hbm_roofline_pct", "moe_experts_hbm_roofline_pct")
FROM_SPANS = ("dsa_selected_rows_pct", "moe_pairs_per_expert")


def reference(cell):
    """The cell's reference with the tiny cell's constants bound."""
    ref = spec.load_module("reference", cell.adapter().REFERENCE, cell.roots)
    c = cell.config
    return types.SimpleNamespace(teacher_forced_logits=functools.partial(
        ref.teacher_forced_logits, mlp_types=tuple(c["mlp_layer_types"]),
        indexer_types=tuple(c["indexer_types"]),
        index_topk=c["index_topk"],
        experts_per_token=c["num_experts_per_tok"],
        routed_scale=c["routed_scaling_factor"],
        first_expert=c["first_expert"],
        rope_theta=c["rope_parameters"]["rope_theta"],
        rms_eps=c["rms_norm_eps"], index_norm_eps=c["index_norm_eps"]))


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
    for name in ("mlp_layer_types", "indexer_types", "first_k_dense_replace",
                 "num_hidden_layers", "rope_parameters", "rms_norm_eps",
                 "routed_scaling_factor", "scoring_func", "topk_method",
                 "norm_topk_prob", "n_shared_experts", "index_norm_eps",
                 "first_published_layer"):
        assert tiny.config[name] == real.config[name], name


def test_real_configuration_is_the_catalogs_but_for_what_reduced_names():
    """Every number of the published ``config.json`` (the catalog's row,
    restated here) under its own key; the eight keys ``reduced`` names,
    each with its published value beside it; the deployment stated."""
    c = spec.load_cell(REAL_CELL).config
    assert c["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "mlp_layer_types",
        "indexer_types", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers", "max_position_embeddings"]
    published = dict(
        ep_size=1, head_dim=192, hidden_size=6144, index_head_dim=128,
        index_n_heads=32, index_skip_topk_offset=3, index_topk=2048,
        index_topk_freq=4, intermediate_size=12288, kv_lora_rank=512,
        moe_intermediate_size=2048, moe_layer_freq=1, n_group=1,
        n_shared_experts=1, num_attention_heads=64, num_experts_per_tok=8,
        num_key_value_heads=64, q_lora_rank=2048, qk_head_dim=256,
        qk_nope_head_dim=192, qk_rope_head_dim=64, rms_norm_eps=1e-05,
        routed_scaling_factor=2.5, topk_group=1, v_head_dim=256,
        first_k_dense_replace=3, n_routed_experts=256, vocab_size=154880,
        num_hidden_layers=78, num_nextn_predict_layers=1,
        max_position_embeddings=1048576)
    for name, value in published.items():
        if name in c["reduced"]:
            assert c[f"published_{name}"] == value, name
            assert c[name] != value, name
        else:
            assert c[name] == value, name
    assert c["rope_parameters"] == {"rope_theta": 8000000,
                                    "rope_type": "default"}
    assert (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["n_routed_experts"], c["vocab_size"],
            c["num_nextn_predict_layers"], c["max_position_embeddings"]
            ) == (5, 1, 16, 19360, 0, 32768)
    first = c["first_published_layer"]
    assert first == 2 and c["first_expert"] == 0
    assert c["mlp_layer_types"] == c["published_mlp_layer_types"][
        first:first + 5] == ["dense"] + ["sparse"] * 4
    assert c["indexer_types"] == c["published_indexer_types"][
        first:first + 5] == ["full", "shared", "shared", "shared", "full"]
    assert len(c["published_mlp_layer_types"]) \
        == len(c["published_indexer_types"]) == 78
    assert c["published_indexer_types"].count("full") == 3 + 18
    assert c["vocab_size"] * 8 == c["published_vocab_size"]
    assert "16" in c["deployment"] and "expert parallelism" in c["deployment"]
    assert all(k in c["reduced_why"] for k in c["reduced"])
    s = c["serving_sizes"]
    assert (s["max_batch"], s["kv_block_size"], s["chunk_prefill_len"],
            s["max_prefill_len"], s["min_prefill_len"]) == (16, 64, 2048,
                                                            2048, 512)
    assert "serving" not in c


def test_the_mix_is_the_issues_parameter_for_parameter():
    mix = spec.load_cell(REAL_CELL).traffic
    assert (mix["kind"], mix["loop"], mix["clients"]) == ("serve", "closed",
                                                          16)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 10240,
                                 "sigma": 0.5, "min": 4096, "max": 30720}
    assert mix["output_len"] == {"dist": "lognormal", "median": 768,
                                 "sigma": 0.5, "min": 256, "max": 2048}
    assert (mix["size_set"], mix["size_seed"], mix["n_requests"],
            mix["ramp_s"], mix["trace_seconds"], mix["check_requests"],
            mix["check_pad_to"]) == (8, 20260929, 512, 30, 4, 4, 32768)
    # the issue's 16 sizes and ramp_s 20 spread serve_tokens_per_s by 5.9 %
    # (its fallback, taken: size_set_why)
    assert "5.9 %" in mix["size_set_why"]
    # the longest prompt + output the mix can draw is the serving limit
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        == mix["check_pad_to"] \
        == spec.load_cell(REAL_CELL).config["max_position_embeddings"]


def test_tiny_cell_runs_and_is_correct(traced):
    cell, r = traced
    assert r["correct"], _failed(r)
    assert r["attempted"] >= 4 and r["failed"] == 0
    ctx = r["layer_context"]
    read = {n: spec.load_module("layer_metrics", n).read(ctx)
            for n in cell.per_layer}
    assert read["decode_step_ms"] > 0 and read["tpot_p50_ms"] > 0
    assert 0 < read["prefill_time_pct"] < 100
    # every prompt is past index_topk: 32 of the 300-700 positions cached
    assert 4 < read["dsa_selected_rows_pct"] < 12
    # two rows of 4 pairs a layer over 16 experts: 0.5 a held expert were
    # the routing even, and the toy's is not
    assert 0.1 < read["moe_pairs_per_expert"] < 1.0
    steps = glm.window_steps(ctx)
    assert steps and all(
        a["selected_rows"] == 32 * a["rows"] < a["kv_rows"]
        and 0 <= a["expert_hits"] <= min(a["expert_pairs"], 16)
        and a["expert_pairs"] <= a["rows"] * 16 for a in steps)
    registry = ctx["registry"]
    assert registry.get("serving_expert_pairs_total", 1) > 0
    # a CPU trace has no device plane: nothing to read, nothing raised
    for name in NEW_READERS:
        if name not in FROM_SPANS:
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
        3: (fusion, body + "attn/dsa_index/dot_general:"),
        4: (fusion, body + "attn/dsa_index/kv_cache/scatter:"),
        5: (fusion, body + "attn/mla_attn/gather:"),
        6: (fusion, body + "mlp/moe_route/sort:"),
        7: (fusion, body + "mlp/moe_experts/while/body/dot_general:"),
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
    ops += [(t, t + 0.6, 1), (t, t + 0.2, 3), (t + 0.2, t + 0.5, 5)]
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
    assert scopes.pool_shapes(cell.config) == []
    assert scopes.pool_shapes(spec.load_cell(REAL_CELL).config) == []
    parsed = _made_up_trace()
    monkeypatch.setattr(scopes, "for_cell", lambda ctx: parsed)
    monkeypatch.setitem(device.PEAKS, jax.devices()[0].device_kind,
                        {"hbm_bytes_per_s": 1e6})
    ctx = dict(r["layer_context"], memory_peak_bytes=12e9,
               trace={"chips": 1, "busy_s": 2.0, "window_s": 3.0})
    read = {n: spec.load_module("layer_metrics", n).read(ctx)
            for n in cell.per_layer}
    assert all(v is not None for v in read.values()), read
    assert read["decode_dsa_index_device_ms"] == pytest.approx(150.0)
    assert read["decode_mla_attn_device_ms"] == pytest.approx(100.0)
    assert read["decode_moe_route_device_ms"] == pytest.approx(40.0)
    assert read["decode_moe_experts_device_ms"] == pytest.approx(20.0)
    assert read["prefill_dsa_index_device_ms"] == pytest.approx(200.0)
    assert read["prefill_mla_attn_device_ms"] == pytest.approx(300.0)
    assert read["decode_kv_cache_device_ms"] == pytest.approx(100.0)
    assert read["decode_attn_device_ms"] == pytest.approx(300.0)
    assert read["decode_mlp_device_ms"] == pytest.approx(250.0)
    steps = glm.traced_steps(ctx, parsed)
    assert len(steps) == 3

    def mean(f):
        return sum(f(a, cell.config) for a in steps) / 3

    # tiny: 2 full layers of 8-wide keys, 5 layers of 16 + 4 wide latents;
    # 3 matrices of 64 x 32 an expert
    a = steps[0]
    assert glm.mla_step_bytes(a, cell.config) \
        == 2 * (a["kv_rows"] * 8 * 2 + a["selected_rows"] * 20 * 5)
    assert glm.experts_step_bytes(a, cell.config) \
        == 2 * a["expert_hits"] * 3 * 64 * 32
    assert read["mla_attn_hbm_roofline_pct"] == pytest.approx(
        100.0 * mean(glm.mla_step_bytes) / 1e6 / 0.25)
    assert read["moe_experts_hbm_roofline_pct"] == pytest.approx(
        100.0 * mean(glm.experts_step_bytes) / 1e6 / 0.02)


def test_readers_find_nothing_in_a_trace_without_the_scopes(traced,
                                                            monkeypatch):
    """A program that lacks the family (the parent commit) or a cell of
    another family: the new readers return None and raise nothing."""
    cell, r = traced
    parsed = _made_up_trace()
    parsed.op_meta["/device:TPU:0"] = {
        k: (line, path.replace("dsa_", "other_").replace("mla_", "other_")
            .replace("moe_", "other_"))
        for k, (line, path) in parsed.op_meta["/device:TPU:0"].items()}
    monkeypatch.setattr(scopes, "for_cell", lambda ctx: parsed)
    ctx = dict(r["layer_context"])
    ctx["spans"] = [(n, s, d, {k: v for k, v in a.items()
                               if k not in glm.STEP_ARGS})
                    for n, s, d, a in ctx["spans"]]
    for name in NEW_READERS:
        assert spec.load_module("layer_metrics", name).read(ctx) is None
    gpt = dict(r["layer_context"], cell=spec.load_cell("gpt2-xl.serve-closed"))
    for name in NEW_READERS:
        if name not in FROM_SPANS[:1]:  # rows over rows needs no config
            assert spec.load_module("layer_metrics", name).read(gpt) is None


def test_readers_know_the_bytes_a_step_has_to_read():
    """From the real cell's configuration: 2 ``full`` layers of 128-wide
    bfloat16 indexer keys, every cached position; 5 layers of 512 + 64
    wide latents (not the rows' 640), the chosen positions; three 6144 x
    2048 bfloat16 matrices for every held expert that got a pair."""
    real = spec.load_cell(REAL_CELL)
    a = {"kv_rows": 160000, "selected_rows": 32768, "expert_pairs": 64,
         "expert_hits": 26}
    assert glm.mla_step_bytes(a, real.config) \
        == 160000 * 128 * 2 * 2 + 32768 * 576 * 2 * 5
    assert glm.experts_step_bytes(a, real.config) \
        == 26 * 3 * 6144 * 2048 * 2
    ctx = {"kind": "serve", "cell": real, "spans": []}
    assert glm.window_steps(ctx) == []
    for name in FROM_SPANS:
        assert spec.load_module("layer_metrics", name).read(ctx) is None
    # 16 rows x 8 pairs x 4 layers over 64 held experts: 0.5 x 16 / 16
    ctx["spans"] = [("serving_decode_step", 1.0, 0.01,
                     {"rows": 16, "kv_rows": 160000,
                      "selected_rows": 32768}),
                    ("decode_commit", 1.0101, 0.001,
                     {"rows": 16, "expert_pairs": 32, "expert_hits": 26})]
    assert spec.load_module("layer_metrics", "moe_pairs_per_expert"
                            ).read(ctx) == 0.5
    assert spec.load_module("layer_metrics", "dsa_selected_rows_pct"
                            ).read(ctx) == pytest.approx(20.48)


def test_a_steps_counts_are_those_of_the_commit_that_followed_it():
    """The device's counts ride ``decode_commit``, which opens after the
    read-back; a step is given the first commit that starts once it has
    ended, and a step with no counts is left out."""
    spans = []
    for i in range(4):
        t = 10.0 + 0.02 * i
        spans += [("serving_decode_step", t, 0.012,
                   {"rows": 2, "kv_rows": 100 + i, "selected_rows": 64}),
                  ("decode_commit", t + 0.0121, 0.002,
                   {"rows": 2, "expert_pairs": i, "expert_hits": min(i, 1)})]
    spans.append(("serving_decode_step", 11.0, 0.012,
                  {"rows": 1, "kv_rows": 7, "selected_rows": 7}))
    ctx = {"kind": "serve", "spans": spans}
    steps = glm.window_steps(ctx)
    assert [(a["kv_rows"], a["expert_pairs"]) for a in steps] \
        == [(100 + i, i) for i in range(4)]
    parsed = scopes.Parsed.__new__(scopes.Parsed)
    parsed.host = [("serving_decode_step", 50.0, 0.0121),
                   ("serving_decode_step", 50.1, 0.0119)]
    assert [a["expert_pairs"] for a in glm.traced_steps(ctx, parsed)] \
        in ([0, 1], [1, 2], [2, 3])


def _altered_run():
    jax.clear_caches()  # the engine's programs were traced as they were
    try:
        return _run()[1]
    finally:
        jax.clear_caches()


def test_program_without_the_selection_is_not_correct(monkeypatch):
    """Every cached position attended past ``index_topk`` too, as a model
    with plain latent attention would: every prompt is past it, so the
    served tokens are no longer the reference's."""
    from determined_clone_tpu.models import glm_moe_dsa

    real = glm_moe_dsa.GLMMoeDsaConfig
    monkeypatch.setattr(
        glm_moe_dsa, "GLMMoeDsaConfig",
        lambda **kw: real(**{**kw, "index_topk": 1 << 20}))
    r = _altered_run()
    assert not r["correct"]
    # what the tokens attend decides where they are routed at toy widths:
    # the reference's own scores make too few of the program's choices,
    # and it compares nothing (NaN; no served token counted)
    assert _failed(r) == ["sample_holds_served_tokens",
                          "served_token_logit_gap"]


def test_gates_normalised_over_the_held_experts_only_are_not_correct(
        monkeypatch):
    """``g = 2.5 s / sum of the chosen s that are held here``: what a
    member that knew only its own experts would compute. The published
    normaliser runs over all eight (here four) chosen."""
    import jax.numpy as jnp

    from determined_clone_tpu.ops import moe

    real = moe.route

    def held_only(router, h, *, k, scale):
        experts, gates = real(router, h, k=k, scale=scale)
        held = (experts >= 4) & (experts < 8)
        mine = jnp.sum(jnp.where(held, gates, 0.0), axis=-1, keepdims=True)
        return experts, gates * scale / jnp.maximum(mine, 1e-9)

    monkeypatch.setattr(moe, "route", held_only)
    r = _altered_run()
    assert not r["correct"]
    # the first sparse layer's experts are the reference's; its output is
    # not, and the layers after it route what they are given
    assert _failed(r) == ["sample_holds_served_tokens",
                          "served_token_logit_gap"]


def test_program_that_routes_without_the_bias_is_not_correct(monkeypatch,
                                                             capsys):
    """The experts of largest ``s`` and not of largest ``s + b``. The
    reference takes the experts the program says it took
    (``reference/served.py``), so the program's logits are those of its
    own routing and tell nothing; what tells is the share of its choices
    that the reference's own scores make, under the floor here and 1.0000
    in the run as it is."""
    import jax.numpy as jnp

    from determined_clone_tpu.ops import moe

    real = moe.route
    monkeypatch.setattr(
        moe, "route", lambda router, h, **kw: real(
            {**router, "bias": jnp.zeros_like(router["bias"])}, h, **kw))
    r = _altered_run()
    said = [line for line in capsys.readouterr().out.splitlines()
            if "take the program's experts" in line]
    assert len(said) == 1 and "1.0000 of its" not in said[0]
    assert not r["correct"]
    assert _failed(r) == ["sample_holds_served_tokens",
                          "served_token_logit_gap"]


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 5])
def test_control_in_fp8_is_not_correct(seed, capsys):
    cell, r = _run(seed=seed, control="fp8")
    gap = r["control"]["served_token_logit_gap"]
    assert gap > 2 * cell.limits["served_token_logit_gap"], gap
    assert r["correct"], _failed(r)
    said = [line for line in capsys.readouterr().out.splitlines()
            if "take the program's experts" in line]
    # every checked request found its record; float32 flips no routing
    assert len(said) == 8 and all("1.0000 of its" in s for s in said)
