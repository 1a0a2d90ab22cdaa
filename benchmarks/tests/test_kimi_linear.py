"""The Kimi-Linear family through the benchmark (PR 41): a tiny
configuration, mix and cell under ``data/`` (files only; the real cell's
five layers, KDA + dense, two KDA + experts, MLA + experts, KDA + experts,
at toy widths, with 16 experts of which experts 8..15 are held, 4 a token)
run through ``harness/serve.py`` on the CPU, every prompt three to five
slices of 128; the same run with the state or the decay held in bfloat16
and with the delta correction left out of the program, and the reference's
own controls (fp8, a bfloat16 decay, a bfloat16 state, no delta
correction), each come out as not correct;
and a traced run yields every per-layer metric the real cell lists, the new
readers among them.

``harness/serve.py`` hands the reference no constants, so the reference's
defaults are the real cell's; the tiny cell's are bound here
(``reference()`` below), in the test and not through an option of the
harness.

The tiny cell computes in float32 (``tiny-kimi.json`` says why), so its
program's ``served_token_logit_gap`` reads 0.0 on every seed tried (every
served token is the reference's first; seeds 7, 2147483653, 2147483689; my
CPU runs, PR 41). The limit is 0.0005, ten times what the float32 serving
tests hold a logit to and a twelfth of the tiny GLM cell's 0.006, because at
toy widths (heads of 16, 300-640 positions) a state or a decay held in
bfloat16 moves a logit by little: the program with its state rounded
wherever it is handed on reads 0.0011-0.0088 (by which requests the window
holds), with its decays rounded 0.0099; the reference's controls (seed 7)
``fp8`` 0.77, ``no_delta`` 3.0-3.5.
"""
import functools
import json
import time
import types

import jax
import jax.numpy as jnp
import pytest

from benchmarks.harness import device, glm, kda, scopes, serve, spec
from benchmarks.tests.conftest import DATA

ROOTS = (DATA, spec.BENCH_DIR)
FAKE_DEVICE = {"kind": "TPU v5 lite"}  # only the peak table is looked up
CELL = "tiny.serve-longdoc"
REAL_CELL = "kimi-linear-48b-a3b.serve-longdoc-closed"
NEW_READERS = ("decode_kda_device_ms", "decode_kda_conv_device_ms",
               "prefill_kda_device_ms", "kda_state_hbm_roofline_pct",
               "prefill_kda_roofline_pct", "mla_dense_attn_hbm_roofline_pct",
               "moe_held_experts_hbm_roofline_pct",
               "moe_held_pairs_per_expert")
FROM_SPANS = ("moe_held_pairs_per_expert",)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def reference(cell):
    """The cell's reference with the tiny cell's constants bound."""
    ref = spec.load_module("reference", cell.adapter().REFERENCE, cell.roots)
    c = cell.config
    return types.SimpleNamespace(teacher_forced_logits=functools.partial(
        ref.teacher_forced_logits,
        experts_per_token=c["num_experts_per_token"],
        routed_scale=c["routed_scaling_factor"],
        first_expert=c["first_expert"], rms_eps=c["rms_norm_eps"],
        l2_eps=c["l2_norm_eps"]))


@pytest.fixture(autouse=True)
def tiny_constants(monkeypatch):
    monkeypatch.setattr(spec.Cell, "reference", reference)


def _run(seed=2 ** 31 + 41, seconds=3.0, traced=False, **kw):
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
    assert "moe_pairs_per_expert" not in real.per_layer  # reads GLM's keys
    assert tiny.end_to_end == real.end_to_end == ["setup_s",
                                                  "serve_tokens_per_s"]
    for name in ("first_k_dense_replace", "num_hidden_layers",
                 "rms_norm_eps", "routed_scaling_factor", "mla_use_nope",
                 "moe_router_activation_func", "moe_renormalize",
                 "num_shared_experts", "q_lora_rank", "l2_norm_eps",
                 "first_published_layer"):
        assert tiny.config[name] == real.config[name], name
    for name in ("kda_layers", "full_attn_layers", "short_conv_kernel_size"):
        assert tiny.config["linear_attn_config"][name] \
            == real.config["linear_attn_config"][name], name
    # every serve cell's readers list the new cell, and the scope-named
    # ones that fit as they are; ``decode_overlap_pct`` alone does not:
    # ``test_overlap.py`` pins its list of cells, and neither may be edited
    # here (CHANGES.md, PR 41)
    listed = {m["name"] for m in manifest["per_layer"]
              if "gpt2-xl.serve-closed" in m.get("workloads", ())
              and "glm-5.2.serve-agent-closed" in m["workloads"]}
    assert listed - set(real.per_layer) == {"decode_overlap_pct"}
    assert {"decode_mla_attn_device_ms", "decode_moe_route_device_ms",
            "decode_moe_experts_device_ms", "prefill_mla_attn_device_ms"
            } <= set(real.per_layer)


def test_real_configuration_is_the_catalogs_but_for_what_reduced_names():
    """Every key of the catalog row's ``config`` under its own name and
    with its value, but the five ``reduced`` names, each with its published
    value beside it; in the nested group only the two lists of layers
    differ; the deployment stated."""
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    c = spec.load_cell(REAL_CELL).config
    assert c["source"] == row["source_url"]
    assert c["reduced"] == ["num_hidden_layers", "linear_attn_config",
                            "num_experts", "vocab_size", "model_max_length"]
    for name, value in row["config"].items():
        if name in c["reduced"]:
            assert c[f"published_{name}"] == value, name
            assert c[name] != value, name
        else:
            assert c[name] == value, name
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"],
            c["model_max_length"]) == (5, 128, 81920, 51200)
    linear, published = c["linear_attn_config"], \
        c["published_linear_attn_config"]
    assert {k: v for k, v in linear.items() if not k.endswith("_layers")} \
        == {k: v for k, v in published.items() if not k.endswith("_layers")} \
        == {"head_dim": 128, "num_heads": 32, "short_conv_kernel_size": 4}
    first = c["first_published_layer"]
    assert first == 1 and c["first_expert"] == 0
    held = range(first, first + 5)
    assert linear["kda_layers"] == [i - first + 1 for i in held
                                    if i in published["kda_layers"]]
    assert linear["full_attn_layers"] == [
        i - first + 1 for i in held if i in published["full_attn_layers"]]
    # every width, both head counts, the rank, 8 of 256, the 3 : 1 pattern
    assert (c["hidden_size"], c["intermediate_size"],
            c["moe_intermediate_size"], c["kv_lora_rank"],
            c["num_attention_heads"], c["num_experts_per_token"],
            c["published_num_experts"]) == (2304, 9216, 1024, 512, 32, 8, 256)
    for key in ("reduced_why", "assumed", "deployment"):
        assert c[key], key
    for key in ("conv_bias", "decay_init", "l2_norm_eps", "selection_bias",
                "top_k_ties", "embedding_std", "dtypes"):
        assert key in c["assumed"], key
    s = c["serving_sizes"]
    assert (s["max_batch"], s["chunk_prefill_len"], s["max_prefill_len"],
            s["min_prefill_len"], s["kv_block_size"]) \
        == (32, 2048, 2048, 512, 64) and "serving" not in c
    with open(spec.MANIFEST) as f:
        entry = next(e for e in json.load(f)["configs"]
                     if e["name"] == "kimi-linear-48b-a3b")
    assert entry["reduced"] == c["reduced"]
    assert entry["source"] == c["source"]


def test_the_mix_is_the_issues_parameter_for_parameter():
    cell = spec.load_cell(REAL_CELL)
    mix = cell.traffic
    assert (mix["kind"], mix["loop"], mix["clients"]) == ("serve", "closed",
                                                          32)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 16384,
                                 "sigma": 0.5, "min": 8192, "max": 49152}
    assert mix["output_len"] == {"dist": "lognormal", "median": 384,
                                 "sigma": 0.5, "min": 128, "max": 1024}
    assert (mix["size_seed"], mix["n_requests"], mix["ramp_s"],
            mix["trace_seconds"], mix["check_requests"],
            mix["check_pad_to"]) == (20260929, 512, 30, 4, 4, 51200)
    assert mix["size_set"] in (16, 8) and mix["size_set_why"]
    # the longest prompt + output the mix can draw fits the serving limit
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        <= mix["check_pad_to"] == cell.config["model_max_length"]
    assert cell.chips == 1 and cell.traffic_name == "longdoc-closed"


def test_tiny_cell_runs_and_is_correct(traced):
    cell, r = traced
    assert r["correct"], _failed(r)
    assert r["attempted"] >= 4 and r["failed"] == 0
    ctx = r["layer_context"]
    read = {n: spec.load_module("layer_metrics", n).read(ctx)
            for n in cell.per_layer}
    assert read["decode_step_ms"] > 0 and read["tpot_p50_ms"] > 0
    assert 0 < read["prefill_time_pct"] < 100
    # 2 rows x 4 pairs x 4 layers over 32 held experts, half of 16 held
    assert 0.1 < read["moe_held_pairs_per_expert"] < 1.0
    steps = glm.window_steps(ctx)
    assert steps and all(
        a["selected_rows"] == a["kv_rows"] >= 300 * a["rows"]
        and a["state_slots"] == a["rows"]
        and 0 <= a["expert_hits"] <= min(a["expert_pairs"], 32)
        and a["expert_pairs"] <= a["rows"] * 16 for a in steps)
    prefills = [a for _, _, a in scopes.span_seconds(ctx, "serving_prefill")]
    assert prefills and all(
        0 < a["tokens"] <= a["batch"] * a["length"] for a in prefills)
    assert ctx["registry"].get("serving_expert_pairs_total", 1) > 0
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
        3: (fusion, body + "attn/kda/mul:"),
        4: (fusion, body + "attn/kda_conv/add:"),
        5: (fusion, body + "attn/mla_attn/while/body/gather:"),
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
    number, the four shares are the counted bytes (or operations) over the
    scopes' time, and the attention scopes lie within ``attn``."""
    cell, r = traced
    assert scopes.pool_shapes(cell.config) == []
    assert scopes.pool_shapes(spec.load_cell(REAL_CELL).config) == []
    parsed = _made_up_trace()
    monkeypatch.setattr(scopes, "for_cell", lambda ctx: parsed)
    monkeypatch.setitem(device.PEAKS, jax.devices()[0].device_kind,
                        {"hbm_bytes_per_s": 1e6, "bf16_flops_per_s": 1e9})
    ctx = dict(r["layer_context"], memory_peak_bytes=12e9,
               trace={"chips": 1, "busy_s": 2.0, "window_s": 3.0})
    read = {n: spec.load_module("layer_metrics", n).read(ctx)
            for n in cell.per_layer}
    assert all(v is not None for v in read.values()), read
    assert read["decode_kda_device_ms"] == pytest.approx(100.0)
    assert read["decode_kda_conv_device_ms"] == pytest.approx(50.0)
    assert read["decode_mla_attn_device_ms"] == pytest.approx(100.0)
    assert read["decode_moe_route_device_ms"] == pytest.approx(40.0)
    assert read["decode_moe_experts_device_ms"] == pytest.approx(20.0)
    assert read["prefill_kda_device_ms"] == pytest.approx(200.0)
    assert read["prefill_mla_attn_device_ms"] == pytest.approx(300.0)
    assert read["decode_kv_cache_device_ms"] == pytest.approx(50.0)
    assert read["decode_attn_device_ms"] == pytest.approx(300.0)
    assert read["decode_kda_device_ms"] + read["decode_kda_conv_device_ms"] \
        + read["decode_mla_attn_device_ms"] \
        + read["decode_kv_cache_device_ms"] \
        <= read["decode_attn_device_ms"] + 1e-9
    steps = glm.traced_steps(ctx, parsed)
    assert len(steps) == 3

    def mean(f):
        return sum(f(a, cell.config) for a in steps) / 3

    # tiny: 4 KDA layers of 4 heads of 16 x 16 states and 3 x 192 tails,
    # one MLA layer of 16 + 4 wide latents, 3 matrices of 64 x 32 an expert
    a = steps[0]
    assert kda.state_step_bytes(a, cell.config) \
        == 2 * a["state_slots"] * (4 * 16 * 16 * 4 + 3 * 192 * 2) * 4
    assert kda.latent_step_bytes(a, cell.config) == 2 * a["kv_rows"] * 20
    assert kda.experts_step_bytes(a, cell.config) \
        == 2 * a["expert_hits"] * 3 * 64 * 32
    assert read["kda_state_hbm_roofline_pct"] == pytest.approx(
        100.0 * mean(kda.state_step_bytes) / 1e6 / 0.15)
    assert read["mla_dense_attn_hbm_roofline_pct"] == pytest.approx(
        100.0 * mean(kda.latent_step_bytes) / 1e6 / 0.1)
    assert read["moe_held_experts_hbm_roofline_pct"] == pytest.approx(
        100.0 * mean(kda.experts_step_bytes) / 1e6 / 0.02)
    tokens = [a["tokens"] for _, _, a
              in scopes.span_seconds(ctx, "serving_prefill")]
    ops, nbytes = kda.chunk_form_cost(sum(tokens) / len(tokens), cell.config)
    assert read["prefill_kda_roofline_pct"] == pytest.approx(
        100.0 * max(ops / 1e9, nbytes / 1e6) / 0.2)


def test_readers_find_nothing_in_a_trace_without_the_scopes(traced,
                                                            monkeypatch):
    """A program that lacks the family (the parent commit) or a cell of
    another family: the new readers return None and raise nothing."""
    cell, r = traced
    parsed = _made_up_trace()
    parsed.op_meta["/device:TPU:0"] = {
        k: (line, path.replace("kda", "other").replace("mla_", "other_")
            .replace("moe_", "other_"))
        for k, (line, path) in parsed.op_meta["/device:TPU:0"].items()}
    monkeypatch.setattr(scopes, "for_cell", lambda ctx: parsed)
    monkeypatch.setitem(device.PEAKS, jax.devices()[0].device_kind,
                        {"hbm_bytes_per_s": 1e6, "bf16_flops_per_s": 1e9})
    ctx = dict(r["layer_context"])
    ctx["spans"] = [(n, s, d, {k: v for k, v in a.items()
                               if k not in glm.STEP_ARGS + ("tokens",)})
                    for n, s, d, a in ctx["spans"]]
    for name in NEW_READERS:
        assert spec.load_module("layer_metrics", name).read(ctx) is None
    for other in ("gpt2-xl.serve-closed", "glm-5.2.serve-agent-closed"):
        there = dict(r["layer_context"], cell=spec.load_cell(other))
        for name in NEW_READERS:
            assert spec.load_module("layer_metrics", name
                                    ).read(there) is None, (other, name)


def test_readers_know_the_bytes_and_operations_a_step_and_a_slice_move():
    """From the real cell's configuration: 4 KDA layers of [32, 128, 128]
    float32 states and [3, 12288] bfloat16 tails, in and out; one MLA layer
    of 512 + 64 wide latents (not the rows' 640), every cached position;
    three 2304 x 1024 bfloat16 matrices for every held expert that got a
    pair; and the chunk form's operations and bytes a token."""
    real = spec.load_cell(REAL_CELL)
    a = {"kv_rows": 560000, "selected_rows": 560000, "state_slots": 32,
         "expert_pairs": 1024, "expert_hits": 320}
    assert kda.state_step_bytes(a, real.config) \
        == 2 * 32 * 4 * (32 * 128 * 128 * 4 + 3 * 12288 * 2)
    assert kda.latent_step_bytes(a, real.config) == 560000 * 576 * 2
    assert kda.experts_step_bytes(a, real.config) == 320 * 3 * 2304 * 1024 * 2
    ops, nbytes = kda.chunk_form_cost(2048, real.config)
    per_chunk_head = 2 * (5 * 64 * 64 * 128 + 3 * 64 * 128 * 128)
    assert ops == per_chunk_head * 32 * 32 * 4
    assert nbytes == 14 * 128 * 32 * 2048 * 4
    ctx = {"kind": "serve", "cell": real, "spans": []}
    for name in FROM_SPANS:
        assert spec.load_module("layer_metrics", name).read(ctx) is None
    # 32 rows x 8 pairs x 4 layers, half of them held, over 4 x 128 experts
    ctx["spans"] = [("serving_decode_step", 1.0, 0.01,
                     {"rows": 32, "kv_rows": 560000,
                      "selected_rows": 560000, "state_slots": 32}),
                    ("decode_commit", 1.0101, 0.001,
                     {"rows": 32, "expert_pairs": 512, "expert_hits": 320})]
    assert spec.load_module("layer_metrics", "moe_held_pairs_per_expert"
                            ).read(ctx) == 1.0
    # GLM's reader of pairs finds none of its keys here
    assert spec.load_module("layer_metrics", "moe_pairs_per_expert"
                            ).read(ctx) is None


def _altered_run():
    jax.clear_caches()  # the engine's programs were traced as they were
    try:
        return _run()[1]
    finally:
        jax.clear_caches()


def test_program_with_the_state_in_bfloat16_is_not_correct(monkeypatch):
    """The state rounded to bfloat16 by every call that hands it on, as a
    bfloat16 state pool would hold it."""
    from determined_clone_tpu.models import kimi_linear
    from determined_clone_tpu.ops import kda as ops_kda

    def rounded(*args, **kw):
        o, state = ops_kda.kda(*args, **kw)
        return o, jax.lax.reduce_precision(state, 8, 7)

    monkeypatch.setattr(kimi_linear, "kda", rounded)
    r = _altered_run()
    assert not r["correct"]
    assert _failed(r) == ["served_token_logit_gap"]


def test_program_with_the_decay_in_bfloat16_is_not_correct(monkeypatch):
    """``a_t = exp(g_t)`` rounded to bfloat16 before it is used: what a
    program that held its decays in the activations' type would compute."""
    from determined_clone_tpu.models import kimi_linear
    from determined_clone_tpu.ops import kda as ops_kda

    def rounded(q, k, v, g, b, state, token_mask, **kw):
        a = jax.lax.reduce_precision(jnp.exp(g), 8, 7)
        return ops_kda.kda(q, k, v, jnp.log(a), b, state, token_mask, **kw)

    monkeypatch.setattr(kimi_linear, "kda", rounded)
    r = _altered_run()
    assert not r["correct"]
    assert _failed(r) == ["served_token_logit_gap"]


def test_program_without_the_delta_correction_is_not_correct(monkeypatch):
    """``S_t = S' + b_t k_t v_t^T``: a gated linear attention that writes
    without first taking out what the state already answers."""
    from determined_clone_tpu.models import kimi_linear
    from determined_clone_tpu.ops import kda as ops_kda

    def plain(q, k, v, g, b, state, token_mask, **kw):
        # the one-token form with the correction's read-back removed, a
        # position at a time: what the chunk form would equal
        def step(state, xs):
            q_t, k_t, v_t, g_t, b_t, m_t = xs
            decayed = jnp.exp(g_t)[..., None] * state
            new = decayed + k_t[..., None] * (b_t[..., None] * v_t
                                              )[..., None, :]
            state = jnp.where(m_t[:, None, None, None], new, state)
            return state, jnp.sum(q_t[..., None] * state, axis=-2)

        xs = tuple(jnp.moveaxis(x.astype(jnp.float32), 1, 0)
                   for x in (q, k, v, g, b)) + (token_mask.T,)
        state, out = jax.lax.scan(step, state, xs)
        return jnp.moveaxis(out, 0, 1), state

    assert ops_kda.kda is kimi_linear.kda
    monkeypatch.setattr(kimi_linear, "kda", plain)
    r = _altered_run()
    assert not r["correct"]
    assert "served_token_logit_gap" in _failed(r)


@pytest.mark.parametrize("control", ["fp8", "no_delta"])
def test_control_is_not_correct(control, capsys):
    """The reference's own controls on the served sample. ``bf16_decay``
    and ``bf16_state`` are not held to the limit here: at toy widths and
    300-640 positions they move a logit by parts in a thousand and, by
    which requests the 3 s window holds, flip a served token or none
    (0.0018 / 0.0 and 0.0069 / 0.0 on one seed); what they move is held
    in ``tests/test_kimi_linear.py``, on a fixed sequence's logits."""
    cell, r = _run(seed=2 ** 31 + 5, control=control)
    gap = r["control"]["served_token_logit_gap"]
    assert gap > 2 * cell.limits["served_token_logit_gap"], gap
    assert r["correct"], _failed(r)
    said = [line for line in capsys.readouterr().out.splitlines()
            if "take the program's experts" in line]
    # every checked request found its record; float32 flips no routing
    assert len(said) == 8 and all("1.0000 of its" in s for s in said)
