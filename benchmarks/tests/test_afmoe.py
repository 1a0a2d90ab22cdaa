"""The Trinity (``afmoe``) family through the benchmark (PR 47): a tiny
configuration, mix and cell under ``data/`` (files only; the real cell's
five layers, sliding + dense, sliding + experts, full + experts, sliding +
experts twice, at toy widths: 4 query heads over 2 KV heads of 16, a window
of 64 beside slices of 32, 16 experts of which experts 8..15 are held, 4 a
token) run through ``harness/serve.py`` on the CPU, prompts inside one
window beside prompts of four; the reference's own controls (fp8, the
window taken away, rotary on the full layer, no output gate) each come out
as not correct; and a traced run yields every per-layer metric the real
cell lists, the new readers among them.

``harness/serve.py`` hands the reference no constants, so the reference's
defaults are the real cell's; the tiny cell's are bound here
(``reference()`` below), in the test and not through an option of the
harness.

The tiny cell computes in float32 (``tiny-trinity.json`` says why), so its
program's ``served_token_logit_gap`` reads 0.0 (every served token is the
reference's first); the limit is 0.0005, ten times what the float32 serving
tests hold a logit to, as the tiny Kimi cell's is.
"""
import functools
import json
import time
import types

import jax
import pytest

from benchmarks.harness import afmoe, device, scopes, serve, spec
from benchmarks.tests.conftest import DATA

ROOTS = (DATA, spec.BENCH_DIR)
FAKE_DEVICE = {"kind": "TPU v5 lite"}  # only the peak table is looked up
CELL = "tiny.serve-mixed"
REAL_CELL = "trinity-large-preview.serve-mixed-closed"
NEW_READERS = ("decode_window_attn_device_ms", "decode_full_attn_device_ms",
               "prefill_window_attn_device_ms", "prefill_full_attn_device_ms",
               "window_attn_hbm_roofline_pct", "full_attn_hbm_roofline_pct",
               "window_attended_rows_pct", "afmoe_experts_hbm_roofline_pct",
               "afmoe_pairs_per_expert")
FROM_SPANS = ("window_attended_rows_pct", "afmoe_pairs_per_expert")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_dense_layers", "layer_types",
           "num_experts", "vocab_size", "max_position_embeddings"]


def reference(cell):
    """The cell's reference with the tiny cell's constants bound."""
    ref = spec.load_module("reference", cell.adapter().REFERENCE, cell.roots)
    c = cell.config
    return types.SimpleNamespace(teacher_forced_logits=functools.partial(
        ref.teacher_forced_logits,
        experts_per_token=c["num_experts_per_tok"],
        routed_scale=c["route_scale"], first_expert=c["first_expert"],
        rms_eps=c["rms_norm_eps"], window=c["sliding_window"],
        rope_theta=float(c["rope_theta"]), mup=c["mup_enabled"]))


@pytest.fixture(autouse=True)
def tiny_constants(monkeypatch):
    monkeypatch.setattr(spec.Cell, "reference", reference)


def _run(seed=2 ** 31 + 47, seconds=3.0, traced=False, **kw):
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
    # the other expert families' readers of pairs and experts' bytes key on
    # their own configurations' names
    assert not {"moe_pairs_per_expert", "moe_held_pairs_per_expert",
                "moe_experts_hbm_roofline_pct",
                "moe_held_experts_hbm_roofline_pct"} & set(real.per_layer)
    assert tiny.end_to_end == real.end_to_end == ["setup_s",
                                                  "serve_tokens_per_s"]
    for name in ("num_dense_layers", "num_hidden_layers", "layer_types",
                 "rms_norm_eps", "route_scale", "route_norm", "score_func",
                 "num_experts_per_tok", "num_shared_experts", "mup_enabled",
                 "rope_theta", "global_attn_every_n_layers",
                 "first_published_layer", "model_type", "adapter"):
        assert tiny.config[name] == real.config[name], name
    # every metric that lists all the serve cells before this one lists it
    # too, but ``decode_overlap_pct``: ``test_overlap.py`` pins its list of
    # cells, and neither may be edited here (CHANGES.md, PR 41)
    listed = {m["name"] for m in manifest["per_layer"]
              if "gpt2-xl.serve-closed" in m.get("workloads", ())
              and "glm-5.2.serve-agent-closed" in m["workloads"]}
    assert listed - set(real.per_layer) == {"decode_overlap_pct"}
    assert {"decode_moe_route_device_ms", "decode_moe_experts_device_ms"
            } <= set(real.per_layer)
    # each new entry is this cell's alone and as its reader describes itself
    for name in NEW_READERS:
        (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
        reader = spec.load_module("layer_metrics", name)
        assert entry["workloads"] == [REAL_CELL]
        assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
            entry["layer"], entry["unit"], entry["source"], entry["moves"])
    assert REAL_CELL in next(e for e in manifest["end_to_end"]
                             if e["name"] == "serve_tokens_per_s")["workloads"]


def test_real_configuration_is_the_catalogs_but_for_what_reduced_names():
    """Every key of the catalog row's ``config`` under its own name and
    with its value, but the six ``reduced`` names, each with its published
    value beside it; the five layers are published layers 6..10; no width
    is cut; the deployment and the memory arithmetic stated."""
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Large-Preview")
    c = spec.load_cell(REAL_CELL).config
    assert c["source"] == row["source_url"]
    assert c["reduced"] == REDUCED
    for name, value in row["config"].items():
        if name in c["reduced"]:
            assert c[f"published_{name}"] == value, name
            assert c[name] != value, name
        else:
            assert c[name] == value, name
    assert (c["num_hidden_layers"], c["num_dense_layers"], c["num_experts"],
            c["vocab_size"], c["max_position_embeddings"]) \
        == (5, 1, 32, 25024, 34816)
    first = c["first_published_layer"]
    assert first == 6 and c["first_expert"] == 0
    assert c["layer_types"] == c["published_layer_types"][first - 1:first + 4]
    assert c["layer_types"] == ["sliding_attention"] * 2 \
        + ["full_attention"] + ["sliding_attention"] * 2
    # the one dense layer is the last of the published six
    assert first == c["published_num_dense_layers"]
    assert c["published_vocab_size"] == 8 * c["vocab_size"]
    # every width, both head counts, 4 of 256, the window
    assert (c["hidden_size"], c["intermediate_size"],
            c["moe_intermediate_size"], c["head_dim"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["num_experts_per_tok"], c["published_num_experts"],
            c["sliding_window"]) \
        == (3072, 12288, 3072, 128, 48, 8, 4, 256, 4096)
    for key in ("reduced_why", "assumed", "deployment"):
        assert c[key], key
    for key in ("rotary", "window_edge", "output_gate", "qk_norm", "norms",
                "router", "selection_bias", "top_k_ties", "mup",
                "embedding_std", "load_balance_coeff", "dtypes"):
        assert key in c["assumed"], key
    assert "8 chips" in c["deployment"] and "12 pipeline stages" \
        in c["deployment"] and "96 chips" in c["deployment"]
    assert "12.5 GB of 16" in c["reduced_why"]
    s = c["serving_sizes"]
    assert (s["max_batch"], s["chunk_prefill_len"], s["max_prefill_len"],
            s["min_prefill_len"], s["kv_block_size"]) \
        == (16, 2048, 2048, 512, 64) and "serving" not in c
    with open(spec.MANIFEST) as f:
        entry = next(e for e in json.load(f)["configs"]
                     if e["name"] == "trinity-large-preview")
    assert entry["reduced"] == c["reduced"]
    assert entry["source"] == c["source"]
    assert entry["file"] == "benchmarks/configs/trinity-large-preview.json"


def test_the_mix_is_the_issues_parameter_for_parameter():
    cell = spec.load_cell(REAL_CELL)
    mix = cell.traffic
    assert (mix["kind"], mix["loop"], mix["clients"]) == ("serve", "closed",
                                                          16)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 8192,
                                 "sigma": 0.9, "min": 1024, "max": 32768}
    assert mix["output_len"] == {"dist": "lognormal", "median": 512,
                                 "sigma": 0.5, "min": 128, "max": 2048}
    assert (mix["size_seed"], mix["n_requests"], mix["ramp_s"],
            mix["trace_seconds"], mix["check_requests"],
            mix["check_pad_to"]) == (20260929, 1024, 30, 4, 4, 34816)
    assert mix["size_set"] in (16, 8) and mix["size_set_why"]
    # the longest prompt + output the mix can draw fits the serving limit
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        <= mix["check_pad_to"] == cell.config["max_position_embeddings"]
    assert cell.chips == 1 and cell.traffic_name == "mixed-closed"
    # the sizes every run offers: some inside one window, some past four
    from benchmarks.harness import traffic
    prompts = sorted({len(r.prompt) for r in traffic.serve_requests(
        mix, cell.config["vocab_size"], 7)})
    assert len(prompts) == mix["size_set"]
    window = cell.config["sliding_window"]
    assert prompts[0] <= window and prompts[-1] > 4 * window


def test_readers_know_the_bytes_a_step_has_to_read():
    """From the real cell's configuration: a K and a V row of 8 x 128
    bfloat16 values (4096 B) a position and layer; ``window_rows`` in the
    four sliding layers, ``kv_rows`` in the one full layer; three 3072 x
    3072 bfloat16 matrices for every held expert that got a pair; and the
    share of a uniform cache's read that is left."""
    real = spec.load_cell(REAL_CELL)
    assert afmoe.layers(real.config) == (4, 1, 4)
    assert afmoe.row_bytes(real.config) == 4096
    a = {"rows": 16, "kv_rows": 160000, "window_rows": 50000,
         "expert_pairs": 64, "expert_hits": 28}
    assert afmoe.window_step_bytes(a, real.config) == 50000 * 4 * 4096
    assert afmoe.full_step_bytes(a, real.config) == 160000 * 4096
    assert afmoe.experts_step_bytes(a, real.config) \
        == 28 * 3 * 3072 * 3072 * 2
    ctx = {"kind": "serve", "cell": real, "spans": []}
    for name in FROM_SPANS:
        assert spec.load_module("layer_metrics", name).read(ctx) is None
    ctx["spans"] = [("serving_decode_step", 1.0, 0.01,
                     {"rows": 16, "kv_rows": 160000, "window_rows": 50000}),
                    ("decode_commit", 1.0101, 0.001,
                     {"rows": 16, "expert_pairs": 64, "expert_hits": 28})]
    # 16 rows x 4 pairs x 4 layers, an eighth of them held, over 4 x 32
    assert spec.load_module("layer_metrics", "afmoe_pairs_per_expert"
                            ).read(ctx) == 0.5
    assert spec.load_module("layer_metrics", "window_attended_rows_pct"
                            ).read(ctx) == pytest.approx(
        100.0 * (4 * 50000 + 160000) / (5 * 160000))
    # the other expert families' readers find none of their keys here, and
    # these none of theirs there
    for name in ("moe_pairs_per_expert", "moe_held_pairs_per_expert"):
        assert spec.load_module("layer_metrics", name).read(ctx) is None
    for other in ("glm-5.2.serve-agent-closed",
                  "kimi-linear-48b-a3b.serve-longdoc-closed",
                  "gpt2-xl.serve-closed"):
        there = dict(ctx, cell=spec.load_cell(other))
        assert afmoe.layers(there["cell"].config) is None
        for name in NEW_READERS:
            assert spec.load_module("layer_metrics", name
                                    ).read(there) is None, (other, name)


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
    assert 0.1 < read["afmoe_pairs_per_expert"] < 1.0
    assert 20 < read["window_attended_rows_pct"] <= 100
    steps = afmoe.window_steps(ctx)
    assert steps and all(
        a["rows"] <= a["window_rows"] <= 64 * a["rows"]
        and a["window_rows"] <= a["kv_rows"]
        and 0 <= a["expert_hits"] <= min(a["expert_pairs"], 32)
        and a["expert_pairs"] <= a["rows"] * 16 for a in steps)
    assert any(a["window_rows"] < a["kv_rows"] for a in steps)
    prefills = [a for _, _, a in scopes.span_seconds(ctx, "serving_prefill")]
    assert prefills and all(
        0 < a["tokens"] <= a["batch"] * a["length"]
        and a["tokens"] <= a["window_key_rows"] <= a["full_key_rows"]
        for a in prefills)
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
        3: (fusion, body + "attn/window_attn/while/body/dot_general:"),
        4: (fusion, body + "attn/full_attn/while/body/dot_general:"),
        5: (fusion, body + "attn/dot_general:"),
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
    ops += [(t, t + 0.6, 1), (t, t + 0.2, 3), (t + 0.2, t + 0.5, 4)]
    p.ops = {chip: sorted(ops, key=lambda o: (o[0], -o[1]))}
    p.reductions = {}
    return p


def test_a_traced_run_yields_every_metric_the_cell_lists(traced,
                                                         monkeypatch):
    """With device events under the program's scopes in the trace (made
    up: the CPU records none), every per-layer metric of the cell reads a
    number, the three shares are the counted bytes over the scopes' time,
    and the attention scopes lie within ``attn``."""
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
    assert read["decode_window_attn_device_ms"] == pytest.approx(100.0)
    assert read["decode_full_attn_device_ms"] == pytest.approx(50.0)
    assert read["decode_moe_route_device_ms"] == pytest.approx(40.0)
    assert read["decode_moe_experts_device_ms"] == pytest.approx(20.0)
    assert read["prefill_window_attn_device_ms"] == pytest.approx(200.0)
    assert read["prefill_full_attn_device_ms"] == pytest.approx(300.0)
    assert read["decode_kv_cache_device_ms"] == pytest.approx(50.0)
    assert read["decode_attn_device_ms"] == pytest.approx(300.0)
    assert read["decode_window_attn_device_ms"] \
        + read["decode_full_attn_device_ms"] \
        + read["decode_kv_cache_device_ms"] \
        <= read["decode_attn_device_ms"] + 1e-9
    steps = afmoe.traced_steps(ctx, parsed)
    assert len(steps) == 3

    def mean(f):
        return sum(f(a, cell.config) for a in steps) / 3

    # tiny: K and V rows of 2 x 16 float-typed-as-bf16 values (2 B each by
    # the reader's count), 4 sliding layers, 1 full, 3 matrices of 64 x 32
    a = steps[0]
    assert afmoe.window_step_bytes(a, cell.config) \
        == a["window_rows"] * 4 * 2 * 32 * 2
    assert afmoe.full_step_bytes(a, cell.config) == a["kv_rows"] * 2 * 32 * 2
    assert afmoe.experts_step_bytes(a, cell.config) \
        == 2 * a["expert_hits"] * 3 * 64 * 32
    assert read["window_attn_hbm_roofline_pct"] == pytest.approx(
        100.0 * mean(afmoe.window_step_bytes) / 1e6 / 0.1)
    assert read["full_attn_hbm_roofline_pct"] == pytest.approx(
        100.0 * mean(afmoe.full_step_bytes) / 1e6 / 0.05)
    assert read["afmoe_experts_hbm_roofline_pct"] == pytest.approx(
        100.0 * mean(afmoe.experts_step_bytes) / 1e6 / 0.02)


def test_readers_find_nothing_in_a_trace_without_the_scopes(traced,
                                                            monkeypatch):
    """A program that lacks the family (the parent commit): the new
    readers return None and raise nothing."""
    cell, r = traced
    parsed = _made_up_trace()
    parsed.op_meta["/device:TPU:0"] = {
        k: (line, path.replace("window_attn", "other")
            .replace("full_attn", "other").replace("moe_", "other_"))
        for k, (line, path) in parsed.op_meta["/device:TPU:0"].items()}
    monkeypatch.setattr(scopes, "for_cell", lambda ctx: parsed)
    monkeypatch.setitem(device.PEAKS, jax.devices()[0].device_kind,
                        {"hbm_bytes_per_s": 1e6, "bf16_flops_per_s": 1e9})
    ctx = dict(r["layer_context"])
    ctx["spans"] = [(n, s, d, {k: v for k, v in a.items()
                               if k not in afmoe.STEP_ARGS})
                    for n, s, d, a in ctx["spans"]]
    for name in NEW_READERS:
        assert spec.load_module("layer_metrics", name).read(ctx) is None


@pytest.mark.parametrize("control", ["fp8", "no_window", "rope_on_full",
                                     "no_gate"])
def test_control_is_not_correct(control, capsys):
    """The reference's own controls on the served sample: each scores the
    tokens it puts first against the reference proper and trails it by far
    more than the limit, while the program's run is correct."""
    cell, r = _run(seed=2 ** 31 + 5, control=control)
    gap = r["control"]["served_token_logit_gap"]
    assert gap > 2 * cell.limits["served_token_logit_gap"], gap
    assert r["correct"], _failed(r)
    said = [line for line in capsys.readouterr().out.splitlines()
            if "take the program's experts" in line]
    # every checked request found its record; float32 flips no routing
    assert len(said) == 8 and all("1.0000 of its" in s for s in said)
