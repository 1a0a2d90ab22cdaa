"""The ``glm4_moe_lite`` family through the benchmark, on the CPU at a tiny
size (``data/configs/tiny-glm-lite.json``, ``data/traffic/train-8k-tiny``,
``data/workloads/tiny.train-glm-lite.json``): the program's loss and
gradients against the plain reference under replayed routing, three
``Trainer`` steps through ``harness/train.py``, the configuration against
the catalog, and the new readers.

``tests/test_benchmark_harness.py`` collects these for tier-1: the runs
through the harness work in a directory of their own (``work_dir``), so
they share none with another process.
"""
import contextlib
import json
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import check, device, moe_train, scopes, spec, train
from benchmarks.reference import replayed
from benchmarks.tests.conftest import DATA

ROOTS = (DATA, spec.BENCH_DIR)
FAKE_DEVICE = {"kind": "TPU v5 lite"}  # only the peak table is looked up
CELL = "tiny.train-glm-lite"
REAL_CELL = "glm-4.7-flash.train-8k"
NEW_READERS = (
    "train_mla_attn_device_ms", "train_moe_route_device_ms",
    "train_moe_experts_device_ms", "train_mtp_device_ms",
    "train_moe_pairs_per_expert", "train_moe_experts_roofline_pct",
    "train_flash_roofline_pct", "train_moe_mfu_pct")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 2 ** 31 + 43


@contextlib.contextmanager
def time_limit(seconds):
    """A time limit of the test's own (no plugin here sets one)."""
    def expired(signum, frame):
        raise TimeoutError(f"not done in {seconds} s")

    old = signal.signal(signal.SIGALRM, expired)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture
def work_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(train, "WORK_DIR", str(tmp_path / "work"))


def _failed(result):
    return [c["check"] for c in result["checks"] if not c["ok"]]


# ---------------------------------------------------------------------------
# (a) loss and every leaf's gradient against the reference, routing replayed
# ---------------------------------------------------------------------------

# Each against the reference's float32 numbers. The program multiplies
# bfloat16-rounded operands (relative rounding 2^-9 an element, averaged
# down over a product's terms, added up over the layers):
LOSS_TOL = 1e-4   # of a loss of 7.2: the heads' logits move by 1e-3 of one
LEAF_TOL = 0.03   # |g - g_ref| of a leaf over |g_ref| of that leaf or of the
#                   median leaf: 1-2 % is what bfloat16 products give a
#                   gradient through four layers; a token that takes another
#                   expert moves an expert stack's by 10 % and more


@pytest.fixture(scope="module")
def compared():
    """``gaps(alter)``: the program with ``alter(params)`` in place of the
    seeded weights, handing over its own routing, against the reference on
    the seeded weights: (loss gap, the widest leaf gap, its leaf)."""
    cell = spec.load_cell(CELL, roots=ROOTS)
    adapter, ref = cell.adapter(), cell.reference()
    from determined_clone_tpu.models import glm_moe_lite

    cfg = adapter.model_config(cell.config)
    batch = np.asarray(jax.random.randint(
        jax.random.PRNGKey(5), (2, 65), 0, cfg.vocab_size), np.int32)
    tokens, targets = jnp.asarray(batch[:, :-1]), jnp.asarray(batch[:, 1:])
    grad = jax.jit(jax.value_and_grad(
        lambda p: glm_moe_lite.loss_fn(p, cfg, tokens, targets)[0]))
    chosen = jax.jit(lambda p: glm_moe_lite.chosen_experts(
        p, cfg, tokens, targets))

    @jax.jit
    def reference(p, replay):
        return ref.loss_and_grads(
            p, jnp.asarray(batch), n_heads=cfg.num_attention_heads,
            first_expert=cfg.first_expert, replay=replay)

    def gaps(alter=lambda p: p):
        params = adapter.make_weights(cell.config, SEED)
        altered = alter(params)
        loss, g = grad(altered)
        want_loss, want, out = reference(params, chosen(altered))
        norms = np.asarray([float(jnp.linalg.norm(w))
                            for w in jax.tree.leaves(want)])
        diffs = np.asarray([float(jnp.linalg.norm(a - w)) for a, w in zip(
            jax.tree.leaves(g), jax.tree.leaves(want))])
        rel = diffs / np.maximum(norms, np.median(norms))
        paths = [jax.tree_util.keystr(p) for p, _
                 in jax.tree_util.tree_flatten_with_path(want)[0]]
        own = min(float(jnp.min(v[1][:, 0])) for k, v in out.items()
                  if k != "losses")
        return (abs(float(loss) - float(want_loss)) / float(want_loss),
                float(rel.max()), paths[int(rel.argmax())], own)

    return gaps


def test_loss_and_every_leafs_gradient_are_the_references(compared):
    with time_limit(900):
        loss_gap, leaf_gap, leaf, own = compared()
    assert loss_gap < LOSS_TOL
    assert leaf_gap < LEAF_TOL, leaf
    # the reference's own choice is the program's for nearly every token
    assert own >= 0.9


def test_a_program_without_the_bias_in_its_selection_is_not_the_references(
        compared):
    """A selection without the bias takes other experts, which the
    reference does not follow (they are not admissible) and so differs by
    whole tokens: at least twice outside the tolerance. (Parameters held in
    bfloat16 show in no single gradient, since the products round them
    anyway; they show in the parameters' change:
    ``test_program_with_bfloat16_parameters_is_not_correct``.)"""
    def alter(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, x: jnp.zeros_like(x) if [
                getattr(k, "key", None) for k in path[-2:]]
            == ["router", "bias"] else x, params)

    with time_limit(900):
        loss_gap, leaf_gap, leaf, _ = compared(alter)
    assert leaf_gap > 2 * LEAF_TOL or loss_gap > 2 * LOSS_TOL, (
        loss_gap, leaf_gap, leaf)


# ---------------------------------------------------------------------------
# (d) three Trainer steps through the harness
# ---------------------------------------------------------------------------

def test_tiny_cell_runs_and_is_correct_and_the_bias_moved_by_the_loads(
        work_dir, monkeypatch):
    """``harness/train.py`` as it is: the run is ``correct``; after the
    three checked steps every selection bias is the seeded one moved by
    ``bias_update_rate`` an expert a step against the load's error (the
    loads of the routing the adapter handed over: the program's own, with
    the parameters each step started from), and Adam holds no moment of
    it."""
    cell = spec.load_cell(CELL, roots=ROOTS)
    seen = {}
    on_boundary = train._Run.on_boundary

    def watch(self, steps, metrics, get_state):
        if steps == train.CHECK_STEPS:
            state = get_state()
            seen["params"] = jax.device_get(state.params)
            seen["mu"] = jax.device_get(
                self.adapter.adam_first_moment(state.opt_state))
            seen["metrics"] = dict(metrics)
        on_boundary(self, steps, metrics, get_state)

    monkeypatch.setattr(train._Run, "on_boundary", watch)
    with time_limit(900):
        r = train.run(cell, SEED, 2.0, False, time.monotonic(),
                      dict(FAKE_DEVICE))
    assert r["correct"], _failed(r)
    assert r["values"]["train_tokens_per_s_per_chip"] > 0
    assert {"loss", "loss_next", "loss_mtp", "moe_pairs_held",
            "moe_experts_hit", "moe_load_max_over_mean"} <= set(
        seen["metrics"])
    adapter = cell.adapter()
    rate = cell.config["assumed"]["bias_update_rate"]
    n_experts = cell.config["published_n_routed_experts"]
    start = jax.device_get(adapter.make_weights(cell.config, SEED))
    handed = list(replayed.ROUTING.values())
    assert len(handed) == train.CHECK_STEPS
    for stack, router_of in (("sparse", lambda p: p["sparse"]["router"]),
                             ("mtp", lambda p: p["mtp"]["layer"]["router"])):
        want = np.asarray(router_of(start)["bias"])
        for step in handed:
            load = np.stack([np.bincount(layer.reshape(-1),
                                         minlength=n_experts)
                             for layer in step["experts"][stack]])
            want = want + rate * np.sign(
                load.mean(-1, keepdims=True) - load)
        got = np.asarray(router_of(seen["params"])["bias"])
        np.testing.assert_allclose(got, want, atol=1e-7)
        moved = np.abs(got - np.asarray(router_of(start)["bias"])) / rate
        assert moved.max() <= train.CHECK_STEPS + 1e-3 and moved.max() > 0.5
        assert not np.any(np.asarray(router_of(seen["mu"])["bias"]))


def test_program_with_bfloat16_parameters_is_not_correct(work_dir,
                                                        monkeypatch):
    """The trial's parameters (and so Adam's moments) held in bfloat16: an
    update of 3e-4 a step is lost in the rounding of a weight of 0.02, and
    the parameters' change is outside its limit."""
    cell = spec.load_cell(CELL, roots=ROOTS)
    adapter = cell.adapter()
    trial_class = adapter.trial_class

    def in_bfloat16(config, seed, batches):
        class Trial(trial_class(config, seed, batches)):
            def initial_params(self, rng):
                return jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                                    super().initial_params(rng))

        return Trial

    monkeypatch.setattr(adapter, "trial_class", in_bfloat16)
    monkeypatch.setattr(spec.Cell, "adapter", lambda self: adapter)
    with time_limit(900):
        r = train.run(cell, SEED, 0.0, False, time.monotonic(),
                      dict(FAKE_DEVICE))
    assert "param_change_leaf_norm_gap" in _failed(r), r["checks"]


@pytest.mark.parametrize("control", ["fp8", "nobias"])
def test_control_is_not_correct(work_dir, control):
    """The reference computed in the nearest precision below the one the
    configuration states, or choosing without the bias, read as the program
    is read: outside at least one of the tiny cell's limits by a factor of
    two."""
    cell = spec.load_cell(CELL, roots=ROOTS)
    with time_limit(900):
        r = train.run(cell, SEED, 0.0, False, time.monotonic(),
                      dict(FAKE_DEVICE), control=control)
    assert not [c for c in _failed(r) if cell.limits.get(c)], _failed(r)
    assert any(r["control"][name] > 2 * limit
               for name, limit in cell.limits.items()), r["control"]


# ---------------------------------------------------------------------------
# (f) the configuration, the mix, the cell, the readers
# ---------------------------------------------------------------------------

def test_real_configuration_is_the_catalogs_but_for_what_reduced_names():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "GLM-4.7-Flash")
    c = spec.load_cell(REAL_CELL).config
    assert c["source"] == row["source_url"]
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size", "max_position_embeddings"]
    for name, value in row["config"].items():
        if name in c["reduced"]:
            assert c[f"published_{name}"] == value, name
            assert c[name] != value, name
        else:
            assert c[name] == value, name
    assert (c["num_hidden_layers"], c["n_routed_experts"], c["vocab_size"],
            c["max_position_embeddings"], c["first_expert"]) \
        == (5, 8, 19360, 8192, 0)
    # every width, both ranks, the head sizes, 4 of 64 with scale 1.8, one
    # shared expert, one leading dense layer, one prediction module
    assert (c["hidden_size"], c["intermediate_size"],
            c["moe_intermediate_size"], c["q_lora_rank"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
            c["num_attention_heads"], c["num_experts_per_tok"],
            c["published_n_routed_experts"], c["routed_scaling_factor"],
            c["n_shared_experts"], c["first_k_dense_replace"],
            c["num_nextn_predict_layers"]) == (
        2048, 10240, 1536, 768, 512, 192, 64, 256, 20, 4, 64, 1.8, 1, 1, 1)
    for key in ("reduced_why", "assumed", "deployment"):
        assert c[key], key
    for key in ("rope_interleave", "init_std", "embedding_std",
                "selection_bias_std",
                "bias_update_rate", "mtp_loss_weight", "balance_loss",
                "dtypes", "routing_replay"):
        assert key in c["assumed"], key
    t = c["training"]
    assert (t["global_batch_size"], t["remat"], t["reference_rows"]) \
        == (1, True, 1) and "serving" not in c
    assert t["optimizer"] == spec.load_cell(
        "gpt2-medium.train").config["training"]["optimizer"]
    with open(spec.MANIFEST) as f:
        entry = next(e for e in json.load(f)["configs"]
                     if e["name"] == "glm-4.7-flash")
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    # the reference's constants are the configuration's
    ref = spec.load_cell(REAL_CELL).reference()
    assert (ref.TOP_K, ref.ROUTED_SCALE, ref.RMS_EPS, ref.ROPE_BASE,
            ref.BIAS_RATE, ref.MTP_WEIGHT) == (
        c["num_experts_per_tok"], c["routed_scaling_factor"],
        c["rms_norm_eps"], c["rope_theta"],
        c["assumed"]["bias_update_rate"], c["assumed"]["mtp_loss_weight"])


def test_the_mix_is_the_issues_parameter_for_parameter():
    cell = spec.load_cell(REAL_CELL)
    mix = cell.traffic
    assert (mix["kind"], mix["seq_len"], mix["pool_sequences"],
            mix["scheduling_unit"], mix["warm_units"], mix["prefetch_depth"],
            mix["trace_seconds"]) == ("train", 8192, 256, 4, 1, 2, 4)
    stream = dict(mix["stream"])
    stream.pop("why")
    assert stream == {"type": "bigram", "branching": 4, "table_seed": 1234}
    assert (cell.chips, cell.mesh, cell.traffic_name) \
        == (1, {"fsdp": 1}, "train-8k")
    assert sorted(cell.limits) == [
        "first_grad_leaf_difference", "first_grad_leaf_norm_gap",
        "loss_rel_gap", "param_change_leaf_norm_gap"]


def test_tiny_cell_lists_what_the_real_cell_lists():
    with open(spec.MANIFEST) as f:
        manifest = json.load(f)
    real = spec.load_cell(REAL_CELL, manifest=manifest)
    tiny = spec.load_cell(CELL, roots=ROOTS)
    assert tiny.per_layer == real.per_layer
    assert set(NEW_READERS) <= set(real.per_layer)
    assert tiny.end_to_end == real.end_to_end == [
        "setup_s", "train_tokens_per_s_per_chip"]
    # GPT's count of a step's operations and the collectives' share are not
    # this cell's; every other reader of the two GPT train cells is
    listed = {m["name"] for m in manifest["per_layer"]
              if "gpt2-medium.train" in m.get("workloads", ())}
    assert listed - set(real.per_layer) == {"train_mfu_pct"}
    assert "collective_time_pct" not in real.per_layer
    for name in ("num_experts_per_tok", "routed_scaling_factor",
                 "rms_norm_eps", "rope_theta", "first_k_dense_replace",
                 "num_nextn_predict_layers", "assumed"):
        assert tiny.config[name] == real.config[name], name
    assert tiny.config["training"]["optimizer"] \
        == real.config["training"]["optimizer"]
    # only this cell lists the new readers
    for m in manifest["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [REAL_CELL], m["name"]


def _made_up_trace(n_steps=3):
    """One chip; ``n_steps`` step programs, each inside a
    ``train_dispatch``, with operations under every scope the program
    names and the three kernels."""
    p = scopes.Parsed.__new__(scopes.Parsed)
    chip = "/device:TPU:0"
    fwd = "jit(step_fn)/jvp()/while/body/checkpoint/"
    bwd = "jit(step_fn)/transpose(jvp())/while/body/checkpoint/" \
          "rematted_computation/"
    fusion = "%f = f32[8,64]{1,0} fusion(bf16[8]{0} %x), kind=kLoop"
    kernel = "%{}.3 = bf16[1,8192,5120]{{2,1,0}} custom-call(bf16[8]{{0}} " \
             '%x), custom_call_target="tpu_custom_call"'
    p.op_meta = {chip: {
        1: (fusion, fwd + "attn/mla_attn/dot_general:"),
        2: (kernel.format("flash_fwd"), fwd + "attn/mla_attn/flash_fwd:"),
        3: (kernel.format("flash_bwd_dkv"),
            bwd + "transpose(jvp(attn))/mla_attn/flash_bwd_dkv:"),
        4: (kernel.format("flash_bwd_dq"),
            bwd + "transpose(jvp(attn))/mla_attn/flash_bwd_dq:"),
        5: (fusion, fwd + "mlp/moe_route/sort:"),
        6: (fusion, bwd + "transpose(jvp(mlp))/moe_experts/while/body/"
                          "dot_general:"),
        7: (fusion, fwd + "mlp/moe_shared/dot_general:"),
        8: (fusion, "jit(step_fn)/mtp/while/body/checkpoint/mlp/"
                    "moe_experts/while/body/dot_general:"),
        9: (fusion, "jit(step_fn)/optimizer/bias_update/add:"),
    }}
    p.modules = {chip: []}
    p.host, ops = [], []
    for i in range(n_steps):
        t = 1.0 + i
        p.host.append(("train_dispatch", t - 0.02, 0.9))
        p.modules[chip].append(("jit_step_fn(1)", t, 0.8))
        ops += [(t, t + 0.1, 1), (t + 0.1, t + 0.2, 2),
                (t + 0.2, t + 0.35, 3), (t + 0.35, t + 0.45, 4),
                (t + 0.45, t + 0.5, 5), (t + 0.5, t + 0.6, 6),
                (t + 0.6, t + 0.65, 7), (t + 0.65, t + 0.7, 8),
                (t + 0.7, t + 0.71, 9)]
    p.ops = {chip: sorted(ops, key=lambda o: (o[0], -o[1]))}
    p.reductions = {}
    return p


def _context(cell, pairs):
    return {"kind": "train", "cell": cell, "window_s": 3.0,
            "tokens_per_s": 2.0 * 8192, "peak_flops_per_s": 197e12,
            "spans": [("training_report", 1.0 + i, 0.001,
                       {"loss": 7.0, "moe_pairs_held": pairs})
                      for i in range(3)]}


def test_readers_read_a_made_up_trace_of_the_real_cell(monkeypatch):
    real = spec.load_cell(REAL_CELL)
    parsed = _made_up_trace()
    monkeypatch.setattr(scopes, "for_cell", lambda ctx: parsed)
    monkeypatch.setitem(device.PEAKS, jax.devices()[0].device_kind,
                        {"hbm_bytes_per_s": 819e9,
                         "bf16_flops_per_s": 197e12})
    ctx = _context(real, pairs=6 * 8 * 512.0)
    read = {n: spec.load_module("layer_metrics", n).read(ctx)
            for n in NEW_READERS}
    assert read["train_mla_attn_device_ms"] == pytest.approx(450.0)
    assert read["train_moe_route_device_ms"] == pytest.approx(50.0)
    assert read["train_moe_experts_device_ms"] == pytest.approx(150.0)
    assert read["train_mtp_device_ms"] == pytest.approx(50.0)
    assert read["train_moe_pairs_per_expert"] == pytest.approx(6 * 512 / 5)
    ops = moe_train.expert_products_flops(6 * 8 * 512.0, real.config)
    assert read["train_moe_experts_roofline_pct"] == pytest.approx(
        100 * ops / 197e12 / 0.15)
    assert read["train_flash_roofline_pct"] == pytest.approx(
        100 * moe_train.flash_kernels_flops(real.config, 8192, 1) / 197e12
        / 0.35)
    assert read["train_moe_mfu_pct"] == pytest.approx(
        100 * 2.0 * moe_train.step_flops(real.config, 8192, 1) / 197e12)


def test_readers_know_the_operations_a_step_has_to_do():
    """From the real cell's configuration: the issue's 352 M parameters a
    token multiplies and 31.7 TFLOP a step; nine products of 2048 x 1536 a
    pair; the kernels' nine (eleven with remat's second forward) products
    over 8192 x 8193 / 2 pairs of 20 heads of 256 in six layers."""
    c = spec.load_cell(REAL_CELL).config
    assert moe_train.step_flops(c, 8192, 1) == pytest.approx(31.7e12,
                                                             rel=0.01)
    assert moe_train.expert_products_flops(512 * 40, c) \
        == 9 * 2 * 2048 * 1536 * 512 * 40
    assert moe_train.held_experts(c) == 40
    assert moe_train.flash_kernels_flops(c, 8192, 1) \
        == 2 * 11 * (8192 * 8193 / 2) * 256 * 20 * 6
    assert moe_train.flash_kernels_flops(
        {**c, "training": {**c["training"], "remat": False}}, 8192, 1) \
        == 2 * 9 * (8192 * 8193 / 2) * 256 * 20 * 6


def test_readers_find_nothing_where_nothing_is_theirs(monkeypatch):
    """No trace (a run that was not traced), a trace without the scopes
    (the parent commit's program), spans without the report, a cell of
    another family: None, and nothing raises."""
    real = spec.load_cell(REAL_CELL)
    ctx = _context(real, pairs=1.0)
    monkeypatch.setattr(scopes, "for_cell", lambda ctx: None)
    ctx["spans"] = []
    for name in NEW_READERS:
        if name != "train_moe_mfu_pct":  # the host's clock and the config
            assert spec.load_module("layer_metrics", name).read(ctx) is None
    parsed = _made_up_trace()
    parsed.op_meta["/device:TPU:0"] = {
        k: (line.replace("flash_", "other_"),
            path.replace("mla_", "other_").replace("moe_", "other_")
            .replace("mtp", "other"))
        for k, (line, path) in parsed.op_meta["/device:TPU:0"].items()}
    monkeypatch.setattr(scopes, "for_cell", lambda ctx: parsed)
    for name in NEW_READERS:
        if name != "train_moe_mfu_pct":
            assert spec.load_module("layer_metrics", name).read(ctx) is None
    gpt = dict(_context(spec.load_cell("gpt2-medium.train"), 1.0))
    serve = dict(gpt, kind="serve", cell=spec.load_cell(
        "glm-5.2.serve-agent-closed"))
    for there in (gpt, serve):
        for name in NEW_READERS:
            assert spec.load_module("layer_metrics", name
                                    ).read(there) is None, name
    assert check.PROBES == 16  # the reference's probe is the harness's
