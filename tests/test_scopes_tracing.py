"""Names in the device program and the host's phases of a decode step
(docs/observability.md "Scopes in the device program", "An engine
iteration"): every scope of the vocabulary reaches the HLO of the train
step and of the paged forward, scopes change no arithmetic, the engine's
tracer records the span tree of an iteration, and an enabled tracer's spans
appear in a profiler trace as ``TraceAnnotation``s on the trace's clock."""
import contextlib
import dataclasses
import glob
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from determined_clone_tpu.models import gpt
from determined_clone_tpu.serving import (
    BucketSpec,
    InferenceEngine,
    KVCacheConfig,
    init_kv_pools,
)
from determined_clone_tpu.telemetry import Telemetry, spans
from determined_clone_tpu.training.train_step import (
    create_train_state,
    make_train_step,
)

TINY = dataclasses.replace(gpt.GPTConfig.tiny(), attention_impl="flash",
                           remat=True)
TRAIN_SCOPES = ("embed", "attn", "mlp", "logits", "optimizer")
PAGED_SCOPES = ("embed", "attn", "kv_cache", "mlp", "logits")


def _train_step(cfg=TINY):
    """(a fresh jitted step, state, batch) of the tiny model. A fresh jit
    every call: a patched ``named_scope`` must be traced, not cached."""
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-3))
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    state = create_train_state(params, tx, jax.random.PRNGKey(1))

    def loss(p, batch, rng):
        return gpt.loss_fn(p, cfg, batch["tokens"], batch["targets"])

    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 65), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}
    return make_train_step(loss, tx, donate=False), state, batch


@pytest.fixture(scope="module")
def train_hlo():
    step, state, batch = _train_step()
    return step.lower(state, batch).as_text(debug_info=True)


@pytest.fixture(scope="module")
def paged_hlo():
    cfg = dataclasses.replace(gpt.GPTConfig.tiny(), attention_impl="mha")
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    pool, _ = init_kv_pools(cfg, KVCacheConfig(num_blocks=8, block_size=8))
    b, t = 2, 1
    fwd = jax.jit(gpt.forward_paged, static_argnums=(1,))
    return fwd.lower(
        params, cfg, jnp.zeros((b, t), jnp.int32),
        jnp.zeros((b, t), jnp.int32), jnp.ones((b, t), bool),
        jnp.zeros((b,), jnp.int32), pool, pool,
        jnp.zeros((b, 4), jnp.int32)).as_text(debug_info=True)


def _has_scope(hlo: str, name: str) -> bool:
    """``name`` as one component of some operation's scope path, bare or
    wrapped by a transform (``jvp(attn)``, ``transpose(jvp(attn))``)."""
    return any(f"{a}{name}{b}" in hlo
               for a in ("/", "(", '"') for b in ("/", ")"))


@pytest.mark.parametrize("scope", TRAIN_SCOPES)
def test_train_step_hlo_carries_every_scope(train_hlo, scope):
    assert _has_scope(train_hlo, scope), scope


def test_train_step_hlo_names_the_flash_kernel(train_hlo):
    # the forward runs the named kernel; remat's second forward does not
    # (the block's policy keeps the kernel's output and log-sum-exp)
    assert _has_scope(train_hlo, "flash_fwd")
    assert "rematted_computation/attn/" in train_hlo
    assert "rematted_computation/attn/flash_fwd" not in train_hlo


@pytest.mark.parametrize("kernel", ["flash_bwd_dkv", "flash_bwd_dq"])
def test_backward_kernels_lie_under_attn_and_are_not_named_forward(
        train_hlo, kernel):
    """The backward kernels' operations carry the ``attn`` scope on their
    path, so ``train_attn_device_ms`` counts them, and their names do not
    start with ``flash_fwd``, so ``flash_fwd_device_ms`` stays the forward
    alone (benchmarks/harness/scopes.py: bucket by scope name on the path,
    the kernel part by ``startswith``)."""
    import re

    paths = set(re.findall(rf'"([^"]*/{kernel}/[^"]*)"', train_hlo))
    assert paths, kernel
    for path in paths:
        names = path.split("/")
        assert "attn" in names[:names.index(kernel)], path
        assert "flash_fwd" not in names, path
    assert not kernel.startswith("flash_fwd")


def test_backward_of_a_scope_carries_its_name(train_hlo):
    assert "transpose(jvp(logits))" in train_hlo


@pytest.mark.parametrize("scope", PAGED_SCOPES)
def test_forward_paged_hlo_carries_every_scope(paged_hlo, scope):
    assert _has_scope(paged_hlo, scope), scope


def test_kv_cache_lies_inside_attn(paged_hlo):
    assert "attn/kv_cache/" in paged_hlo


def test_scopes_change_no_arithmetic(monkeypatch):
    """Losses, gradients (through Adam's first moment) and updated
    parameters of three steps are bit-equal with the scopes on and with
    ``jax.named_scope`` patched to a no-op."""
    def three_steps():
        step, state, batch = _train_step()
        losses = []
        for _ in range(3):
            state, metrics = step(state, batch)
            losses.append(np.asarray(metrics["loss"]))
        return losses, jax.tree.map(np.asarray,
                                    (state.params, state.opt_state))

    named_losses, named_state = three_steps()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    step, state, batch = _train_step()
    assert not _has_scope(step.lower(state, batch).as_text(debug_info=True),
                          "optimizer")
    plain_losses, plain_state = three_steps()
    for a, b in zip(named_losses, plain_losses):
        assert a.tobytes() == b.tobytes()
    for a, b in zip(jax.tree.leaves(named_state),
                    jax.tree.leaves(plain_state)):
        assert a.tobytes() == b.tobytes()


# -- the host's phases ------------------------------------------------------

SERVE_CFG = gpt.GPTConfig(vocab_size=97, n_layers=2, d_model=32, n_heads=4,
                          d_ff=64, max_seq_len=48, remat=False,
                          attention_impl="mha")
PHASES = ("decode_prepare", "serving_decode_step", "decode_dispatch",
          "decode_readback", "decode_commit")


def _serve(telemetry):
    params = gpt.init(jax.random.PRNGKey(0), SERVE_CFG)
    engine = InferenceEngine(
        params, SERVE_CFG, buckets=BucketSpec.build(4, 16),
        cache=KVCacheConfig(num_blocks=16, block_size=8), telemetry=telemetry)
    try:
        handles = [engine.submit(p, max_new_tokens=4)
                   for p in ([5, 17, 3], [9] * 7)]
        return [h.result(timeout=120.0).tokens for h in handles]
    finally:
        engine.close()


def _inside(inner, outer):
    return (inner["tid"] == outer["tid"]
            and outer["ts_us"] <= inner["ts_us"]
            and inner["ts_us"] + inner["dur_us"]
            <= outer["ts_us"] + outer["dur_us"] + 0.2)


def test_engine_records_the_span_tree_of_every_decode_step():
    """A step's spans, now that the engine runs one step ahead
    (docs/observability.md "An engine iteration"): ``serving_decode_step``
    holds the turn, ``decode_prepare`` then ``decode_dispatch`` of this
    step and, where the step before it was still unread (``overlapped``),
    that step's ``decode_readback`` then ``decode_commit``. A step that no
    other follows (every row's last token is in flight) is read back
    outside every step's span, in a turn with nothing left to dispatch.
    A prefill call is dispatched before the turn's step and read after it
    (``serving_prefill``), outside the step's span."""
    tel = Telemetry(enabled=True)
    tokens = _serve(tel)
    events = [e for e in tel.tracer.events() if e.get("ph") != "i"]
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    steps = by_name["serving_decode_step"]
    assert len(steps) >= 3  # 4 tokens a request, the first from prefill
    for name in PHASES:
        # one of each a dispatched step, a step's own in the step's order
        assert len(by_name[name]) == len(steps), name
    iterations = by_name["engine_iteration"]

    def end(e):
        return e["ts_us"] + e["dur_us"]

    assert steps[0]["args"]["overlapped"] == 0
    assert any(s["args"]["overlapped"] for s in steps)
    for i, step in enumerate(steps):
        (iteration,) = [it for it in iterations if _inside(step, it)]
        prepare, dispatch, readback, commit = (
            by_name[n][i] for n in ("decode_prepare", "decode_dispatch",
                                    "decode_readback", "decode_commit"))
        # this step's prepare then dispatch inside its span; its read-back
        # and commit after them, in that order, a turn late or in a drain
        assert _inside(prepare, step) and _inside(dispatch, step)
        assert end(prepare) <= dispatch["ts_us"] + 0.2
        assert end(dispatch) <= readback["ts_us"] + 0.2
        assert end(readback) <= commit["ts_us"] + 0.2
        assert prepare["depth"] == dispatch["depth"] == step["depth"] + 1 \
            == iteration["depth"] + 2
        (held,) = [it for it in iterations if _inside(readback, it)]
        assert _inside(commit, held)
        later = steps[i + 1] if i + 1 < len(steps) else None
        if later is not None and later["args"]["overlapped"]:
            # read inside the next step's span, after that step's dispatch
            assert _inside(readback, later) and _inside(commit, later)
            assert end(by_name["decode_dispatch"][i + 1]) \
                <= readback["ts_us"] + 0.2
        else:
            # a drain: outside every step's span, before the next step
            assert not any(_inside(readback, s) for s in steps)
            assert not any(_inside(commit, s) for s in steps)
            assert later is None or end(commit) <= later["ts_us"] + 0.2
        assert step["args"].pop("overlapped") in (0, 1)
        for e in (prepare, step, dispatch, readback, commit):
            # the uniform cache's rows: attended, and spanned by the tables
            assert e["args"] == step["args"]
            assert set(e["args"]) == {"rows", "batch", "kv_rows",
                                      "table_rows"}
            assert 1 <= e["args"]["rows"] <= e["args"]["batch"]
            assert e["args"]["rows"] <= e["args"]["kv_rows"] \
                < e["args"]["table_rows"]
    # a prefill call: prepare then dispatch, the turn's decode step if
    # there is one, then the read-back, inside no step's span
    calls = by_name["serving_prefill"]
    assert len(by_name["prefill_prepare"]) == len(
        by_name["prefill_dispatch"]) == len(calls)
    for prepare, dispatch, call in zip(by_name["prefill_prepare"],
                                       by_name["prefill_dispatch"], calls):
        (iteration,) = [it for it in iterations if _inside(call, it)]
        assert _inside(prepare, iteration) and _inside(dispatch, iteration)
        assert end(prepare) <= dispatch["ts_us"] + 0.2
        assert end(dispatch) <= call["ts_us"] + 0.2
        assert dispatch["args"] == {k: call["args"][k]
                                    for k in ("batch", "length")}
        for step in steps:
            assert not _inside(call, step) and not _inside(step, call)
            if _inside(step, iteration):  # dispatched between the two
                assert end(dispatch) <= step["ts_us"] + 0.2
                assert end(step) <= call["ts_us"] + 0.2
    # admission is spanned alone (the wait on the condition never is), and
    # a prefill has its prepare phase
    assert by_name["admit"]
    assert all(not _inside(a, it) for a in by_name["admit"]
               for it in iterations)
    assert all(len(t) == 4 for t in tokens)


def test_engine_without_tracer_records_nothing_and_serves_the_same():
    traced = Telemetry(enabled=True)
    off = Telemetry(enabled=False)
    assert _serve(off) == _serve(traced)
    assert off.tracer.events() == []
    assert _serve(None) == _serve(off)


# -- spans on the profiler's clock ------------------------------------------

def test_enabled_span_is_a_trace_annotation_in_a_profiler_trace(tmp_path):
    tracer = spans.Tracer(enabled=True)
    quiet = spans.Tracer(enabled=False)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with tracer.span("scopes_probe_outer", step=1):
            with tracer.span("scopes_probe_inner"):
                jnp.ones((8, 8)).sum().block_until_ready()
        with quiet.span("scopes_probe_quiet"):
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    found = {}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("scopes_probe"):
                    found[e.name] = (e.start_ns, e.start_ns + e.duration_ns)
    assert set(found) == {"scopes_probe_outer", "scopes_probe_inner"}
    outer, inner = found["scopes_probe_outer"], found["scopes_probe_inner"]
    assert outer[0] <= inner[0] and inner[1] <= outer[1]
    # the tracer's own record and the annotation time the same span
    (rec,) = [e for e in tracer.events() if e["name"] == "scopes_probe_outer"]
    assert abs((outer[1] - outer[0]) / 1e3 - rec["dur_us"]) < 500.0


def test_spans_pull_no_jax_into_a_process_without_it(monkeypatch):
    monkeypatch.setitem(sys.modules, "jax", None)
    assert spans._trace_annotation() is None
    tracer = spans.Tracer(enabled=True)
    with tracer.span("no_jax_here"):
        pass
    assert [e["name"] for e in tracer.events()] == ["no_jax_here"]
