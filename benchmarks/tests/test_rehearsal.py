"""A whole run of one train and one serve cell on the CPU, at a tiny
configuration kept in ``data/`` (added the way ``README.md`` says: files
only), calling the harness past its look for a chip. Then the same runs
with the timed path broken underneath, and the control: each has to come
out as not correct.

The tiny cells' limits were set as the real ones were (PERF.md, section 2),
from readings at the tiny size on the CPU (PR 23): program largest over six
seeds / fp8 control smallest: first_grad_leaf_difference 0.0177 / 0.109,
first_grad_leaf_norm_gap 0.0028 / 0.0095 (64 wide),
loss_rel_gap 1.6e-5 / 1.2e-4, served_token_logit_gap 0.00034 / 0.0044 (16 wide; one
seed of six reads 0 for the control: so few near-ties at this size).
"""
import time

import pytest

from benchmarks.harness import serve, spec, train
from benchmarks.tests.conftest import DATA

ROOTS = (DATA, spec.BENCH_DIR)
FAKE_DEVICE = {"kind": "TPU v5 lite"}  # only the peak table is looked up


def _run(module, cell_name, seed=2 ** 31 + 21, seconds=2.0, traced=False,
         **kw):
    cell = spec.load_cell(cell_name, roots=ROOTS)
    return cell, module.run(cell, seed, seconds, traced, time.monotonic(),
                            dict(FAKE_DEVICE), **kw)


def _failed(result):
    return [c["check"] for c in result["checks"] if not c["ok"]]


def test_train_cell_runs_and_is_correct():
    cell, r = _run(train, "tiny.train", traced=True)
    assert r["correct"], _failed(r)
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["values"]["train_tokens_per_s_per_chip"] > 0
    assert r["values"]["setup_s"] > 0
    ctx = r["layer_context"]
    names = {s[0] for s in ctx["spans"]}
    assert {"train_dispatch", "dataload_wait", "host_sync"} <= names
    read = {n: spec.load_module("layer_metrics", n).read(ctx)
            for n in cell.per_layer}
    assert read["train_dispatch_ms"] > 0
    assert 0 <= read["dataload_wait_pct"] < 100
    assert read["train_mfu_pct"] > 0
    # no device plane in a CPU trace: the reader finds nothing to read
    assert read["device_idle_pct.train"] is None


def test_serve_cell_runs_and_is_correct():
    cell, r = _run(serve, "tiny.serve", traced=True)
    assert r["correct"], _failed(r)
    assert r["attempted"] > 10 and r["failed"] == 0
    v = r["values"]
    assert v["serve_tokens_per_s"] > 0 and v["ttft_p95_ms"] > 0
    assert v["tpot_p95_ms"] > 0
    ctx = r["layer_context"]
    read = {n: spec.load_module("layer_metrics", n).read(ctx)
            for n in cell.per_layer}
    assert read["decode_step_ms"] > 0 and read["queue_wait_ms"] >= 0
    assert 1 <= read["decode_rows_per_step"] <= 5
    assert {"serving_decode_step", "serving_prefill"} <= {
        s[0] for s in ctx["spans"]}


def test_train_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch):
    from determined_clone_tpu.training import trainer as trainer_mod

    real = trainer_mod.make_train_step

    def frozen(loss_fn, tx, **kw):
        step = real(loss_fn, tx, **dict(kw, donate=False))

        def call(state, *batches):
            _, metrics = step(state, *batches)
            return state, metrics

        return call

    monkeypatch.setattr(trainer_mod, "make_train_step", frozen)
    _, r = _run(train, "tiny.train")
    assert not r["correct"]
    assert {"first_grad_leaf_norm_gap", "first_grad_leaf_difference",
            "param_change_leaf_norm_gap"} <= set(_failed(r))


def test_train_step_that_leaves_out_half_the_batch_is_not_correct(
        monkeypatch):
    from determined_clone_tpu.models import gpt

    real = gpt.loss_fn

    def half(params, cfg, tokens, targets, *a, **kw):
        n = tokens.shape[0] // 2
        return real(params, cfg, tokens[:n], targets[:n], *a, **kw)

    monkeypatch.setattr(gpt, "loss_fn", half)
    _, r = _run(train, "tiny.train")
    assert not r["correct"]
    assert "loss_rel_gap" in _failed(r)


def test_served_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    from determined_clone_tpu.serving import engine as engine_mod

    real = engine_mod.forward_paged  # the entry ``make_paged_forward`` jits

    def altered(params, cfg, rows, tables, last_tokens, *pools):
        # every token the program samples, as the host reads it and as the
        # next step takes it from the device, is its neighbour instead
        tokens, *rest = real(params, cfg, rows, tables, last_tokens, *pools)
        return (tokens ^ 1, *rest)

    monkeypatch.setattr(engine_mod, "forward_paged", altered)
    _, r = _run(serve, "tiny.serve")
    assert not r["correct"]
    assert _failed(r) == ["served_token_logit_gap"]


@pytest.mark.parametrize("n_requests,outlasts", [(128, False), (4096, True)],
                         ids=["short_list", "ample_list"])
def test_a_list_that_runs_dry_inside_the_window_is_not_correct(
        n_requests, outlasts):
    """A closed loop whose list is used up sends its clients home: the batch
    thins before the window closes and the rate reads lower the faster the
    engine is (``gpt2-medium.serve-closed``, PRs 34-38). Such a run says so."""
    cell = spec.load_cell("tiny.serve", roots=ROOTS)
    cell.traffic = dict(cell.traffic, n_requests=n_requests)
    r = serve.run(cell, 2 ** 31 + 23, 2.0, False, time.monotonic(),
                  dict(FAKE_DEVICE))
    (row,) = [c for c in r["checks"]
              if c["check"] == "requests_outlast_window"]
    taken, clients, offered = row["detail"]
    assert offered == n_requests and clients == 4
    assert row["ok"] == outlasts == (taken + clients <= offered)
    assert r["correct"] == outlasts


@pytest.mark.parametrize("seed", [1, 2, 4])
def test_control_train_in_fp8_is_not_correct(seed):
    cell, r = _run(train, "tiny.train", seed=seed, seconds=0.0,
                   control="fp8")
    over = [k for k, v in r["control"].items() if v > cell.limits[k]]
    assert "first_grad_leaf_difference" in over, r["control"]
    # and the program itself stays inside every limit on the same seed
    assert all(c["ok"] for c in r["checks"] if c["limit"] > 0), r["checks"]


@pytest.mark.parametrize("seed", [1, 2, 5])
def test_control_serve_in_fp8_is_not_correct(seed):
    # which requests fall into the window depends on the host's speed, so
    # the window is long and 96 requests are checked: near-ties are rare here
    cell, r = _run(serve, "tiny.serve", seed=seed, seconds=4.0,
                   control="fp8")
    gap = r["control"]["served_token_logit_gap"]
    assert gap > cell.limits["served_token_logit_gap"], gap
    assert r["correct"], _failed(r)
