"""The paged-attention readers (PR 32: ``harness/paged.py``,
``layer_metrics/decode_paged_attn_device_ms``,
``paged_attn_hbm_roofline_pct``) on made-up traces and spans: with the
program's scope and span args they read the scope's time a step and the
attended rows' bytes over it; without either (the parent's program, a cell
of another family) they read None and raise nothing.
"""
import json

import jax
import pytest

from benchmarks.harness import device, paged, scopes, spec

READERS = ("decode_paged_attn_device_ms", "paged_attn_hbm_roofline_pct")
CELLS = ("gpt2-medium.serve-closed", "gpt2-xl.serve-closed")


def _made_up_trace(scope="paged_attn", n_steps=3):
    """One chip; ``n_steps`` decode programs, each inside a
    ``serving_decode_step``: 50 ms of scatters under ``kv_cache``, 200 ms
    of a kernel under ``scope``, 100 ms of the projections under ``attn``
    alone, 150 ms under ``mlp``."""
    p = scopes.Parsed.__new__(scopes.Parsed)
    chip = "/device:TPU:0"
    body = "jit(forward_paged)/while/body/closed_call/"
    fusion = "%f = bf16[32,1024]{1,0} fusion(bf16[32]{0} %x), kind=kLoop"
    p.op_meta = {chip: {
        1: ("%w.1 = (s32[]) while((s32[]) %t), body=%b",
            "jit(forward_paged)/while:"),
        2: (fusion, body + "attn/kv_cache/scatter:"),
        3: ("%c = bf16[32,1,1024]{2,1,0} custom-call(%q), "
            "custom_call_target=\"tpu_custom_call\"",
            body + f"attn/{scope}/pallas_call:"),
        4: (fusion, body + "attn/dot_general:"),
        5: (fusion, body + "mlp/dot_general:"),
    }}
    p.modules = {chip: []}
    p.host, ops = [], []
    for i in range(n_steps):
        t = 1.0 + i
        p.host += [("engine_iteration", t - 0.05, 0.9),
                   ("serving_decode_step", t - 0.02, 0.8)]
        p.modules[chip].append(("jit_forward_paged(1)", t, 0.7))
        ops += [(t, t + 0.6, 1), (t, t + 0.05, 2), (t + 0.05, t + 0.25, 3),
                (t + 0.25, t + 0.35, 4), (t + 0.35, t + 0.5, 5)]
    p.ops = {chip: sorted(ops, key=lambda o: (o[0], -o[1]))}
    p.reductions = {}
    return p


def _ctx(cell, args):
    """Three recorded decode steps with ``args``, as long as the trace's."""
    return {"kind": "serve", "cell": spec.load_cell(cell),
            "spans": [("serving_decode_step", 100.0 + i, 0.8,
                       dict(a, batch=32, rows=20))
                      for i, a in enumerate(args)]}


def _read(ctx):
    return {n: spec.load_module("layer_metrics", n).read(ctx)
            for n in READERS}


def test_both_gpt_serve_cells_list_the_readers_and_no_other_cell_does():
    with open(spec.MANIFEST) as f:
        manifest = json.load(f)
    for w in manifest["workloads"]:
        listed = set(spec.load_cell(w["name"], manifest=manifest).per_layer)
        assert (set(READERS) <= listed) == (w["name"] in CELLS), w["name"]
        assert not set(READERS) & listed or set(READERS) <= listed


@pytest.mark.parametrize("cell,width,layers", [(CELLS[0], 1024, 24),
                                               (CELLS[1], 1600, 48)])
def test_a_traced_run_reads_the_scopes_time_and_the_rows_bytes(
        monkeypatch, cell, width, layers):
    """The share counts the useful columns (``n_embd``, not the padded pool
    row: 1600 of xl's 1664) of the rows attended at their real lengths."""
    parsed = _made_up_trace()
    monkeypatch.setattr(scopes, "for_cell", lambda ctx: parsed)
    monkeypatch.setitem(device.PEAKS, jax.devices()[0].device_kind,
                        {"hbm_bytes_per_s": 1e9})
    rows = [4000, 5000, 6000]
    ctx = _ctx(cell, [{"kv_rows": n, "table_rows": 32 * 1024}
                      for n in rows])
    assert paged.attended_bytes(5000, ctx["cell"].config) \
        == 5000 * 2 * width * 2 * layers
    read = _read(ctx)
    assert read["decode_paged_attn_device_ms"] == pytest.approx(200.0)
    assert read["paged_attn_hbm_roofline_pct"] == pytest.approx(
        100.0 * 5000 * 2 * width * 2 * layers / 1e9 / 0.2)
    # the scatters stay under kv_cache, and attn still holds all three
    assert spec.load_module("layer_metrics", "decode_kv_cache_device_ms"
                            ).read(dict(ctx, trace={})) \
        == pytest.approx(50.0)


def test_readers_find_nothing_without_the_scope_or_the_args(monkeypatch):
    """The parent's program (no ``paged_attn`` scope, no row args on its
    spans), a program that has one of the two, a cell of another family
    whose spans carry ``kv_rows`` of their own, a training cell, a device
    the peak table lacks: None, and nothing raised."""
    args = [{"kv_rows": 5000, "table_rows": 32768}] * 3
    with_scope, without = _made_up_trace(), _made_up_trace("other")
    for parsed, a, cell in (
            (without, [{}] * 3, CELLS[0]),
            (without, args, CELLS[0]),
            (with_scope, [{"kv_rows": 5000, "selected_rows": 9,
                           "state_slots": 8}] * 3,
             "minicpm-sala-9b.serve-long-closed")):
        monkeypatch.setattr(scopes, "for_cell", lambda ctx, p=parsed: p)
        ctx = _ctx(cell, a)
        assert spec.load_module(
            "layer_metrics", "paged_attn_hbm_roofline_pct").read(ctx) is None
        if parsed is without:
            assert _read(ctx) == dict.fromkeys(READERS)
    monkeypatch.setattr(scopes, "for_cell", lambda ctx: with_scope)
    assert _read(dict(_ctx(CELLS[0], args), kind="train")) \
        == dict.fromkeys(READERS)
    monkeypatch.delitem(device.PEAKS, jax.devices()[0].device_kind,
                        raising=False)
    assert _read(_ctx(CELLS[0], args))["paged_attn_hbm_roofline_pct"] is None
    monkeypatch.setattr(scopes, "for_cell", lambda ctx: None)
    assert _read(_ctx(CELLS[0], args)) == dict.fromkeys(READERS)


def test_traced_steps_are_matched_by_their_durations():
    parsed = scopes.Parsed.__new__(scopes.Parsed)
    parsed.host = [("serving_decode_step", 10.0, 0.030),
                   ("serving_decode_step", 10.1, 0.050)]
    args = [{"kv_rows": 100 * i, "table_rows": 4096} for i in range(5)]
    durations = [0.041, 0.020, 0.0301, 0.0502, 0.041]
    ctx = {"spans": [("serving_decode_step", 100.0 + i, d, args[i])
                     for i, d in enumerate(durations)]}
    assert paged.traced_steps(ctx, parsed) == args[2:4]
    ctx["spans"][2] = ("serving_decode_step", 102.0, 0.0301, {"rows": 1})
    assert paged.traced_steps(ctx, parsed) == args[3:4]
