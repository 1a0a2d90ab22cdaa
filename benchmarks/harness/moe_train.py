"""What the readers of a trained expert model share (``layer_metrics/
train_mla_attn_*``, ``train_moe_*``, ``train_mtp_*``,
``train_flash_roofline_pct``; PR 43): device time of a train step under a
scope that ``harness/scopes.py``'s fixed vocabulary does not hold or inside
named kernels, what the loss's own metrics said of the step, and the
operations a step has to do, as functions of the cell's configuration.

The program (``determined_clone_tpu/models/glm_moe_lite.py``,
``ops/moe.py:routed_experts_trained``) names the scopes ``mla_attn`` inside
``attn``, ``moe_route``, ``moe_experts`` and ``moe_shared`` inside ``mlp``,
``mtp`` around the whole prediction module (whose inner scopes keep these
names) and ``bias_update`` inside ``optimizer``; the trainer's
``training_report`` span (one a scheduling unit) carries the report's
metrics as its args, the means over the unit's steps: ``moe_pairs_held``,
``moe_experts_hit``, ``moe_load_max_over_mean``, ``loss_next``,
``loss_mtp``. Where a trace or a span has none of this, every function here
returns None and nothing raises.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

from benchmarks.harness import device, scopes, trace
from benchmarks.harness.eva import on_path

STEP_SPAN = scopes.STEP_SPAN["train"]
REPORT_SPAN = "training_report"
FLASH_KERNELS = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")


def is_expert_model(config: Dict[str, Any]) -> bool:
    return "moe_intermediate_size" in config and "training" in config


def step_seconds(parsed: scopes.Parsed, names: Sequence[str] = (),
                 kernels: Sequence[str] = ()) -> Optional[float]:
    """Device seconds per train step of the operations with one of
    ``names`` on their scope path, or whose result is named after one of
    ``kernels``: self times (``scopes._self_times``) inside the programs
    that ran within a ``train_dispatch`` annotation, over the executions of
    the step program, averaged over the chips, as ``scopes.reduce_scopes``
    counts its buckets. None where the trace has no such step or no such
    operation."""
    spans = sorted(parsed.spans(STEP_SPAN))
    if not spans or not parsed.ops:
        return None
    total, chips, found = 0.0, 0, False
    for chip, ops in parsed.ops.items():
        stepped = sorted((s, s + d, n) for n, s, d
                         in parsed.modules.get(chip, ())
                         if scopes._inside(spans, s + d / 2))
        if not stepped:
            continue
        by_program: Dict[str, List[float]] = {}
        for s, e, n in stepped:
            by_program.setdefault(n, []).append(e - s)
        steps = len(max(by_program.values(), key=sum))
        windows = [(s, e) for s, e, _ in stepped]
        meta = parsed.op_meta[chip]
        wanted: Dict[int, bool] = {}
        seconds = 0.0
        for key, self_s in scopes._self_times(
                [o for o in ops if scopes._inside(windows, o[0])]):
            if key not in wanted:
                line, path = meta.get(key, ("", ""))
                wanted[key] = on_path(path, names) or (
                    bool(kernels)
                    and trace.parse_op(line)[0].startswith(tuple(kernels)))
            if wanted[key]:
                seconds += self_s
                found = True
        total += seconds / steps
        chips += 1
    return total / chips if chips and found else None


def scope_step_seconds(ctx: Dict[str, Any], names: Sequence[str] = (),
                       kernels: Sequence[str] = ()) -> Optional[float]:
    parsed = scopes.for_cell(ctx) if ctx["kind"] == "train" else None
    return None if parsed is None else step_seconds(parsed, names, kernels)


def scope_step_ms(ctx: Dict[str, Any], *names: str) -> Optional[float]:
    seconds = scope_step_seconds(ctx, names)
    return None if seconds is None else 1e3 * seconds


def reported(ctx: Dict[str, Any], name: str) -> Optional[float]:
    """The mean of the metric ``name`` over the window's training reports
    (each the mean over a unit's steps)."""
    values = [a[name] for _, _, a in scopes.span_seconds(ctx, REPORT_SPAN)
              if isinstance(a.get(name), (int, float))]
    return sum(values) / len(values) if values else None


# ---------------------------------------------------------------------------
# the operations a step has to do, from the configuration
# ---------------------------------------------------------------------------

def _layers(config: Dict[str, Any]) -> Dict[str, int]:
    total = int(config["num_hidden_layers"])
    dense = int(config["first_k_dense_replace"])
    mtp = int(config["num_nextn_predict_layers"])
    return {"dense": dense, "expert": total - dense + mtp,
            "attention": total + mtp, "mtp": mtp}


def held_experts(config: Dict[str, Any]) -> int:
    """Held experts over all expert layers, the prediction module's too."""
    return _layers(config)["expert"] * int(config["n_routed_experts"])


def attended_pairs(seq_len: int) -> float:
    """(query, key) pairs one causal head attends over."""
    return seq_len * (seq_len + 1) / 2


def expert_products_flops(pairs_held: float, config: Dict[str, Any]) -> float:
    """Operations of the held experts' grouped products for ``pairs_held``
    token-expert pairs (summed over the layers): three products of
    ``hidden_size x moe_intermediate_size`` a pair, in three passes (the
    forward, the rows' gradient, the weights' gradient). Remat's second
    forward and the backward's own remaking of the up-products are not
    counted."""
    return 3 * 3 * 2.0 * int(config["hidden_size"]) \
        * int(config["moe_intermediate_size"]) * pairs_held


def flash_kernels_flops(config: Dict[str, Any], seq_len: int, rows: int
                        ) -> float:
    """Operations the three flash kernels execute in a step, by
    ``ops/flash_attention.py:flash_cost``'s formula: ``2 x products x
    attended pairs x head size`` a head, with 2 products in the forward
    kernel, 4 in dK / dV and 3 in dQ; the forward kernel runs twice a layer
    under remat (its second run is inside the time this is divided by)."""
    head = int(config["qk_nope_head_dim"]) + int(config["qk_rope_head_dim"])
    forwards = 2 if config["training"]["remat"] else 1
    products = 2 * forwards + 4 + 3
    return 2.0 * products * attended_pairs(seq_len) * head * rows \
        * int(config["num_attention_heads"]) * _layers(config)["attention"]


def step_flops(config: Dict[str, Any], seq_len: int, rows: int) -> float:
    """Operations the forward and backward passes of one step need;
    recomputation is not counted. Per token, ``3 x 2 x`` the parameters it
    multiplies: the attention projections in every layer, the dense FFNs,
    in every expert layer the shared expert, the router and ``num_experts_
    per_tok x held / published`` routed experts (the pairs expected here),
    the prediction module's ``eh_proj``, and one head of ``vocab_size``
    rows for each loss. Causal attention: 2 products forward and 5
    backward (one remakes the scores) over the attended pairs."""
    D, H = int(config["hidden_size"]), int(config["num_attention_heads"])
    nope, rope = int(config["qk_nope_head_dim"]), \
        int(config["qk_rope_head_dim"])
    v, rq, rkv = int(config["v_head_dim"]), int(config["q_lora_rank"]), \
        int(config["kv_lora_rank"])
    n = _layers(config)
    attention = D * rq + rq * H * (nope + rope) + D * (rkv + rope) \
        + rkv * H * (nope + v) + H * v * D
    expert = 3 * D * int(config["moe_intermediate_size"])
    published = int(config["published_n_routed_experts"])
    routed = int(config["num_experts_per_tok"]) \
        * int(config["n_routed_experts"]) / published * expert
    multiplied = n["attention"] * attention \
        + n["dense"] * 3 * D * int(config["intermediate_size"]) \
        + n["expert"] * (expert + D * published + routed) \
        + n["mtp"] * 2 * D * D \
        + (1 + n["mtp"]) * D * int(config["vocab_size"])
    products = 3 * 2.0 * multiplied * seq_len * rows
    scores = 2.0 * (2 + 5) * attended_pairs(seq_len) * (nope + rope) * H \
        * rows * n["attention"]
    return products + scores


# ---------------------------------------------------------------------------
# what the layer metrics call
# ---------------------------------------------------------------------------

def _peak_flops() -> Optional[float]:
    import jax

    peak = device.PEAKS.get(jax.devices()[0].device_kind)
    return None if peak is None else peak["bf16_flops_per_s"]


def _sizes(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    cell = ctx["cell"]
    if ctx["kind"] != "train" or not is_expert_model(cell.config):
        return None
    return {"config": cell.config, "seq_len": int(cell.traffic["seq_len"]),
            "rows": int(cell.config["training"]["global_batch_size"])}


def pairs_per_held_expert(ctx: Dict[str, Any]) -> Optional[float]:
    sizes = _sizes(ctx)
    pairs = reported(ctx, "moe_pairs_held") if sizes else None
    return None if pairs is None else pairs / held_experts(sizes["config"])


def experts_roofline_pct(ctx: Dict[str, Any]) -> Optional[float]:
    """100 x the least time the chip's matrix unit could take for the held
    experts' products of a step, over the device time a step spends under
    ``moe_experts``: bound by operations."""
    sizes = _sizes(ctx)
    pairs = reported(ctx, "moe_pairs_held") if sizes else None
    seconds = scope_step_seconds(ctx, ("moe_experts",)) if sizes else None
    peak = _peak_flops()
    if pairs is None or not seconds or peak is None:
        return None
    return 100.0 * expert_products_flops(pairs, sizes["config"]) / peak \
        / seconds


def flash_roofline_pct(ctx: Dict[str, Any]) -> Optional[float]:
    """100 x the least time the matrix unit could take for what the three
    flash kernels execute in a step, over their device time: bound by
    operations at 8192 positions (2048 operations a byte moved)."""
    sizes = _sizes(ctx)
    seconds = scope_step_seconds(ctx, kernels=FLASH_KERNELS) if sizes \
        else None
    peak = _peak_flops()
    if not seconds or peak is None:
        return None
    return 100.0 * flash_kernels_flops(**sizes) / peak / seconds


def mfu_pct(ctx: Dict[str, Any]) -> Optional[float]:
    """The step's operations (``step_flops``) times the steps a second of
    the traced run, over the chips' peak."""
    sizes = _sizes(ctx)
    if sizes is None or not ctx["tokens_per_s"] > 0:
        return None
    steps_per_s = ctx["tokens_per_s"] / (sizes["seq_len"] * sizes["rows"])
    peak = ctx["cell"].chips * ctx["peak_flops_per_s"]
    return 100.0 * step_flops(**sizes) * steps_per_s / peak
