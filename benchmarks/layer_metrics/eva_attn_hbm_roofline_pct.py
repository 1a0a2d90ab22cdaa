"""The least time the chip's memory could take to read the cache rows a
decode step attends, over the device time the step spends under the scopes
``kv_cache`` (both kinds' scatters and gathers) and ``eva_attn`` (the joint
softmax over what was gathered): bound by bytes, not by operations.

Bytes: the rows the traced steps attended at the sequences' real lengths
(the program's span args), a K and a V row of ``hidden_size`` bfloat16
values in each of ``num_hidden_layers`` layers
(``harness/eva.py:attended_cache_bytes``), a step. The program reads every
row of the pool's tables, attended or masked (a full batch in place, a
smaller one through a gathered copy), so this reads far under 100 until
attention skips the blocks past the sequences' real lengths.
"""
from benchmarks.harness import device, eva, scopes

LAYER = "serving scheduler"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    parsed = scopes.for_cell(ctx) if ctx["kind"] == "serve" else None
    if parsed is None:
        return None
    seconds = eva.scope_step_seconds(parsed, ("kv_cache", "eva_attn"))
    steps = eva.traced_steps(ctx, parsed)
    config = ctx["cell"].config
    if not seconds or not steps or "hidden_size" not in config:
        return None
    rows = sum(a["window_rows"] + a["summary_rows"] for a in steps) \
        / len(steps)
    needed = eva.attended_cache_bytes(rows, int(config["hidden_size"]),
                                      int(config["num_hidden_layers"]))
    import jax

    peak = device.PEAKS.get(jax.devices()[0].device_kind)
    if peak is None:  # no entry in the peak table: nothing to hold it to
        return None
    return 100.0 * needed / peak["hbm_bytes_per_s"] / seconds
