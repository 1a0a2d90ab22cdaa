"""The least time the chip's memory could take to read the rows a decode step
attends in the full layer, over the device time the step spends under the
scope ``full_attn``: bound by bytes.

Bytes (``harness/afmoe.py:full_step_bytes``): ``kv_rows`` (every cached
position of every row, from the rows' lengths) x a K and a V row of 1024
bfloat16 values (4096 B), in the one full layer. A program that reads a
row's blocks to its length and no further cannot read over 100.
"""
from benchmarks.harness import afmoe

LAYER = "serving scheduler"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return afmoe.hbm_share(ctx, ("full_attn",), afmoe.full_step_bytes)
