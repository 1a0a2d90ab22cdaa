"""The least time the chip's memory could take to read the rows a decode step
attends in the sliding layers, over the device time the step spends under
the scope ``window_attn``: bound by bytes.

Bytes (``harness/afmoe.py:window_step_bytes``): ``window_rows``
(``min(length, 4096)`` a row, from the rows' lengths) x 4 sliding layers x a
K and a V row of 1024 bfloat16 values (4096 B). Attended rows at real
lengths: a program that reads no more than it must cannot read over 100.
"""
from benchmarks.harness import afmoe

LAYER = "serving scheduler"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return afmoe.hbm_share(ctx, ("window_attn",), afmoe.window_step_bytes)
