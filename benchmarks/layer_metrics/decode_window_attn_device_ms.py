"""Device time of one decode step under the scope ``window_attn`` (inside
``attn`` of the sliding-window layers): every row's ring read through its
slot's table from the window's first position to the row's last, 32 blocks
a pass under an online softmax, grouped heads, and the output gate
(``ops/window_attention.py:decode_rows``), in the four sliding layers.
"""
from benchmarks.harness import eva

LAYER = "serving scheduler"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return eva.scope_step_ms(ctx, "window_attn")
