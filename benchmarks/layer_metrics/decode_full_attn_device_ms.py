"""Device time of one decode step under the scope ``full_attn`` (inside
``attn`` of the full-attention layer): every row's blocks read through its
table to its real length, 64 blocks a pass under an online softmax, grouped
heads, and the output gate (``ops/window_attention.py:decode_rows``).
"""
from benchmarks.harness import eva

LAYER = "serving scheduler"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return eva.scope_step_ms(ctx, "full_attn")
