"""Device time of one decode step under the scope ``eva_attn`` (inside
``attn``): EVA's joint softmax over a sequence's window rows and chunk
summaries and the two weighted sums, on the gathered context
(``ops/attention.py:eva_attention``).
"""
from benchmarks.harness import eva

LAYER = "serving scheduler"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return eva.scope_step_ms(ctx, "eva_attn")
