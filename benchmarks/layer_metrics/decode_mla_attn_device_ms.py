"""Device time of one decode step under the scope ``mla_attn`` (inside
``attn`` of every layer): the absorbed query, the gather of the chosen
latents by position through the block table, scores, softmax, the weighted
sum of rows and ``W_UV`` after it (``ops/mla_attention.py:mla_decode``).
"""
from benchmarks.harness import eva

LAYER = "serving scheduler"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return eva.scope_step_ms(ctx, "mla_attn")
