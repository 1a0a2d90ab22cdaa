"""Device time of one prefill slice (a ``serving_prefill`` span) under the
scope ``mla_attn``: the absorbed form over the sequence's cached latents a
chunk of blocks at a time, under the mask of chosen positions, at dense cost
(``ops/mla_attention.py:mla_slice``).
"""
from benchmarks.harness import sala

LAYER = "serving scheduler"
UNIT = "ms/slice"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return sala.prefill_scope_ms(ctx, "mla_attn")
