"""Device time of one decode step under the scope ``eva_summarize`` (inside
``attn``): gathering the block of the chunk a step may complete, pooling
its summary (``ops/attention.py:eva_chunk_summary``) and scattering it
into the summary blocks.
"""
from benchmarks.harness import eva

LAYER = "serving scheduler"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return eva.scope_step_ms(ctx, "eva_summarize")
