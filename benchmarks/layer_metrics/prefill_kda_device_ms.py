"""Device time of one prefill call (a ``serving_prefill`` span) under the scope
``kda``: the chunked delta rule of the four KDA layers, chunks of 64 tokens:
the decayed Gram matrices a chunk, the solve, and the walk over the chunks
with the state (``ops/kda.py``, the chunk form).
"""
from benchmarks.harness import sala

LAYER = "serving scheduler"
UNIT = "ms/slice"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return sala.prefill_scope_ms(ctx, "kda")
