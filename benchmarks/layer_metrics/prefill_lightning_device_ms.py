"""Device time of one prefill slice (a ``serving_prefill`` span) under the
scope ``lightning``: the chunked form of lightning attention over the
slice, with the state read before it and written after it
(``ops/lightning_attention.py``).
"""
from benchmarks.harness import sala

LAYER = "serving scheduler"
UNIT = "ms/slice"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return sala.prefill_scope_ms(ctx, "lightning")
