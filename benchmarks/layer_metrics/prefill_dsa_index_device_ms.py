"""Device time of one prefill slice (a ``serving_prefill`` span) under the
scope ``dsa_index``: the slice's indexer keys and their write, its queries'
scores against every cached key up to the slice's end, and the exact top-k
of up to 2048 x 32768 scores (``ops/dsa_index.py``).
"""
from benchmarks.harness import sala

LAYER = "serving scheduler"
UNIT = "ms/slice"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return sala.prefill_scope_ms(ctx, "dsa_index")
