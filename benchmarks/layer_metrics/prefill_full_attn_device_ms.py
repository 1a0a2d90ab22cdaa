"""Device time of one prefill call (a ``serving_prefill`` span) under the scope
``full_attn``: in the full layer, a slice's queries 256 at a time against
every earlier position's blocks and their own, 1024 positions a pass under
an online softmax (``ops/window_attention.py:slice_rows``), and the output
gate.
"""
from benchmarks.harness import sala

LAYER = "serving scheduler"
UNIT = "ms/slice"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return sala.prefill_scope_ms(ctx, "full_attn")
