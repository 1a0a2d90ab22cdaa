"""Device time of one decode step under the scope ``unscoped``: operations that
carry none of the scope names: the compiler's copies of the KV pool
(``decode_pool_copy_device_ms``) and the weight casts hoisted out of the
layer scan. A step is every program that ran inside a
``serving_decode_step`` span; the buckets (with ``embed`` and ``logits``)
add up to the step's busy time.
"""
from benchmarks.harness import scopes

LAYER = "serving scheduler"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return scopes.step_ms(ctx, "serve", "buckets", "unscoped")
