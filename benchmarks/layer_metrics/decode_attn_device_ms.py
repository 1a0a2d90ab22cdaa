"""Device time of one decode step under the scope ``attn``: the attention half
of every block, the KV scatter and gathers (``decode_kv_cache_device_ms``)
included. A step is every program that ran inside a ``serving_decode_step``
span; the buckets (with ``embed`` and ``logits``) add up to the step's busy
time.
"""
from benchmarks.harness import scopes

LAYER = "serving scheduler"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return scopes.step_ms(ctx, "serve", "buckets", "attn")
