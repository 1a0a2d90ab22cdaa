"""Device time of one decode step under the scope ``kv_cache`` (inside
``attn``): the scatter of the new tokens' K and V into the paged pool and
the gather of every row's whole context from it (ROADMAP A3's full-length
gathers).
"""
from benchmarks.harness import scopes

LAYER = "serving scheduler"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return scopes.step_ms(ctx, "serve", "parts", "kv_cache")
