"""Device time of one decode step in the compiler's copies of the KV pool: of
the unscoped operations, ``copy`` / ``copy-done`` / ``dynamic-slice`` /
``dynamic-update-slice`` (and fusions named after them) whose result has the
shape of the pool or of one layer's slice of it (from the cell's
configuration). ROADMAP A3's pool copies: the layer scan does not update the
pool in place.
"""
from benchmarks.harness import scopes

LAYER = "serving scheduler"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return scopes.step_ms(ctx, "serve", "parts", "pool_copy")
