"""Device time of one decode step under the scope ``paged_attn`` (inside
``attn``): the kernel that attends every row of the step through its block
table at the row's real length (``ops/paged_attention.py``), the copies of
the rows' blocks from the pool included. A program without the scope (one
that gathers whole tables under ``kv_cache``) reads nothing.
"""
from benchmarks.harness import eva, paged

LAYER = "serving scheduler"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return eva.scope_step_ms(ctx, paged.SCOPE)
