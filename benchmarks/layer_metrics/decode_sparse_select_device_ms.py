"""Device time of one decode step under the scope ``sparse_select`` (inside
``attn`` of the block-sparse layers): completing and writing a compressed
key, gathering the sequence's compressed keys, their scores, the group sums,
the block maxima and the top-k choice
(``ops/sparse_attention.py:sparse_select``).
"""
from benchmarks.harness import eva

LAYER = "serving scheduler"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return eva.scope_step_ms(ctx, "sparse_select")
