"""Device time of one decode step under the scope ``sparse_attn`` (inside
``attn`` of the block-sparse layers): the gather of the chosen blocks from
the paged pool by ``block_tables[choice]`` and the softmax over their rows
(``ops/sparse_attention.py:sparse_attend``).
"""
from benchmarks.harness import eva

LAYER = "serving scheduler"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return eva.scope_step_ms(ctx, "sparse_attn")
