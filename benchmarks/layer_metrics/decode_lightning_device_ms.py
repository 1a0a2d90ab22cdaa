"""Device time of one decode step under the scope ``lightning`` (inside
``attn`` of the lightning layers): reading every row's state, the decay,
the update by ``k^T v``, the read-out and the write back
(``ops/lightning_attention.py``, the one-token form).
"""
from benchmarks.harness import eva

LAYER = "serving scheduler"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return eva.scope_step_ms(ctx, "lightning")
