"""Device time of one decode step under the scope ``moe_experts`` (inside
``mlp`` of the sparse layers): the held experts' SwiGLU a tile of pairs at
a time, over as many tiles as the step's pairs fill, and the weighted sum
back into the tokens' rows (``ops/moe.py:routed_experts``). The shared
expert is ``mlp``'s own.
"""
from benchmarks.harness import eva

LAYER = "serving scheduler"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return eva.scope_step_ms(ctx, "moe_experts")
