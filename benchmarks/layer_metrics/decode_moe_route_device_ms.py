"""Device time of one decode step under the scope ``moe_route`` (inside
``mlp`` of the sparse layers): the router's product in float32, sigmoid,
the biased top-k, the gates, and the sort of the token-expert pairs by held
expert (``ops/moe.py:routed_experts``).
"""
from benchmarks.harness import eva

LAYER = "serving scheduler"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return eva.scope_step_ms(ctx, "moe_route")
