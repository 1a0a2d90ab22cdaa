"""Device time of one train step under the scope ``moe_route``: the router's
float32 product, the sigmoid, the biased top-k, the gates, the loads and
the sort of the token-expert pairs by held expert, in every expert layer,
and the gates' gradient into the router. A part of ``train_mlp_device_ms``.
"""
from benchmarks.harness import moe_train

LAYER = "step program"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s_per_chip"


def read(ctx):
    return moe_train.scope_step_ms(ctx, "moe_route")
