"""Device time of one train step under the scope ``moe_experts``: the held
experts' tile loops (``ops/moe.py:routed_experts_trained``), forward, remat's
second forward and the hand-written backward, with the casts of the expert
stacks. A part of ``train_mlp_device_ms``.
"""
from benchmarks.harness import moe_train

LAYER = "step program"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s_per_chip"


def read(ctx):
    return moe_train.scope_step_ms(ctx, "moe_experts")
