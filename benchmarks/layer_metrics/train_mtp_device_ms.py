"""Device time of one train step under the scope ``mtp``: the whole
prediction module (its embedding norms and ``eh_proj``, its expert layer,
its head and loss), forward and backward. It cuts across the buckets: its
operations count in ``attn``, ``mlp``, ``embed`` and ``logits`` as well.
"""
from benchmarks.harness import moe_train

LAYER = "step program"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s_per_chip"


def read(ctx):
    return moe_train.scope_step_ms(ctx, "mtp")
