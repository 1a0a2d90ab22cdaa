"""The least time the chip's matrix unit could take for the held experts'
products of a train step, over the device time the step spends under the
scope ``moe_experts``: bound by operations.

Operations (``harness/moe_train.py:expert_products_flops``): 3 passes x 3
products x 2 x 2048 x 1536 x ``moe_pairs_held`` (counted on the device).
Remat's second forward and the two up-products the backward makes again are
inside the time and not among the operations.
"""
from benchmarks.harness import moe_train

LAYER = "step program"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s_per_chip"


def read(ctx):
    return moe_train.experts_roofline_pct(ctx)
