"""The least time the chip's matrix unit could take for what the three flash
kernels execute in a train step, over their device time (events named
``flash_fwd``, ``flash_bwd_dkv``, ``flash_bwd_dq``): bound by operations.

Operations (``harness/moe_train.py:flash_kernels_flops``, the formula of
``ops/flash_attention.py:flash_cost``): 2 x products x causal pairs x head
size a head and layer, 2 products in the forward kernel (which runs twice
under remat), 4 in dK / dV, 3 in dQ.
"""
from benchmarks.harness import moe_train

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s_per_chip"


def read(ctx):
    return moe_train.flash_roofline_pct(ctx)
