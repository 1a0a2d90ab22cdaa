"""The least time the chip's memory could take to read the weights of the
held experts that got a pair in a decode step, over the device time the
step spends under the scope ``moe_experts``: bound by bytes.

Bytes (``harness/afmoe.py:experts_step_bytes``): ``expert_hits`` (counted on
the device, from the real routing; about 7 of 32 a layer at 16 rows) x 3
matrices of 3072 x 3072 bfloat16 values. An expert no pair fell to is not
counted. (``moe_experts_hbm_roofline_pct`` and
``moe_held_experts_hbm_roofline_pct`` read the other expert families' key
names and find nothing here.)
"""
from benchmarks.harness import afmoe

LAYER = "serving scheduler"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return afmoe.hbm_share(ctx, ("moe_experts",), afmoe.experts_step_bytes)
