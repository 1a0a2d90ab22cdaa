"""The least time the chip's memory could take to read the weights of the
held experts that got a pair in a decode step, over the device time the
step spends under the scope ``moe_experts``: bound by bytes.

Bytes (``harness/glm.py:experts_step_bytes``): ``expert_hits`` (counted on
the device, from the real routing) x 3 matrices of 6144 x 2048 bfloat16
values. An expert no pair fell to is not counted, so a program that reads
only the experts it needs cannot read over 100.
"""
from benchmarks.harness import glm

LAYER = "serving scheduler"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return glm.hbm_share(ctx, ("moe_experts",), glm.experts_step_bytes)
