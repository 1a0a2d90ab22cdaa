"""The least time the chip's memory could take to read the weights of the
held experts that got a pair in a decode step, over the device time the
step spends under the scope ``moe_experts``: bound by bytes.

Bytes (``harness/glm.py:experts_step_bytes``, which ``harness/kda.py`` takes
as it is): ``expert_hits`` (counted on the device, from the real routing;
about 80 of 128 a layer at 32 rows) x 3 matrices of 2304 x 1024 bfloat16
values. An expert no pair fell to is not counted.
"""
from benchmarks.harness import kda

LAYER = "serving scheduler"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return kda.hbm_share(ctx, ("moe_experts",), kda.experts_step_bytes)
