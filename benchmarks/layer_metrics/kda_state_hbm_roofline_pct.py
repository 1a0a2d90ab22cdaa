"""The least time the chip's memory could take to read and write the states
and convolution tails a decode step updates, over the device time the step
spends under the scopes ``kda`` and ``kda_conv``: bound by bytes.

Bytes (``harness/kda.py:state_step_bytes``): every served row's state ``[32,
128, 128]`` float32 and tail ``[3, 12288]`` bfloat16, once in and once out,
in each of the four KDA layers (the program's ``state_slots`` span arg).
"""
from benchmarks.harness import kda

LAYER = "serving scheduler"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return kda.hbm_share(ctx, ("kda", "kda_conv"), kda.state_step_bytes)
