"""The least time the chip's memory could take to read and write the
recurrent states a decode step updates, over the device time the step
spends under the scope ``lightning``: bound by bytes.

Bytes (``harness/sala.py:state_step_bytes``): every served row's state
``[32, 128, 128]`` float32 once in and once out, in each of the six
lightning layers (the program's ``state_slots`` span arg).
"""
from benchmarks.harness import sala

LAYER = "serving scheduler"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return sala.hbm_share(
        ctx, ("lightning",),
        lambda a, config: sala.state_step_bytes(a["state_slots"], config))
