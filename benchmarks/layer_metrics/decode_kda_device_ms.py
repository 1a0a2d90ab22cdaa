"""Device time of one decode step under the scope ``kda`` (inside ``attn`` of
the KDA layers): every row's state ``[32, 128, 128]`` float32 read, decayed a
channel, corrected by the delta rule, written to and read out, and written
back (``ops/kda.py``, the one-token form).
"""
from benchmarks.harness import eva

LAYER = "serving scheduler"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return eva.scope_step_ms(ctx, "kda")
