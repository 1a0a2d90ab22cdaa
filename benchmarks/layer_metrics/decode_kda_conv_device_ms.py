"""Device time of one decode step under the scope ``kda_conv`` (inside ``attn``
of the KDA layers): the tail of three rows read, the four-tap causal
convolution over it and the new row, SiLU, and the tail written back
(``ops/kda.py:short_conv``).
"""
from benchmarks.harness import eva

LAYER = "serving scheduler"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return eva.scope_step_ms(ctx, "kda_conv")
