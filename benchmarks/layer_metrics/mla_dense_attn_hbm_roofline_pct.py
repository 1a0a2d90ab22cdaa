"""The least time the chip's memory could take to read the latents a decode
step attends, over the device time the step spends under the scope
``mla_attn``: bound by bytes.

Bytes (``harness/kda.py:latent_step_bytes``): ``kv_rows`` (every cached
position of every row, from the rows' lengths) x 576 useful bfloat16 values
of a 640-wide row, in the one MLA layer. A program that reads a row's blocks
to its length and no further cannot read over 100.
"""
from benchmarks.harness import kda

LAYER = "serving scheduler"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return kda.hbm_share(ctx, ("mla_attn",), kda.latent_step_bytes)
