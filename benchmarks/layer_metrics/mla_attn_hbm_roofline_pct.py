"""The least time the chip's memory could take to read what a decode step
has to read of the cache, over the device time the step spends under the
scopes ``dsa_index`` and ``mla_attn``: bound by bytes, not by operations.

Bytes (``harness/glm.py:mla_step_bytes``): the indexer keys scored (one of
128 bfloat16 values a cached position, in both ``full`` layers) and the
latents attended (512 + 64 bfloat16 values a chosen position, in all five
layers), at the traced steps' real lengths (the program's span args). Only
bytes any correct step has to read: the rows' padding to 640 and the index
blocks past a row's length are not counted.
"""
from benchmarks.harness import glm

LAYER = "serving scheduler"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return glm.hbm_share(ctx, ("dsa_index", "mla_attn"), glm.mla_step_bytes)
