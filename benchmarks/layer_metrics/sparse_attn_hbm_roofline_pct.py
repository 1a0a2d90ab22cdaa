"""The least time the chip's memory could take to read what a decode step
has to read in the block-sparse layers, over the device time the step
spends under the scopes ``sparse_select`` and ``sparse_attn``: bound by
bytes, not by operations.

Bytes (``harness/sala.py:sparse_step_bytes``): the compressed keys scored
(one a ``kernel_stride`` cached positions) and the K and V rows of the
selected positions, 2 key/value heads of 128 bfloat16 values each, in both
sparse layers, at the traced steps' real lengths (the program's span args).
The program gathers each group's whole rows (both groups' columns) and 64
whole blocks, so this reads under 50 until the gather is by group.
"""
from benchmarks.harness import sala

LAYER = "serving scheduler"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return sala.hbm_share(
        ctx, ("sparse_select", "sparse_attn"),
        lambda a, config: sala.sparse_step_bytes(
            a["kv_rows"], a["selected_rows"], config))
