"""The least time the chip's memory could take to read the cache rows a
decode step attends, over the device time the step spends under the scope
``paged_attn``: bound by bytes, not by operations.

Bytes (``harness/paged.py:attended_bytes``): the rows the traced steps
attended at the sequences' real lengths (the program's span arg
``kv_rows``, not rounded up to blocks), a K and a V row of ``n_embd``
bfloat16 values (not the pool row's padded width) in each of ``n_layer``
layers. The kernel copies whole blocks of whole pool rows, each once, so
this cannot pass 100.
"""
from benchmarks.harness import paged

LAYER = "serving scheduler"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return paged.hbm_share(ctx)
