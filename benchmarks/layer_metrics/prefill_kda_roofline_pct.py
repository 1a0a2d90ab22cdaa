"""The least time the chip could take for the chunked delta rule of one prefill
call, over the device time the call spends under the scope ``kda``: the
larger of the chunk form's operations over the bfloat16 peak and its bytes
over the memory's peak (``harness/kda.py:chunk_form_cost``; at the published
sizes the bytes bound it: 14 x 128 a token and head against 2 (5 x 64 + 3 x
128) x 128 multiply-adds), at the mean real tokens of the window's calls
(the ``tokens`` arg of ``serving_prefill`` spans).
"""
from benchmarks.harness import kda

LAYER = "serving scheduler"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return kda.prefill_roofline_share(ctx, ("kda",))
