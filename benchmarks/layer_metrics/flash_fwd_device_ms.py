"""Device time of one train step inside the Pallas kernel ``flash_fwd``
(``ops/flash_attention.py``): its events in the forward pass and in remat's
second forward. A part of ``train_attn_device_ms``; the rest of that bucket
is the projections and, since PR 30, the two backward kernels
(``flash_bwd_dkv``, ``flash_bwd_dq``), which have no metric of their own.
"""
from benchmarks.harness import scopes

LAYER = "kernels"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s_per_chip"


def read(ctx):
    return scopes.step_ms(ctx, "train", "parts", scopes.KERNEL)
