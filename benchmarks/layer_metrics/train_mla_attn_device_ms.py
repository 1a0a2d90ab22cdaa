"""Device time of one train step under the scope ``mla_attn``: latent
attention's projections, norms, rotary and the three flash kernels, forward,
remat's second forward and backward, in every layer and in the prediction
module. A part of ``train_attn_device_ms`` (the rest is the layer's first
norm and the residual add).
"""
from benchmarks.harness import moe_train

LAYER = "step program"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s_per_chip"


def read(ctx):
    return moe_train.scope_step_ms(ctx, "mla_attn")
