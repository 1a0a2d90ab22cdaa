"""Device time of one train step under the scope ``mlp``: the MLP half of every
block (layer norm, up-projection, GELU, down-projection, residual add),
forward, remat's second forward and backward. The outermost scope name on an
operation's path gives its bucket (``harness/scopes.py``), so the
``train_*_device_ms`` buckets and ``embed`` add up to the step's busy time.
"""
from benchmarks.harness import scopes

LAYER = "step program"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s_per_chip"


def read(ctx):
    return scopes.step_ms(ctx, "train", "buckets", "mlp")
