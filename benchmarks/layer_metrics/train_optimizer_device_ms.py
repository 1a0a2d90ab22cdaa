"""Device time of one train step under the scope ``optimizer``: the gradient
clip, the AdamW update and its application to the parameters. The outermost
scope name on an operation's path gives its bucket (``harness/scopes.py``),
so the ``train_*_device_ms`` buckets and ``embed`` add up to the step's busy
time.
"""
from benchmarks.harness import scopes

LAYER = "step program"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s_per_chip"


def read(ctx):
    return scopes.step_ms(ctx, "train", "buckets", "optimizer")
