"""Mean duration of the train step program on the device's ``XLA Modules``
line, over the executions that ran inside a ``train_dispatch`` span of the
traced window, averaged over the chips: the device's own clock, where
``train_dispatch_ms`` is the host's.
"""
from benchmarks.harness import scopes

LAYER = "step program"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s_per_chip"


def read(ctx):
    return scopes.step_ms(ctx, "train", "step_program_s")
