"""Model FLOP/s utilisation of the traced run of a trained expert model: the
operations one step needs (``harness/moe_train.py:step_flops``, a function
of the configuration: 31.7 TFLOP for ``glm-4.7-flash`` at 8192 positions;
recomputation not counted) times the steps a second of the traced run, over
chips times the peak of the benchmark's own table. ``train_mfu_pct`` is the
same share by GPT's count.
"""
from benchmarks.harness import moe_train

LAYER = "step program"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "train_tokens_per_s_per_chip"


def read(ctx):
    return moe_train.mfu_pct(ctx)
