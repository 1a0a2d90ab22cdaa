"""Token-expert pairs a held expert got in one train step, from the
``moe_pairs_held`` the program counted on the device (the loss's metric, in
the trainer's ``training_report`` spans), over the held experts of all
expert layers: 512 expected of 8192 tokens, 4 a token over 64, without the
exchange; the deployment's eight chips would bring 4096.
"""
from benchmarks.harness import moe_train

LAYER = "step program"
UNIT = "pairs/step"
SOURCE = "program_span"
MOVES = "train_tokens_per_s_per_chip"


def read(ctx):
    return moe_train.pairs_per_held_expert(ctx)
