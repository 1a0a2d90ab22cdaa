"""Token-expert pairs a held expert got in one decode step, from the
``expert_pairs`` the program counted on the device (``decode_commit``
spans), over the 32 held experts of the four expert layers: 0.25 expected
of 16 rows without the exchange, where the stage's eight chips would bring
it 2.
"""
from benchmarks.harness import afmoe

LAYER = "serving scheduler"
UNIT = "pairs/step"
SOURCE = "program_span"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return afmoe.pairs_per_expert(ctx)
