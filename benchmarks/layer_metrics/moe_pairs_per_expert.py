"""Token-expert pairs a held expert got in one decode step, from the
``expert_pairs`` the program counted on the device (``decode_commit``
spans), over the held experts of all sparse layers: how near the step's
experts are to their deployment load (8 a step where 16 chips exchange
their rows; 0.5 expected of 16 rows without the exchange).
"""
from benchmarks.harness import glm

LAYER = "serving scheduler"
UNIT = "pairs/step"
SOURCE = "program_span"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return glm.pairs_per_expert(ctx)
