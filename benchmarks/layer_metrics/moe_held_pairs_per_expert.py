"""Token-expert pairs a held expert got in one decode step, from the
``expert_pairs`` the program counted on the device (``decode_commit``
spans), over the 128 held experts of the four expert layers: 1.0 expected of
32 rows without the exchange, which is the deployment's at 16 rows a chip.
(``moe_pairs_per_expert`` reads GLM's key names and finds nothing here.)
"""
from benchmarks.harness import kda

LAYER = "serving scheduler"
UNIT = "pairs/step"
SOURCE = "program_span"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return kda.pairs_per_held_expert(ctx)
