"""Device time of one decode step under the scope ``dsa_index`` (inside
``attn`` of the layers whose indexer is ``full``): the indexer's key and its
write, the indexer's queries and weights, their scores against every cached
key a chunk of blocks at a time, and the exact top-k
(``ops/dsa_index.py``).
"""
from benchmarks.harness import eva

LAYER = "serving scheduler"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return eva.scope_step_ms(ctx, "dsa_index")
