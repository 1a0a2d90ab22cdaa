"""Of the positions cached (and scored by an indexer) for the sequences the
window's decode steps served, the share those steps' queries attended, from
the ``selected_rows`` / ``kv_rows`` args of the program's decode steps:
lower is sparser (2048 of 4-32 k cached positions).
"""
from benchmarks.harness import glm

LAYER = "serving scheduler"
UNIT = "%"
SOURCE = "program_span"
MOVES = "serve_tokens_per_s"


def read(ctx):
    steps = glm.window_steps(ctx)
    cached = sum(a["kv_rows"] for a in steps)
    if not cached:
        return None
    return 100.0 * sum(a["selected_rows"] for a in steps) / cached
