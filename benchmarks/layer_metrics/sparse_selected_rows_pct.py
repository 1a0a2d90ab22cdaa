"""Of the rows cached in a block-sparse layer for the sequences the
window's decode steps served, the share that lay in the blocks those steps'
queries attended, from the ``selected_rows`` / ``kv_rows`` args of the
program's ``serving_decode_step`` spans: lower is sparser (64 blocks of 64
over 9-33 k cached positions).
"""
from benchmarks.harness import sala

LAYER = "serving scheduler"
UNIT = "%"
SOURCE = "program_span"
MOVES = "serve_tokens_per_s"


def read(ctx):
    steps = sala.window_steps(ctx) if ctx["kind"] == "serve" else []
    cached = sum(a["kv_rows"] for a in steps)
    if not cached:
        return None
    return 100.0 * sum(a["selected_rows"] for a in steps) / cached
