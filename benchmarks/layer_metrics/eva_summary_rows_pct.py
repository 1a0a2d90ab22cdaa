"""Of the cache rows the window's decode steps attended, the share that
were chunk summaries (the rest: exact rows of the current window), from the
``window_rows`` / ``summary_rows`` args of the program's
``serving_decode_step`` spans: how much of a step's context EVA's second
kind of state is, at the sequences' real lengths.
"""
from benchmarks.harness import eva

LAYER = "serving scheduler"
UNIT = "%"
SOURCE = "program_span"
MOVES = "serve_tokens_per_s"


def read(ctx):
    steps = eva.window_steps(ctx) if ctx["kind"] == "serve" else []
    window = sum(a["window_rows"] for a in steps)
    summary = sum(a["summary_rows"] for a in steps)
    if not window + summary:
        return None
    return 100.0 * summary / (window + summary)
