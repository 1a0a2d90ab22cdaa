"""Share of the window's decode steps that were dispatched while the step
before them was still unread: the engine's ``serving_decode_step`` spans
carry ``overlapped`` (1: the host's turn ran beside the device's step; 0:
the first step, or the first after a drain: a prefill call, an idle engine).
The counter that says the one-step-ahead pipeline engages (PR 34); a program
whose spans carry no such arg, as the parent's do not, reads nothing.
"""
from benchmarks.harness import scopes

LAYER = "serving scheduler"
UNIT = "%"
SOURCE = "program_span"
MOVES = "serve_tokens_per_s"


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    steps = [a["overlapped"] for _, _, a
             in scopes.span_seconds(ctx, scopes.STEP_SPAN["serve"])
             if "overlapped" in a]
    if not steps:
        return None
    return 100.0 * sum(1 for o in steps if o) / len(steps)
