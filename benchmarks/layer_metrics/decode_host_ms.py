"""Time per decode step in which the device ran nothing while the host was
inside an ``engine_iteration`` that holds a decode step: the host's share of
the step (preparing arrays, dispatch, read-back, committing tokens), on the
trace's own clock through the spans' ``TraceAnnotation``s. ROADMAP A4's
premise.
"""
from benchmarks.harness import scopes

LAYER = "serving scheduler"
UNIT = "ms/step"
SOURCE = "program_span"
MOVES = "serve_tokens_per_s"


def read(ctx):
    parsed = scopes.for_cell(ctx) if ctx["kind"] == "serve" else None
    idle = scopes.decode_host_idle(parsed) if parsed is not None else None
    if idle is None:
        return None
    return 1e3 * idle["idle_s"] / idle["steps"]
