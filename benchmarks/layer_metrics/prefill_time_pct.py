"""Share of the scheduler's ``engine_iteration`` time spent inside
``serving_prefill`` spans over the window: what stands between
``decode_step_ms`` and ``tpot_p50_ms`` (a decode step waits for the prefill
of the same iteration).
"""
from benchmarks.harness import scopes

LAYER = "serving scheduler"
UNIT = "%"
SOURCE = "program_span"
MOVES = "serve_tokens_per_s"


def read(ctx):
    total = sum(d for _, d, _ in scopes.span_seconds(ctx, "engine_iteration"))
    if ctx["kind"] != "serve" or not total:
        return None
    prefill = sum(d for _, d, _ in scopes.span_seconds(ctx, "serving_prefill"))
    return 100.0 * prefill / total
