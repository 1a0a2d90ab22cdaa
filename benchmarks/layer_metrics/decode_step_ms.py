"""Mean of the engine's ``serving_decode_step_seconds`` (host clock round
one bucketed decode step and its blocking read-back) over the window."""
LAYER = "serving scheduler"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "serve_tokens_per_s"


def read(ctx):
    reg = ctx.get("registry") or {}
    n = reg.get("serving_decode_step_seconds.count")
    if not n:
        return None
    return 1e3 * reg["serving_decode_step_seconds.sum"] / n
