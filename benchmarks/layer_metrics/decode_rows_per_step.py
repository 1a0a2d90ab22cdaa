"""Tokens the engine generated in the window (``serving_tokens_generated_
total``, counted as requests retire) over its decode steps: how full the
running batch was."""
LAYER = "serving scheduler"
UNIT = "rows"
SOURCE = "program_counter"
MOVES = "serve_tokens_per_s"


def read(ctx):
    reg = ctx.get("registry") or {}
    steps = reg.get("serving_decode_step_seconds.count")
    if not steps:
        return None
    return reg["serving_tokens_generated_total"] / steps
