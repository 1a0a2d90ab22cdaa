"""Mean of the engine's ``serving_queue_wait_seconds`` (submit to admitted
into the batch) over the window."""
LAYER = "serving scheduler"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "ttft_p95_ms"


def read(ctx):
    reg = ctx.get("registry") or {}
    n = reg.get("serving_queue_wait_seconds.count")
    if not n:
        return None
    return 1e3 * reg["serving_queue_wait_seconds.sum"] / n
