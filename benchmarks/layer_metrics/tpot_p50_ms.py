"""Median, over the requests submitted in the window, of the mean gap
between a request's tokens ((completion seen by the client - first token) /
(output tokens - 1)): the steadier companion of ``tpot_p95_ms``, and the
only per-request latency a cell with a few tens of requests a window can
carry."""
import statistics

LAYER = "serving scheduler"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "serve_tokens_per_s"


def read(ctx):
    if not ctx.get("tpot_ms"):
        return None
    return statistics.median(ctx["tpot_ms"])
