"""p99 over the tokens generated in the window of the time between consecutive
``decode_commit`` ends: the gap a client would see between two tokens if the
engine streamed them. Each step's gap is weighted by its ``rows`` (one token
a row); a prefill between two decode steps lengthens the gap it falls in.
"""
from benchmarks.harness import scopes

LAYER = "serving scheduler"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "tpot_p95_ms"


def read(ctx):
    commits = scopes.span_seconds(ctx, "decode_commit")
    gaps = sorted((b[0] - a[0], int(b[2].get("rows", 1)))
                  for a, b in zip(commits, commits[1:]))
    if ctx["kind"] != "serve" or not gaps:
        return None
    target = 0.99 * sum(rows for _, rows in gaps)
    seen = 0
    for gap, rows in gaps:
        seen += rows
        if seen >= target:
            return 1e3 * gap
    return 1e3 * gaps[-1][0]
