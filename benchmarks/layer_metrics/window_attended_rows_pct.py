"""How much of a uniform cache's read the traffic lets the window skip: 100 x
(4 ``window_rows`` + ``kv_rows``) / (5 ``kv_rows``) over the window's decode
steps (``harness/afmoe.py:attended_rows_pct``): 100 while every row lies
inside one window of 4096, 20 + 80 x 4096 / length a row past it. Lower is
less to read; the number is the traffic's and the layout's, not the
kernels'.
"""
from benchmarks.harness import afmoe

LAYER = "serving scheduler"
UNIT = "%"
SOURCE = "program_span"
MOVES = "serve_tokens_per_s"


def read(ctx):
    return afmoe.attended_rows_pct(ctx)
