"""Model FLOP/s utilisation of the traced run: the benchmark's own
operations per token (``harness/flops.py``; recomputation not counted)
times the tokens per second of the window, over chips times the peak of the
benchmark's own table (``harness/device.py``)."""
LAYER = "step program"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "train_tokens_per_s_per_chip"


def read(ctx):
    if ctx["kind"] != "train" or not ctx["tokens_per_s"] > 0:
        return None
    peak = ctx["cell"].chips * ctx["peak_flops_per_s"]
    return 100.0 * ctx["flops_per_token"] * ctx["tokens_per_s"] / peak
