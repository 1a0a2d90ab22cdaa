"""Median of the trainer's ``train_dispatch`` spans. In a traced run the
trainer blocks on every dispatch, so this is the step's time on the device
plus its dispatch."""
import statistics

LAYER = "training loop"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "train_tokens_per_s_per_chip"


def read(ctx):
    spans = [d for name, _, d, _ in ctx.get("spans", ())
             if name == "train_dispatch"]
    if ctx["kind"] != "train" or not spans:
        return None
    return 1e3 * statistics.median(spans)
