"""Share of the window the training loop spent waiting for its next batch:
the trainer's ``dataload_wait`` spans over the window's length."""
LAYER = "training loop"
UNIT = "%"
SOURCE = "program_span"
MOVES = "train_tokens_per_s_per_chip"


def read(ctx):
    if ctx["kind"] != "train" or not ctx["window_s"] > 0:
        return None
    waited = sum(d for name, _, d, _ in ctx["spans"]
                 if name == "dataload_wait")
    return 100.0 * waited / ctx["window_s"]
