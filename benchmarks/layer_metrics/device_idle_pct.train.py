"""Share of the traced window in which no operation ran on the device: 1 -
the union of the device's operation intervals over the window, averaged
over the chips (train cells)."""
LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s_per_chip"


def read(ctx):
    tr = ctx["trace"]
    if ctx["kind"] != "train" or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
