"""Share of the traced window in which no operation ran on the device: 1 -
the union of the device's operation intervals over the window, averaged
over the chips (serve cells)."""
LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(ctx):
    tr = ctx["trace"]
    if ctx["kind"] != "serve" or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
