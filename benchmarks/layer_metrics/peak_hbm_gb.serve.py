"""Peak bytes in use on the fullest chip after the window, as the
allocator reports (``memory_stats()["peak_bytes_in_use"]``), before the
reference runs (serve cells)."""
LAYER = "device"
UNIT = "GB"
SOURCE = "program_counter"
MOVES = "serve_tokens_per_s"


def read(ctx):
    if ctx["kind"] != "serve" or not ctx["memory_peak_bytes"]:
        return None
    return ctx["memory_peak_bytes"] / 1e9
