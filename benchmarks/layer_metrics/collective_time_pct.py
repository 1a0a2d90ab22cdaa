"""Share of the device's busy time spent in collectives (all-gather, all-
reduce, reduce-scatter, all-to-all, collective-permute): the union of their
intervals over the union of all operations', as ``trace.reduce_trace`` sums
both, averaged over the chips. Time a collective overlaps with compute
counts as collective time here (exposed time is not separated yet).
"""
LAYER = "step program"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s_per_chip"


def read(ctx):
    tr = ctx["trace"]
    if ctx["kind"] != "train" or not tr.get("busy_s") \
            or "collective_s" not in tr:
        return None
    return 100.0 * tr["collective_s"] / tr["busy_s"]
