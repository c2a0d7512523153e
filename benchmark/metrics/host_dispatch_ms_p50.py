"""Median ``dispatch_s`` of the window's ``TRAIN_STEP`` events, in
milliseconds: the call of the compiled step until it returns (the device
runs on). Layer: trainer."""
from _scoped import reading


def read(ctx):
    return reading(ctx, "host_dispatch_ms_p50")
