"""Median ``flag_wait_s`` of the window's ``TRAIN_STEP`` events, in
milliseconds: the read of the guard's flag, which waits for the device to
finish the step. Layer: trainer."""
from _scoped import reading


def read(ctx):
    return reading(ctx, "flag_wait_ms_p50")
