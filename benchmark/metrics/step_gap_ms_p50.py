"""Median time between the end of one run of the step program and the start
of the next on device 0, over the traced stretch, in milliseconds. Layer:
device."""
from _scoped import reading


def read(ctx):
    return reading(ctx, "step_gap_ms_p50")
