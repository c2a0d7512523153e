"""Median ``prepare_s`` of the window's ``TRAIN_STEP`` events, in milliseconds:
batch to arrays, parameter and state gathering, flattening, key, scalars.
Layer: trainer."""
from _scoped import reading


def read(ctx):
    return reading(ctx, "host_prepare_ms_p50")
