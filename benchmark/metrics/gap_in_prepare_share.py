"""Share of device 0's between-step gaps that falls inside the next step's
``prepare`` phase on the aligned clock, in percent. Layer: trainer."""
from _scoped import reading


def read(ctx):
    return reading(ctx, "gap_in_prepare_share")
