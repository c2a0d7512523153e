"""Share of device 0's between-step gaps that falls inside the next step's
``dispatch`` phase on the aligned clock (the call until the device starts),
in percent. Layer: trainer."""
from _scoped import reading


def read(ctx):
    return reading(ctx, "gap_in_dispatch_share")
