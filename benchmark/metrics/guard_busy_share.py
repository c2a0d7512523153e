"""Share of the summed device-operation time spent in instructions of scope
``mx.guard`` (the all-finite reduction over the gradients and the selects
that apply or skip the update), in percent. Layer: guard."""
from _scoped import reading


def read(ctx):
    return reading(ctx, "guard_busy_share")
