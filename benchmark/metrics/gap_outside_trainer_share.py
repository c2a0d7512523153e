"""Share of device 0's between-step gaps that falls between the end of one
step's ``flag_wait`` and the next step's start: the outcome record, the
return, and the harness's own feed, in percent. Layer: trainer."""
from _scoped import reading


def read(ctx):
    return reading(ctx, "gap_outside_trainer_share")
