"""Share of device 0's between-step gaps that falls inside the finished
step's ``flag_wait`` phase on the aligned clock (the flag's way back to the
host), in percent. Layer: trainer."""
from _scoped import reading


def read(ctx):
    return reading(ctx, "gap_in_flag_wait_share")
