"""Share of the summed device-operation time spent in instructions of scope
``mx.attn`` (projections, the flash kernels, output projection; forward and
backward), in percent. Layer: model blocks. Source: device trace joined with
the compiled step's scope table."""
from _scoped import reading


def read(ctx):
    return reading(ctx, "attn_busy_share")
