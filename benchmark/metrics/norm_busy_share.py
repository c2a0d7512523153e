"""Share of the summed device-operation time spent in instructions of scope
``mx.norm`` (the blocks' layer norms with, in a post-norm block, the
residual adds they close; forward and backward), in percent. Layer: model
blocks."""
from _scoped import reading


def read(ctx):
    return reading(ctx, "norm_busy_share")
