"""Share of the summed device-operation time spent in instructions of scope
``mx.ffn`` (the two feed-forward matmuls and the activation between them;
forward and backward), in percent. Layer: model blocks."""
from _scoped import reading


def read(ctx):
    return reading(ctx, "ffn_busy_share")
