"""Share of the summed device-operation time spent in instructions of scope
``mx.optimizer`` (``apply_updates`` inside the compiled step), in percent.
Layer: optimizer."""
from _scoped import reading


def read(ctx):
    return reading(ctx, "optimizer_busy_share")
