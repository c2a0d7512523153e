"""Seconds of the first ``TRAIN_STEP``'s span that JAX spent lowering jaxprs
to MLIR modules. Layer: compile."""
from _scoped import reading


def read(ctx):
    return reading(ctx, "first_step_lower_s")
