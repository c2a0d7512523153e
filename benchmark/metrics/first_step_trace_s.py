"""Seconds of the first ``TRAIN_STEP``'s span that JAX spent tracing Python
to jaxprs (union of the ``trace`` compile events that ended inside it).
Layer: compile."""
from _scoped import reading


def read(ctx):
    return reading(ctx, "first_step_trace_s")
