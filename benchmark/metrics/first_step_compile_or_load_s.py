"""Seconds of the first ``TRAIN_STEP``'s span that the backend spent compiling
or loading executables from the persistent cache. Layer: compile."""
from _scoped import reading


def read(ctx):
    return reading(ctx, "first_step_compile_or_load_s")
