"""Backend compiles and persistent-cache misses that JAX reported between the
window's first and last ``TRAIN_STEP`` (``profiler.compile_events()``): any
compile in the process, not only a retrace of the step. 0 expected. Layer:
device."""
from _scoped import reading


def read(ctx):
    return reading(ctx, "backend_compiles_in_window.train")
