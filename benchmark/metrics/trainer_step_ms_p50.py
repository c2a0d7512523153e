"""Median ``dur_s`` of the window's ``TRAIN_STEP`` events, in milliseconds:
``SPMDTrainer.step`` timed from inside, first stamp to the end of the flag
read. The inside twin of ``step_ms_p50``. Layer: trainer."""
from _scoped import reading


def read(ctx):
    return reading(ctx, "trainer_step_ms_p50")
