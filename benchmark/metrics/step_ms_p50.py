"""Median host time of ``trainer.step`` in the window (ends in its applied-flag
read), in milliseconds. Layer: trainer. Source: the benchmark's span."""
import statistics


def read(ctx):
    steps = ctx.get("window", {}).get("step_s")
    return statistics.median(steps) * 1e3 if steps else None
