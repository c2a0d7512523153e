"""Longest ``trainer.step`` of the window, in milliseconds: one stalled step
shows here and not in the median. Layer: trainer."""


def read(ctx):
    steps = ctx.get("window", {}).get("step_s")
    return max(steps) * 1e3 if steps else None
