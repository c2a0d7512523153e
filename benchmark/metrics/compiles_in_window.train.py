"""Traces of the training step inside the window (``step_trace_count`` after
less before): nothing may compile there, so 0 is expected."""


def read(ctx):
    if ctx["kind"] != "train":
        return None
    win = ctx["window"]
    return win["after"]["step_trace_count"] - win["before"]["step_trace_count"]
