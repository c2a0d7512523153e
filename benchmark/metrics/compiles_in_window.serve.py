"""Traces of the engine's programs inside the window (decode, prefill and
page-copy trace counts after less before): nothing may compile there."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    m = ctx["run"]["marks"]
    keys = ("decode_trace_count", "prefill_trace_count", "copy_trace_count")
    return sum(m["after"][k] - m["before"][k] for k in keys)
