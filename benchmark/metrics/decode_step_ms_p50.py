"""Median of the benchmark's own span around ``engine.step()`` over the
window's steps in which a sequence decoded, in milliseconds."""
import statistics


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    run = ctx["run"]
    spans = [e - s for s, e, live in run["steps"]
             if live and run["w0"] <= s < run["w1"]]
    return statistics.median(spans) * 1e3 if spans else None
