"""Shared by the readers of what the program says of its own training step
(``harness/attribute.py`` over ``adapters/spans_mxtpu.py``): the attribution
is worked out once a run, kept in ``ctx``, logged, and written whole beside
the profiler's scratch. A program that says nothing gives every reader None.
"""
import os

from harness import attribute, manifest


def _build(ctx):
    if ctx.get("kind") != "train":
        return {}
    said = manifest.load_module(os.path.join(
        manifest.BENCH_DIR, "adapters", "spans_mxtpu.py")).collect()
    if said is None:
        return {}
    win = ctx["window"]
    reduced = ctx.get("trace")
    out, detail = attribute.build(
        said, win["steps"], win.get("traced", {}).get("steps", 0), reduced)
    attribute.report(out, detail)
    attribute.save_side_file(said, out, detail, reduced)
    return out


def reading(ctx, name):
    if "_attribution" not in ctx:
        ctx["_attribution"] = _build(ctx)
    return ctx["_attribution"].get(name)
