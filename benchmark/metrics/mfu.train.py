"""The whole training step's share of the chips' peak: the reference's count
of forward and backward operations a step (recomputation not counted) times
steps over the window's time, over chips x peak bf16 rate. In percent."""
from harness import peaks


def read(ctx):
    if ctx["kind"] != "train":
        return None
    win = ctx["window"]
    flops = ctx["reference"].train_flops_per_step(
        ctx["config"], ctx["traffic"], ctx["rows"])
    peak = peaks.peak(ctx["device_kind"])["flops_bf16"]
    return 100.0 * flops * win["steps"] / win["elapsed_s"] \
        / (ctx["chips"] * peak)
