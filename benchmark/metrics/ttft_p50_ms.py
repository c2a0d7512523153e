"""Median time to first token from the due instant, over the requests due in
the window, in milliseconds: the steadier statistic beside the tail."""
from harness import stats


def read(ctx):
    xs = ctx.get("detail", {}).get("ttft_s")
    return stats.percentile(xs, 50) * 1e3 if xs else None
