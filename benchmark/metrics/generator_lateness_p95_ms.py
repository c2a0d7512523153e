"""95th percentile of how late the load generator submitted the window's
requests after they were due (it submits between engine steps), in
milliseconds: a starved generator is not to be read as a fast server."""
from harness import stats


def read(ctx):
    late = ctx.get("detail", {}).get("late_s")
    return stats.percentile(late, 95) * 1e3 if late else None
