"""95th percentile, over the requests due in the window, of the wait from the
due instant to admission into a slot: the generator's lateness plus the
engine's own ``queue_delay_s`` of the ADMIT event. Milliseconds."""
from harness import stats


def read(ctx):
    if ctx["kind"] != "serve" or not ctx["events"].get("ADMIT"):
        return None
    delay = {}
    for e in ctx["events"]["ADMIT"]:
        if e.get("queue_delay_s") is not None:
            delay.setdefault(e["request_id"], e["queue_delay_s"])
    waits = []
    for r in ctx["run"]["window_reqs"]:
        q = r["req"]
        if q.request_id in delay and q.submit_time is not None:
            waits.append(q.submit_time - (ctx["run"]["t_s"] + r["due"])
                         + delay[q.request_id])
    return stats.percentile(waits, 95) * 1e3 if waits else None
