"""Shared by the serving readers: what the traced stretch processed, rebuilt
from the requests' own token stamps and the engine's chunk events.

A token other than a request's first comes from a decode step, and its context
is the prompt and the tokens before it. A first token comes from the chunk
that ended its prompt."""


def traced_span(ctx):
    tr, run = ctx.get("trace"), ctx.get("run")
    if tr is None or not run or not run.get("traced") or \
            run["traced"][1] is None:
        return None
    return run["traced"]


def decode_work(ctx, span):
    """(tokens decoded, sum of their context lengths, sequences-steps)."""
    t0, t1 = span
    n = ctx_sum = 0
    for r in ctx["run"]["reqs"]:
        stamps = r["req"].token_stamps
        for j in range(1, len(stamps)):
            if t0 <= stamps[j] < t1:
                n += 1
                ctx_sum += r["n_prompt"] + j
    return n, ctx_sum


def prefill_work(ctx, span):
    """[(new positions, cached positions before them)] of the chunks that
    ran in the span."""
    t0, t1 = span
    return [(int(e["n"]), int(e["start"]))
            for e in ctx["events"].get("PREFILL_CHUNK", [])
            if t0 <= e["ts"] < t1]


def first_tokens(ctx, span):
    t0, t1 = span
    return sum(1 for r in ctx["run"]["reqs"]
               if r["req"].token_stamps
               and t0 <= r["req"].token_stamps[0] < t1)


def in_window(ctx, ts):
    return ctx["run"]["w0"] <= ts < ctx["run"]["w1"]
