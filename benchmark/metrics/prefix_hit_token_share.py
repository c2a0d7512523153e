"""Share of the prompt tokens of the requests due in the window that the
prefix cache served (``prefix_hit_tokens`` after the window less before), in
percent."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    m = ctx["run"]["marks"]
    hit = m["after"]["prefix_hit_tokens"] - m["before"]["prefix_hit_tokens"]
    prompt = sum(r["n_prompt"] for r in ctx["run"]["window_reqs"])
    return 100.0 * hit / prompt if prompt else None
