"""Mean number of live sequences in a decode step of the window (the
engine's DECODE_STEP events): tokens decoded over ``decode_steps``."""
import _serve


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    live = [e["live"] for e in ctx["events"].get("DECODE_STEP", [])
            if _serve.in_window(ctx, e["ts"])]
    return sum(live) / len(live) if live else None
