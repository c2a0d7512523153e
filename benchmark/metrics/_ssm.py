"""Shared by the state-space readers: the operations and bytes that updating
recurrent state needs, from its shapes (the same work whatever implements it),
and what the traced stretch decoded, from the engine's own step events.

One position of one sequence in one layer reads the sequence's state of
``heads x head width x state size`` elements once and writes it once; each
element is decayed, gets one term of an outer product added and is read for
the output: 6 operations. The vectors beside it (x, B, C, dt) are a thousandth
of the bytes and are not counted."""


def state_update(slot_layers, heads, head_dim, state_dim, itemsize):
    """(operations, bytes) of ``slot_layers`` one-position updates."""
    elems = float(slot_layers) * heads * head_dim * state_dim
    return 6.0 * elems, 2.0 * elems * itemsize


def decoded(ctx, span):
    """(decode steps, state rows updated) in the traced stretch: a row is one
    live sequence in one state layer of one step. None where the engine's
    step events say nothing of state rows (a program that keeps none)."""
    t0, t1 = span
    evs = [e for e in ctx["events"].get("DECODE_STEP", [])
           if t0 <= e["ts"] < t1]
    if not evs or any("state_rows" not in e for e in evs):
        return None
    return len(evs), sum(int(e["state_rows"]) for e in evs)


def state_layers(config):
    return sum(1 for t in config.get("layer_types", ()) if t == "mamba")
