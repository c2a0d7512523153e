"""What the program says of its own training step, fetched from the live
trainer: the ``TRAIN_STEP`` spans of its flight recorder (host phases of
``SPMDTrainer.step``), the scope table of the compiled step (which model
part, optimizer or guard each HLO instruction belongs to) and the process's
compile events. ``ctx`` holds no handle on the trainer, so it is reached
through ``parallel.live_trainers()``. A program that has none of this (the
commit before these existed) gives ``None``, and every reader built on it
leaves its metric out."""

import sys


def _span(event):
    data = event.data
    return {"ts": float(event.ts), "step": int(data["step"]),
            **{k: float(data[k]) for k in
               ("dur_s", "prepare_s", "dispatch_s", "bind_s", "flag_wait_s")}}


def collect():
    """``{"steps": [span, ...], "scope_table": {instruction: [scope,
    direction]} or None, "compile_events": [...]}`` of the trainer that
    stepped last, or None."""
    try:
        from incubator_mxnet_tpu import parallel, profiler
        from incubator_mxnet_tpu.events import EventType
    except ImportError:
        return None
    live = getattr(parallel, "live_trainers", None)
    compile_events = getattr(profiler, "compile_events", None)
    if live is None or compile_events is None:
        return None
    stepped = []
    for trainer in live():
        steps = [_span(e) for e in trainer.flight.events(
            "trainer", EventType.TRAIN_STEP) if "dur_s" in e.data]
        if steps:
            stepped.append((steps[-1]["ts"], id(trainer), trainer, steps))
    if not stepped:
        return None
    _, _, best, best_steps = max(stepped)
    try:
        table = {k: list(v) for k, v in best.scope_table().items()}
    except Exception as e:      # a reader returns nothing; it does not raise
        print(f"spans_mxtpu: no scope table: {e!r}", file=sys.stderr)
        table = None
    return {"steps": best_steps, "scope_table": table,
            "compile_events": compile_events()}
