"""Share of the summed device-operation time of the traced stretch that went
to the state-space kernels, in percent: events whose name holds
``mxtpu_ssm_``. Nothing where the trace holds no such event."""
import _serve


def read(ctx, part="mxtpu_ssm_"):
    if ctx["kind"] != "serve" or _serve.traced_span(ctx) is None:
        return None
    ops = ctx["trace"].op_seconds()
    busy = sum(ops.values())
    mine = sum(v for name, v in ops.items() if part in name)
    if busy <= 0 or mine <= 0:
        return None
    return 100.0 * mine / busy
