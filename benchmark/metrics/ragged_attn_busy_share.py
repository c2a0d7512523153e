"""Share of the summed device-operation time of the traced stretch that went
to the paged attention kernels, in percent: events whose name holds
``mxtpu_ragged_`` (decode, chunk prefill, verify). In a hybrid model only the
attention layers run them. Nothing where the trace holds no such event."""
import ssm_busy_share


def read(ctx):
    return ssm_busy_share.read(ctx, part="mxtpu_ragged_")
