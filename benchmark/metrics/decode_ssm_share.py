"""Share of the decode programs' device time spent in operations whose
innermost scope is ``mx.ssm`` itself: the Mamba-2 mixer's in and out
projections, convolution, gate and inner norm (the state update nested in it
reads apart), in percent. Layer: serving programs. Source: device trace
joined with the decode program's scope table (``_decode_scopes``)."""
from _decode_scopes import reading


def read(ctx):
    return reading(ctx, "decode_ssm_share")
