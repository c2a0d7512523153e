"""Share of the decode programs' device time spent in operations whose
innermost scope is ``mx.attn`` itself: the query/key/value and output
projections and the residual add (the kernel call and the page writes nested
in it read apart), in percent. Layer: serving programs. Source: device trace
joined with the decode program's scope table (``_decode_scopes``)."""
from _decode_scopes import reading


def read(ctx):
    return reading(ctx, "decode_attn_share")
