"""Share of the decode programs' device time spent in operations whose
innermost scope is ``mx.ffn``: both feed-forward products, the activation
and the residual add, in percent. Layer: serving programs. Source: device
trace joined with the decode program's scope table (``_decode_scopes``)."""
from _decode_scopes import reading


def read(ctx):
    return reading(ctx, "decode_ffn_share")
