"""Share of the decode programs' device time spent in operations whose
innermost scope is ``mx.embed``: the token and position lookup, and any
relayout of the table that the lookup asks for, in percent. Layer: serving
programs. Source: device trace joined with the decode program's scope table
(``_decode_scopes``)."""
from _decode_scopes import reading


def read(ctx):
    return reading(ctx, "decode_embed_share")
