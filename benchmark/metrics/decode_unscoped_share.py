"""Share of the decode programs' device time spent in operations that carry
no ``mx.`` scope or that the decode program's scope table does not hold, in
percent. Nothing (not 100) where the table holds no ``mx.`` name at all: an
executable cached from before the scopes. Layer: serving programs. Source:
device trace joined with the decode program's scope table
(``_decode_scopes``)."""
from _decode_scopes import reading


def read(ctx):
    return reading(ctx, "decode_unscoped_share")
