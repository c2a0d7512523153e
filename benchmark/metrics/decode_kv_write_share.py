"""Share of the decode programs' device time spent in operations whose
innermost scope is ``mx.kv_write``: the page writes of the step's keys and
values, in percent. Layer: serving programs. Source: device trace joined
with the decode program's scope table (``_decode_scopes``)."""
from _decode_scopes import reading


def read(ctx):
    return reading(ctx, "decode_kv_write_share")
