"""Share of the decode programs' device time spent in operations whose
innermost scope is ``mx.attend``: the ragged attention kernel's call with
its query pad and output slice, in percent. Layer: serving programs. Source:
device trace joined with the decode program's scope table
(``_decode_scopes``)."""
from _decode_scopes import reading


def read(ctx):
    return reading(ctx, "decode_attend_share")
