"""Share of the decode programs' device time spent in operations whose
innermost scope is ``mx.sample``: everything after the logits, penalties,
the sampling branches, argmax or draw, acceptance and the non-finite guard,
in percent. Layer: serving programs. Source: device trace joined with the
decode program's scope table (``_decode_scopes``)."""
from _decode_scopes import reading


def read(ctx):
    return reading(ctx, "decode_sample_share")
