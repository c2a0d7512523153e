"""Share of their weight-byte floor that the decode programs reach, in
percent: the weights a decode step streams (the blocks' matrices and biases
and the tied table through the head, in the configuration's types), times
the decode programs the trace holds, over the chip's bandwidth, against
those programs' device time. Layer: serving programs. Source: device trace
(``_decode_scopes``, which says why the products' own time cannot be the
denominator)."""
from _decode_scopes import reading


def read(ctx):
    return reading(ctx, "decode_weight_roofline")
