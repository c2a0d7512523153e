"""Share of the summed device-operation time spent in instructions that carry
no ``mx.`` scope or that the scope table does not hold (collectives the
partitioner put in, copies the compiler made), in percent. 100 where the
executable came from a cache that predates the scopes. Layer: device."""
from _scoped import reading


def read(ctx):
    return reading(ctx, "scope_unattributed_share")
