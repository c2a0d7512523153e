"""Share of the decode programs' device time spent in operations whose
innermost scope is ``mx.ssm_state``: the state update kernel with the
state's pack and unpack, in percent. Layer: serving programs. Source: device
trace joined with the decode program's scope table (``_decode_scopes``)."""
from _decode_scopes import reading


def read(ctx):
    return reading(ctx, "decode_ssm_state_share")
