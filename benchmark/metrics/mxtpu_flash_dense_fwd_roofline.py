"""Share of its roofline that the dense flash forward kernel reaches, in
percent: device time of ``mxtpu_flash_dense_fwd`` events in the trace against
the larger of operations/peak and bytes/peak of as many calls."""
import _flash
from harness import roofline


def read(ctx):
    return _flash.share(ctx, "mxtpu_flash_dense_fwd", roofline.attention_fwd)
