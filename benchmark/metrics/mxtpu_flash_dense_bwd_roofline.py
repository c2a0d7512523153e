"""Share of its roofline that the dense flash backward kernel reaches, in
percent: device time of ``mxtpu_flash_dense_bwd`` events in the trace against
the larger of operations/peak and bytes/peak of as many calls."""
import _flash
from harness import roofline


def read(ctx):
    return _flash.share(ctx, "mxtpu_flash_dense_bwd", roofline.attention_bwd)
