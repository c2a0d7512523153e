"""Shared by the flash kernels' roofline readers: kernel seconds from the
trace against the roofline time of as many calls, each on one chip's rows."""
from harness import peaks, roofline


def share(ctx, kernel, cost):
    tr = ctx.get("trace")
    if tr is None or ctx["kind"] != "train":
        return None
    secs, calls = tr.kernel_seconds(kernel)
    if not calls or secs <= 0:
        return None
    shp = ctx["reference"].attention_shape(
        ctx["config"], ctx["traffic"], ctx["rows"] // ctx["chips"])
    flops, nbytes = cost(shp["B"], shp["H"], shp["T"], shp["D"],
                         shp["causal"])
    least = roofline.seconds(flops, nbytes, peaks.peak(ctx["device_kind"]))
    return 100.0 * least * calls / secs
