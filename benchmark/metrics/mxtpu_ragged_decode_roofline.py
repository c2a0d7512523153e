"""Share of its roofline that the paged decode kernel reaches, in percent:
device time of ``mxtpu_ragged_decode`` events in the traced stretch against
the larger of operations/peak and bytes/peak of reading every live page of
the sequences decoded there once a layer (it is bound by bytes)."""
import _serve
from harness import peaks, roofline


def read(ctx):
    span = _serve.traced_span(ctx) if ctx["kind"] == "serve" else None
    if span is None:
        return None
    secs, calls = ctx["trace"].kernel_seconds("mxtpu_ragged_decode")
    n, ctx_sum = _serve.decode_work(ctx, span)
    if not calls or secs <= 0 or not n:
        return None
    shp = ctx["reference"].attention_shape(ctx["config"], {"seq_len": 0}, 1)
    layers = ctx["reference"].n_layers(ctx["config"])
    flops, nbytes = roofline.paged_decode(
        ctx_sum, n, shp["H"], shp["D"], ctx["traffic"]["engine"]["page_size"])
    least = layers * roofline.seconds(flops, nbytes,
                                      peaks.peak(ctx["device_kind"]))
    return 100.0 * least / secs
