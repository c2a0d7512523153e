"""Share of its roofline that the paged chunk-prefill kernel reaches, in
percent: device time of ``mxtpu_ragged_prefill`` events in the traced stretch
against the larger of operations/peak and bytes/peak of the chunks that ran
there, each attending to what is cached before it, once a layer."""
import _serve
from harness import peaks, roofline


def read(ctx):
    span = _serve.traced_span(ctx) if ctx["kind"] == "serve" else None
    if span is None:
        return None
    secs, calls = ctx["trace"].kernel_seconds("mxtpu_ragged_prefill")
    chunks = _serve.prefill_work(ctx, span)
    if not calls or secs <= 0 or not chunks:
        return None
    shp = ctx["reference"].attention_shape(ctx["config"], {"seq_len": 0}, 1)
    peak = peaks.peak(ctx["device_kind"])
    least = sum(roofline.seconds(
        *roofline.paged_prefill(n, start, shp["H"], shp["D"]), peak)
        for n, start in chunks)
    return 100.0 * ctx["reference"].n_layers(ctx["config"]) * least / secs
