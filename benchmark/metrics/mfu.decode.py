"""The decode program's share of the chip's peak, in percent: forward
operations of the tokens decoded in the traced stretch (blocks, attention
over each token's live context, head) over the device time of the
``_decode_step_fn`` programs there times the peak bf16 rate."""
import _serve
from harness import peaks


def read(ctx):
    span = _serve.traced_span(ctx) if ctx["kind"] == "serve" else None
    if span is None:
        return None
    secs, runs = ctx["trace"].module_seconds("decode_step_fn")
    n, ctx_sum = _serve.decode_work(ctx, span)
    if not runs or secs <= 0 or not n:
        return None
    flops = ctx["reference"].forward_flops(ctx["config"], n, ctx_sum, n)
    return 100.0 * flops / (secs * peaks.peak(ctx["device_kind"])["flops_bf16"])
