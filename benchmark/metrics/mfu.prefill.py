"""The chunk-prefill programs' share of the chip's peak, in percent: forward
operations of the prompt positions processed in the traced stretch (blocks,
causal attention against what is cached before them, head for each first
token) over the device time of the ``_chunk_prefill_fn`` programs there times
the peak bf16 rate."""
import _serve
from harness import peaks


def read(ctx):
    span = _serve.traced_span(ctx) if ctx["kind"] == "serve" else None
    if span is None:
        return None
    secs, runs = ctx["trace"].module_seconds("chunk_prefill_fn")
    chunks = _serve.prefill_work(ctx, span)
    if not runs or secs <= 0 or not chunks:
        return None
    n = sum(c for c, _ in chunks)
    pairs = sum(c * s + c * (c + 1) / 2.0 for c, s in chunks)
    flops = ctx["reference"].forward_flops(
        ctx["config"], n, pairs, _serve.first_tokens(ctx, span))
    return 100.0 * flops / (secs * peaks.peak(ctx["device_kind"])["flops_bf16"])
