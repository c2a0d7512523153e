"""Operations and bytes that a kernel's call needs, from its shapes: the same
work whatever implements it. The roofline time is the larger of operations
over the peak rate and bytes over the peak bandwidth.

Attention forward: two products, 4*B*H*Tq*Tk*D operations (half when causal
and square); reads q, k, v and writes o. Attention backward: four products
(dV, dP, dQ, dK; recomputing the scores is the implementation's choice and is
not counted), 8*B*H*Tq*Tk*D; reads q, k, v, o, do and writes dq, dk, dv.
Paged decode: one query a sequence against its live context, 4*ctx*H*D
operations; reads every live page of keys and values once.
"""


def seconds(flops, nbytes, peak):
    return max(flops / peak["flops_bf16"], nbytes / peak["hbm_bytes_per_s"])


def attention_fwd(B, H, T, D, causal, itemsize=2):
    flops = 4.0 * B * H * T * T * D * (0.5 if causal else 1.0)
    return flops, 4.0 * B * H * T * D * itemsize


def attention_bwd(B, H, T, D, causal, itemsize=2):
    flops = 8.0 * B * H * T * T * D * (0.5 if causal else 1.0)
    return flops, 8.0 * B * H * T * D * itemsize


def paged_decode(context_tokens, n_seqs, H, D, page, itemsize=2):
    """One layer's call: ``context_tokens`` live positions over ``n_seqs``
    sequences, rounded up to whole pages by half a page a sequence."""
    held = context_tokens + n_seqs * page * 0.5
    return 4.0 * context_tokens * H * D, 2.0 * held * H * D * itemsize


def paged_prefill(n_new, start, H, D, itemsize=2):
    """One layer's call of a chunk of ``n_new`` positions that follow
    ``start`` cached ones: causal inside the chunk, full against the cache."""
    pairs = n_new * start + n_new * (n_new + 1) / 2.0
    nbytes = (2.0 * (start + n_new) + 2.0 * n_new) * H * D * itemsize
    return 4.0 * pairs * H * D, nbytes
