"""Shared packed-qkv flash attention fast path for the transformer
model families (BERT/GPT self-attention cells).

Rationale: the projection produces (B, T, 3*H*D), q | k | v side by
side, and that IS the packed dense flash pair's layout
(ops.pallas_attention.flash_dense_packed): the kernels read their
column blocks of it and write (B, T, H*D), the backward writes one
(B, T, 3*H*D) gradient, and nothing is transposed or split in between.
It is the lane tile that decides this. Compiled for the v5e at
BERT-large's shapes a (B, H, T, D) operand is
``bf16[32,16,512,64]{3,2,1,0:T(8,128)(2,1)}``: D=64 is the minor
dimension under a 128-lane tile, every tile half empty, so the kernels
and the five relayouts a layer forward and four backward (``copy``,
``slice_bitcast_fusion``, ``add_bitcast_fusion``: 18-22% of the
BERT-large step's device time, PERF.md PR 27/29) moved twice the bytes;
the per-row statistics as ``f32[32,16,512,1]{3,2,1,0:T(8,128)}`` padded
128-fold. With heads side by side on the lanes two D=64 heads fill a
tile and the statistics are ``f32[32,8,2,512]{3,2,1,0:T(2,128)}``. The
reference keeps an interleaved QKV buffer for its fused attention GEMMs
for the same reason: the projection's layout is the kernels'
(src/operator/contrib/transformer.cc, interleaved_matmul_selfatt_*).

Which call sites get that pair is ``packed_dense_eligible``, from what
the trace sees: the Pallas kernel runs here, T within the dense limit
and a multiple of 128, D = 64 with H even or D a multiple of 128, and
the active trainer mesh absent or of ONE device. Everything else — a
mesh of several devices (dp, fsdp, tp, sp alike), D=16 test models, odd
head counts, T over the dense limit — keeps the older route, unchanged:
one relayout to (3, B, H, T, D) for the (B, H, T, D)-native kernels,
only when the TPU kernel will actually consume it
(``use_packed_fast_path``) — on the jnp path the repack would buy
nothing and the sharding constraints between a transpose and its
inverse could stop XLA from cancelling them. The two routes are kept
apart on purpose (PERF.md section 7: the new pair under a four-chip
``shard_map`` has an unexplained process crash, the old route a clean
record there).
"""

from __future__ import annotations


def packed_flash_self_attention(F, qkv, B, T, H, D, units, causal=False,
                                mask=None, valid_length=None,
                                seq_ax=None):
    """qkv: (B, T, 3*H*D) NDArray, the projection's output as it
    stands. Returns the attention output as (B, T, units). ``seq_ax``
    keeps an active sequence-parallel sharding on the T axis through the
    relayout route (dropping it would force a per-layer all-gather); the
    packed pair runs only where no mesh axis exists to constrain."""
    from ..ops.pallas_attention import packed_dense_eligible

    if packed_dense_eligible(T, H, D):
        return F.flash_attention_packed(qkv, valid_length=valid_length,
                                        heads=H, causal=causal)
    return relayout_flash_self_attention(
        F, qkv, B, T, H, D, units, causal, mask, valid_length, seq_ax)


def relayout_flash_self_attention(F, qkv, B, T, H, D, units, causal, mask,
                                  valid_length, seq_ax):
    """The route for call sites the packed pair does not take: pack once
    to (3, B, H, T, D), the (B, H, T, D) kernels' layout, and back."""
    from ..parallel.spmd import constrain

    qkv_p = qkv.reshape((B, T, 3, H, D)) \
        .transpose((2, 0, 3, 1, 4))                  # (3, B, H, T, D)
    qkv_p = constrain(qkv_p, None, ("dp", "fsdp"), "tp", seq_ax, None)
    qh = qkv_p._op("slice_axis", axis=0, begin=0,
                   end=1).reshape((B, H, T, D))
    kh = qkv_p._op("slice_axis", axis=0, begin=1,
                   end=2).reshape((B, H, T, D))
    vh = qkv_p._op("slice_axis", axis=0, begin=2,
                   end=3).reshape((B, H, T, D))
    out = F.scaled_dot_product_attention(qh, kh, vh, mask=mask,
                                         causal=causal, flash=True,
                                         valid_length=valid_length,
                                         layout="bhtd")
    out = constrain(out, ("dp", "fsdp"), "tp", seq_ax, None)
    return out.transpose((0, 2, 1, 3)).reshape((B, T, units))


def use_packed_fast_path(D):
    """Gate: engage the packed layout only when the Pallas TPU kernel
    will consume it (self-attention is square, so the causal Tq != Tk
    kernel exclusion can never apply here). MXTPU_FORCE_PACKED=1
    overrides — the CPU test mesh uses it to keep parity coverage of
    the packed wiring. Callers must ALSO ensure the mask is in length
    form (valid_length, or no mask) — a boolean-only mask sends
    use_flash_attention to the jnp fallback where the repack buys
    nothing."""
    import os
    if os.environ.get("MXTPU_FORCE_PACKED") == "1":
        return True
    from ..ops.pallas_attention import tpu_kernel_eligible
    return tpu_kernel_eligible(D, causal=False)
