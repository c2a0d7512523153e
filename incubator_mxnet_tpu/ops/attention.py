"""Attention primitives.

Parity target: the reference's fused BERT attention kernels
(`src/operator/contrib/transformer.cc` — ``interleaved_matmul_selfatt_qk`` /
``_valatt`` and the masked softmax they feed; file-level citation, SURVEY.md
caveat §5.7). Those are hand-written CUDA GEMM+softmax fusions; here ONE
pure function expresses the whole attention block and XLA fuses it onto the
MXU. ``flash=True`` switches to a blockwise streaming-softmax evaluation
(O(T·block) score memory) — the slot a Pallas kernel plugs into; the same
recurrence is what ring attention (parallel/ring_attention.py) runs per
sequence shard.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register
from ..base import MXNetError

_NEG_INF = -1e30


def _sdpa_dense(q, k, v, mask, scale):
    """(B,T,H,D) attention, materializing the (B,H,Tq,Tk) score matrix."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    probs = probs.astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _sdpa_blockwise(q, k, v, key_mask, causal, scale, block_k: int = 512):
    """Streaming-softmax over key blocks (the flash-attention recurrence).

    q: (B,Tq,H,D); k/v: (B,Tk,H,D); key_mask: (B,Tk) bool or None.
    Never materializes more than (B,H,Tq,block_k) scores.
    """
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    block_k = min(block_k, Tk)
    pk = (-Tk) % block_k
    if key_mask is None:
        key_mask = jnp.ones((B, Tk), bool)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        key_mask = jnp.pad(key_mask, ((0, 0), (0, pk)))
    nk = (Tk + pk) // block_k

    # operands keep the input dtype (bf16 -> full-rate MXU); scores and
    # the streaming statistics accumulate in f32, and the scale is
    # applied to the f32 scores (scaling a bf16 q would round it)
    qf = q
    k_blocks = jnp.moveaxis(k.reshape(B, nk, block_k, H, D), 1, 0)
    v_blocks = jnp.moveaxis(v.reshape(B, nk, block_k, H, D), 1, 0)
    m_blocks = jnp.moveaxis(key_mask.reshape(B, nk, block_k), 1, 0)

    pos_q = jnp.arange(Tq)

    acc0 = jnp.zeros((B, Tq, H, D), jnp.float32)
    max0 = jnp.full((B, Tq, H), _NEG_INF, jnp.float32)
    sum0 = jnp.zeros((B, Tq, H), jnp.float32)

    def body(carry, inp):
        acc, row_max, row_sum = carry
        blk_idx, k_blk, v_blk, m_blk = inp
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_blk,
                       preferred_element_type=jnp.float32) * scale
        allow = m_blk[:, None, None, :]                       # (B,1,1,block)
        if causal:
            # bottom-right aligned for Tq != Tk (KV-cache convention):
            # query i sees keys [0, Tk-Tq+i]
            pos_k = blk_idx * block_k + jnp.arange(block_k)
            allow = jnp.logical_and(
                allow,
                (pos_k[None, :] <= pos_q[:, None] + (Tk - Tq))[None, None])
        s = jnp.where(allow, s, _NEG_INF)
        blk_max = jnp.moveaxis(s.max(axis=-1), 1, -1)         # (B,Tq,H)
        new_max = jnp.maximum(row_max, blk_max)
        corr = jnp.exp(row_max - new_max)
        p = jnp.exp(s - jnp.moveaxis(new_max, -1, 1)[..., None])
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32)
        row_sum = row_sum * corr + jnp.moveaxis(p.sum(-1), 1, -1)
        return (acc, new_max, row_sum), None

    (acc, row_max, row_sum), _ = lax.scan(
        body, (acc0, max0, sum0),
        (jnp.arange(nk), k_blocks, v_blocks, m_blocks))
    out = acc / jnp.maximum(row_sum[..., None], 1e-30)
    # fully-masked rows (all-False key mask): row_max never left
    # _NEG_INF, so p was uniformly 1 and out is the mean of V — zero
    # them instead (same contract as the Pallas kernels)
    out = jnp.where((row_max > _NEG_INF / 2)[..., None], out, 0.0)
    return out.astype(q.dtype)


@register("scaled_dot_product_attention", aliases=("sdpa",))
def scaled_dot_product_attention(q, k, v, mask=None, scale=None,
                                 causal=False, flash=False,
                                 valid_length=None, layout="bthd"):
    """Multi-head attention core. q/k/v: (B, T, H, D). ``mask`` is either a
    key-padding mask (B, Tk) or broadcastable to (B, H, Tq, Tk), True =
    attend. Returns (B, Tq, H, D). ``flash=True`` uses the blockwise
    streaming evaluation (key-padding/causal masks only).

    ``layout="bhtd"`` (flash only): q/k/v and the result are
    (B, H, T, D) — the Pallas kernels' native layout. Callers that
    produce a packed (3, B, H, T, D) projection (the transformer cells'
    perf path, mirroring the rationale of the reference's interleaved
    QKV layout in src/operator/contrib/transformer.cc) avoid the
    per-tensor relayout transposes around every kernel call.

    ``valid_length`` (B,) key lengths: the TPU Pallas kernel needs the
    mask in LENGTH form — a (B, Tk) boolean ``mask`` alone sends the
    flash path to the jnp fallback (a boolean mask cannot be converted
    back to lengths under jit), so length-mask callers should pass this
    through for the real kernel to engage. When BOTH ``mask`` and
    ``valid_length`` are given they must describe the same keep-set
    (the kernel uses the lengths, other paths AND the two — this cannot
    be validated under jit, see use_flash_attention)."""
    D = q.shape[-1]
    if scale is None:
        scale = D ** -0.5
    if layout not in ("bthd", "bhtd"):
        raise MXNetError(f"sdpa: unknown layout {layout!r}")
    if layout == "bhtd" and not (flash and (mask is None or
                                            mask.ndim == 2)):
        raise MXNetError(
            "sdpa: layout='bhtd' is the flash-path fast layout; use the "
            "default layout for the dense/attention-weights path")
    if flash and (mask is None or mask.ndim == 2):
        # Pallas kernel on TPU (length-style masks), blockwise jnp
        # otherwise — same streaming-softmax math either way
        from .pallas_attention import use_flash_attention
        return use_flash_attention(q, k, v, key_mask=mask, causal=causal,
                                   scale=scale, valid_length=valid_length,
                                   layout=layout)
    Tq, Tk = q.shape[1], k.shape[1]
    m = mask
    if m is not None and m.ndim == 2:
        m = m[:, None, None, :]                               # key padding
    if valid_length is not None:
        # honor the length form on the dense path too (silently
        # attending padding keys would be wrong whenever the caller
        # passes lengths without a boolean mask)
        vlm = (lax.broadcasted_iota(jnp.int32, (1, 1, 1, Tk), 3) <
               valid_length.astype(jnp.int32)[:, None, None, None])
        m = vlm if m is None else jnp.logical_and(m.astype(bool), vlm)
    if causal:
        # bottom-right aligned when Tq != Tk (queries sit at the END of
        # the key buffer — the KV-cache decode convention; top-left
        # alignment would let early cached queries see future keys)
        cm = jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq)[None, None]
        m = cm if m is None else jnp.logical_and(m, cm)
    return _sdpa_dense(q, k, v, m, scale)


@register("flash_attention_packed")
def flash_attention_packed(qkv, valid_length=None, heads=1, causal=False,
                           scale=None):
    """Flash self-attention straight off the fused projection: ``qkv``
    (B, T, 3*H*D) with q | k | v side by side, as ``Dense(3*units)``
    leaves it; returns (B, T, H*D). No transpose, no split: the packed
    dense Pallas pair reads and writes this layout (the reference's
    interleaved_matmul_selfatt_* pair has the same contract over its
    interleaved buffer, src/operator/contrib/transformer.cc). Only for
    call sites ``ops.pallas_attention.packed_dense_eligible`` admits
    (it raises elsewhere); the transformer cells ask it first
    (models/_attention.py)."""
    from .pallas_attention import flash_packed_self_attention
    return flash_packed_self_attention(qkv, heads, valid_length, causal,
                                       scale)


@register("masked_softmax")
def masked_softmax(scores, mask=None, axis=-1):
    """Softmax with optional boolean mask (True = keep). Parity surface for
    the reference's masked softmax in the transformer contrib ops."""
    if mask is not None:
        scores = jnp.where(mask, scores, _NEG_INF)
    return jax.nn.softmax(scores, axis=axis)


# --------------------------------------------------------------------- #
# interleaved-projection matmul surface (reference:
# src/operator/contrib/transformer.cc interleaved_matmul_selfatt_qk /
# _valatt, interleaved_matmul_encdec_qk / _valatt, div_sqrt_dim —
# file-level citations, SURVEY.md caveat). The reference hand-writes
# strided-batched CUDA GEMMs over an interleaved (seq, batch,
# heads*3*head_dim) QKV buffer; here each op is one reshape+einsum that
# XLA lowers to a single MXU batch-matmul — same user contract, no
# layout gymnastics needed on TPU.
# --------------------------------------------------------------------- #

@register("div_sqrt_dim", aliases=("_contrib_div_sqrt_dim",))
def div_sqrt_dim(data):
    """data / sqrt(last_dim) (reference transformer.cc DivSqrtDim)."""
    return data * (data.shape[-1] ** -0.5)


def _split_interleaved(qkv, heads, parts):
    """(S, B, heads*parts*D) -> ``parts`` tensors of (B*heads, S, D)."""
    S, B = qkv.shape[0], qkv.shape[1]
    x = qkv.reshape(S, B, heads, parts, -1)
    outs = []
    for p in range(parts):
        t = x[:, :, :, p, :]                     # (S, B, H, D)
        t = t.transpose(1, 2, 0, 3).reshape(B * heads, S, -1)
        outs.append(t)
    return outs


@register("interleaved_matmul_selfatt_qk",
          aliases=("_contrib_interleaved_matmul_selfatt_qk",))
def interleaved_matmul_selfatt_qk(queries_keys_values, heads=1):
    """Scaled Q·Kᵀ over an interleaved (S, B, H*3*D) self-attention
    projection. Returns (B*H, S, S); queries pre-scaled by 1/sqrt(D)
    exactly like the reference kernel."""
    q, k, _ = _split_interleaved(queries_keys_values, heads, 3)
    q = q * (q.shape[-1] ** -0.5)
    return jnp.einsum("bqd,bkd->bqk", q, k)


@register("interleaved_matmul_selfatt_valatt",
          aliases=("_contrib_interleaved_matmul_selfatt_valatt",))
def interleaved_matmul_selfatt_valatt(queries_keys_values, attention,
                                      heads=1):
    """attention @ V, restored to the (S, B, H*D) seq-major layout."""
    S, B = queries_keys_values.shape[0], queries_keys_values.shape[1]
    _, _, v = _split_interleaved(queries_keys_values, heads, 3)
    out = jnp.einsum("bqk,bkd->bqd", attention, v)     # (B*H, S, D)
    out = out.reshape(B, heads, S, -1).transpose(2, 0, 1, 3)
    return out.reshape(S, B, -1)


@register("interleaved_matmul_encdec_qk",
          aliases=("_contrib_interleaved_matmul_encdec_qk",))
def interleaved_matmul_encdec_qk(queries, keys_values, heads=1):
    """Scaled Q·Kᵀ for cross-attention: queries (Sq, B, H*D), interleaved
    keys/values (Sk, B, H*2*D). Returns (B*H, Sq, Sk)."""
    Sq, B = queries.shape[0], queries.shape[1]
    q = queries.reshape(Sq, B, heads, -1).transpose(1, 2, 0, 3)
    q = q.reshape(B * heads, Sq, -1)
    q = q * (q.shape[-1] ** -0.5)
    k, _ = _split_interleaved(keys_values, heads, 2)
    return jnp.einsum("bqd,bkd->bqk", q, k)


@register("interleaved_matmul_encdec_valatt",
          aliases=("_contrib_interleaved_matmul_encdec_valatt",))
def interleaved_matmul_encdec_valatt(keys_values, attention, heads=1):
    """attention @ V for cross-attention; output (Sq, B, H*D)."""
    B = keys_values.shape[1]
    _, v = _split_interleaved(keys_values, heads, 2)
    out = jnp.einsum("bqk,bkd->bqd", attention, v)     # (B*H, Sq, D)
    Sq = out.shape[1]
    out = out.reshape(B, heads, Sq, -1).transpose(2, 0, 1, 3)
    return out.reshape(Sq, B, -1)


# --------------------------------------------------------------------- #
# sliding-window (banded) attention surface (reference:
# src/operator/contrib/sldwin_atten*.cc — masked-window self-attention
# for Longformer-style long-context models; file-level citations,
# SURVEY.md caveat). The reference stores scores in a compact
# (B, L, H, W_len) band; on TPU a banded gather breaks MXU tiling, so the
# idiomatic mapping keeps the dense (B*H, L, L) score layout masked to
# the band — XLA fuses the mask into the matmul epilogue, and the flash /
# ring-attention path (ops/pallas_attention.py, parallel/ring_attention)
# is the scalable long-context engine. The op CONTRACT (shapes in/out,
# symmetric + dilation semantics) matches the reference.
# --------------------------------------------------------------------- #

def _sldwin_band_mask(L, w, symmetric, dilation, dtype):
    """(L, L) band mask. ``dilation`` may be a Python int OR a traced
    scalar (the reference passes it as a tensor input) — all arithmetic
    is jnp elementwise, so tracing never needs a concrete value."""
    i = lax.broadcasted_iota(jnp.int32, (L, L), 0)
    j = lax.broadcasted_iota(jnp.int32, (L, L), 1)
    d = j - i
    dil = jnp.asarray(dilation, jnp.int32).reshape(-1)[0]
    lo = -w * dil
    hi = w * dil if symmetric else 0
    band = (d >= lo) & (d <= hi) & (d % jnp.maximum(dil, 1) == 0)
    return band.astype(dtype)


@register("sldwin_atten_mask_like",
          aliases=("_contrib_sldwin_atten_mask_like",))
def sldwin_atten_mask_like(score, dilation, valid_length, num_heads=1,
                           w=1, symmetric=True):
    """Mask with ones where the banded score is valid (reference
    sldwin_atten_mask_like). score: (B*H, L, L) dense-band layout."""
    L = score.shape[-1]
    band = _sldwin_band_mask(L, int(w), bool(symmetric), dilation,
                             score.dtype)
    BH = score.shape[0]
    B = BH // num_heads
    vl = valid_length.astype(jnp.int32).reshape(B, 1)
    pos = jnp.arange(L, dtype=jnp.int32)[None, :]
    keyok = (pos < vl).astype(score.dtype)          # (B, L)
    keyok = jnp.repeat(keyok, num_heads, axis=0)    # (B*H, L)
    return band[None] * keyok[:, None, :] * keyok[:, :, None]


@register("sldwin_atten_score", aliases=("_contrib_sldwin_atten_score",))
def sldwin_atten_score(query, key, dilation, num_heads=1, w=1,
                       symmetric=True):
    """Banded Q·Kᵀ. query/key: (B, L, H*D) → (B*H, L, L) scores with
    out-of-band entries zeroed (reference sldwin_atten_score)."""
    B, L, HD = query.shape
    D = HD // num_heads
    q = query.reshape(B, L, num_heads, D).transpose(0, 2, 1, 3)
    k = key.reshape(B, L, num_heads, D).transpose(0, 2, 1, 3)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).reshape(
        B * num_heads, L, L)
    band = _sldwin_band_mask(L, int(w), bool(symmetric), dilation,
                             scores.dtype)
    return scores * band[None]


@register("sldwin_atten_context",
          aliases=("_contrib_sldwin_atten_context",))
def sldwin_atten_context(score, value, dilation, num_heads=1, w=1,
                         symmetric=True):
    """attention @ V over the band. score: (B*H, L, L); value:
    (B, L, H*D) → (B, L, H*D) (reference sldwin_atten_context)."""
    BH, L, _ = score.shape
    B = BH // num_heads
    D = value.shape[-1] // num_heads
    band = _sldwin_band_mask(L, int(w), bool(symmetric), dilation,
                             score.dtype)
    s = (score * band[None]).reshape(B, num_heads, L, L)
    v = value.reshape(B, L, num_heads, D).transpose(0, 2, 1, 3)
    out = jnp.einsum("bhqk,bhkd->bhqd", s, v)
    return out.transpose(0, 2, 1, 3).reshape(B, L, num_heads * D)
