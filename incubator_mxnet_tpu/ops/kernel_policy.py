"""Automatic kernel / remat / batch selection policy (VERDICT r4 item 4).

One function family maps STATIC shapes + hardware budgets to the
training configuration, replacing the measurement ladder's env-knob
folklore. The ladder's A/B rungs remain as audits of this policy.

Measured anchors (v5e, 2026-07-31, TPU_RUNS_r04 / BENCH_MEASURED_r04.json):
  - bert-base  B=96  dense kernels, dots-remat: 85,771 tok/s/chip (25.6%)
  - bert-large B=32  dense kernels, dots-remat: 29,184 tok/s/chip (29.5%)
  - large-b24 on the STREAMING kernels measured slower than plain
    large-b16 — kernel family, remat and batch interact, which is why
    this is one joint policy rather than three knobs.
  - B=64 full-remat measured slower than B=48 no-remat (r3): whole-layer
    remat recompute outweighs the batch gain; selective "dots" remat
    (save matmul outputs, recompute elementwise) is the default.

The reference's analogue is the per-op cuDNN algo + workspace selection
(`src/operator/nn/convolution.cu` cudnn_algoreg; file-level citation,
SURVEY.md caveat) — there the tuner measures at runtime; here shapes are
static under jit, so the policy is closed-form + measured anchors.
"""

from __future__ import annotations

# the chip the measured anchors below were taken on; a process with no
# accelerator (the CPU tests, planning ahead of a run) plans for it
_ANCHOR_DEVICE_KIND = "TPU v5 lite"
# deliberately conservative: fragmentation, XLA workspaces and the fused
# optimizer all eat into the nominal HBM
_USABLE_SHARE = 0.85


def usable_hbm_bytes():
    """Plannable HBM per device: ``_USABLE_SHARE`` of what the local
    accelerator reports (``memory_stats()["bytes_limit"]``), or of the
    anchor chip's row in the one device table when the process holds no
    accelerator."""
    import jax

    from ..utils.flops import DEVICE_PEAKS
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        limit = DEVICE_PEAKS[_ANCHOR_DEVICE_KIND]["hbm_bytes"]
    else:
        limit = dev.memory_stats()["bytes_limit"]
    return _USABLE_SHARE * limit


# (num_layers, units) -> largest batch validated on hardware. The
# arithmetic below may admit a larger batch (e.g. base B=128 pencils
# out); raise an anchor only when the ladder's audit rung for that
# batch has banked a number (b128-dense-dots / large-b48-dense).
_MEASURED_MAX_BATCH = {(12, 768): 96, (24, 1024): 32}

_BATCH_CANDIDATES = (128, 96, 64, 48, 32, 24, 16, 8, 4, 2, 1)


def flash_kernel_plan(Tq, H, Tk=None, bwd=False):
    """Dense-vs-streaming + heads-per-program for the attention kernels.
    Delegates to the kernels' own static dispatch so this plan can never
    drift from what ops.pallas_attention actually runs. (Head dim does
    not enter this dispatch — eligibility on D is the separate
    tpu_kernel_eligible gate.)"""
    from .pallas_attention import _dense_hpp, _use_dense
    dense = _use_dense(Tq, Tk if Tk is not None else Tq)
    return {"dense": dense,
            "heads_per_program": _dense_hpp(H, bwd=bwd) if dense else None}


def _param_count(L, units, hidden, vocab, T):
    """Encoder-family parameter count: embeddings + L transformer layers
    (qkv/out projections 4*units^2 + FFN 2*units*hidden) + pooler/head
    order-of-magnitude terms."""
    emb = (vocab + T + 8) * units
    layer = 4 * units * units + 2 * units * hidden + 9 * units
    head = units * units + vocab  # pooler + tied-embedding LM bias
    return emb + L * layer + head


def _saved_activation_bytes(B, T, units, hidden, dtype_bytes, remat):
    """Per-layer residual bytes the backward needs.

    remat="dots" keeps matmul OUTPUTS only (qkv 3u, attn out u, ffn-in
    hidden, ffn-out u) and recomputes elementwise chains — the policy's
    default. remat=False keeps the elementwise intermediates too
    (~2x). remat=True (whole-layer) keeps only layer boundaries but
    recomputes every dot (measured slower end-to-end; never chosen)."""
    dots = B * T * (5 * units + hidden) * dtype_bytes
    if remat == "dots":
        return dots
    if remat is True:
        return B * T * units * dtype_bytes
    return 2 * dots


def training_plan(num_layers, units, hidden, vocab, seq_len,
                  dtype="bfloat16", hbm_bytes=None):
    """{batch, remat, dense, fwd/bwd heads_per_program} for one chip
    (``hbm_bytes`` defaults to ``usable_hbm_bytes()``).

    Largest candidate batch whose params (multi-precision LAMB: bf16
    weights + f32 master + 2 f32 moments = 14 B/param) plus saved
    activations fit the usable HBM, clamped to the hardware-validated
    anchor for known model shapes."""
    if hbm_bytes is None:
        hbm_bytes = usable_hbm_bytes()
    dtype_bytes = 2 if dtype in ("bfloat16", "float16") else 4
    params = _param_count(num_layers, units, hidden, vocab, seq_len)
    param_bytes = params * (14 if dtype_bytes == 2 else 12)
    batch = None
    for b in _BATCH_CANDIDATES:
        act = _saved_activation_bytes(b, seq_len, units, hidden,
                                      dtype_bytes, "dots") * num_layers
        if param_bytes + act <= hbm_bytes:
            batch = b
            break
    if batch is None:
        batch = 1
    anchor = _MEASURED_MAX_BATCH.get((num_layers, units))
    if anchor is not None:
        batch = min(batch, anchor)
    # heads: encoder convention units = H * 64
    H = max(1, units // 64)
    plan = flash_kernel_plan(seq_len, H)
    return {"batch": batch, "remat": "dots", "dense": plan["dense"],
            "fwd_heads_per_program": plan["heads_per_program"],
            "bwd_heads_per_program": flash_kernel_plan(
                seq_len, H, bwd=True)["heads_per_program"]}
