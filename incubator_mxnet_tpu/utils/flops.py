"""Analytic FLOPs and MFU accounting (round 16, docs/TRAINING_PERF.md).

MFU (model FLOPs utilization) is the honest throughput number: the
analytic FLOPs a training step MUST perform (matmuls of the model's
math, nothing the implementation happens to add — recompute under
remat, the optimizer, casts and copies all count as ZERO) divided by
what the hardware could have done in the same wall time. The SNIPPETS
north-star is BERT-large pretraining at >= 45% MFU; this module is how
every training PR banks its number next to tokens/s.

FLOPs formulas (the PaLM-appendix convention, counting a multiply-add
as 2 FLOPs):

  forward  per token ≈ 2·P  +  4·L·T·d       (params + attention scores)
  backward ≈ 2× forward
  train    per token ≈ 6·P  + 12·L·T·d

where P counts the MATMUL-VISIBLE parameters: embedding tables are
excluded from the 2·P term (a lookup is a gather, not a matmul) but a
tied LM head re-enters as a full d×V matmul. Both model helpers below
build the terms from the model's own dims, so step_bench computes MFU
from the same run that banks tokens/s.

Peaks (the one table, ``DEVICE_PEAKS``): keyed by
``jax.devices()[0].device_kind``, each row with its source. An
accelerator that is not in the table RAISES — a peak assumed for a
device nobody looked up turns every utilization into fiction. On the
CPU backend there is no datasheet peak, so ``peak_flops_per_device``
measures a sustained large-matmul rate once per process and labels it
``cpu-proxy``: a relative regression number for the CPU tools
(comparable across arms of one run on one box), never a
hardware-utilization claim. ``MXTPU_PEAK_FLOPS`` overrides both.
"""

from __future__ import annotations

import os
from typing import Optional

from ..base import MXNetError

__all__ = ["transformer_train_flops", "gpt_train_flops",
           "bert_train_flops", "model_train_flops", "count_params",
           "DEVICE_PEAKS", "device_peaks", "peak_flops_per_device", "mfu"]

# The one peak table. Key = jax.devices()[0].device_kind, exactly as the
# runtime spells it (no prefix matching: "TPU v5 lite" is v5e, "TPU v5"
# would be v5p). Only kinds this repository has run on are listed; add a
# row, with its source, when a new chip is first used.
DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e" system architecture: 197
    # TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
    # device_kind as reported by libtpu 0.0.34 on the v5e (first
    # contact, 2026-09).
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9},
}


def device_peaks(device) -> dict:
    """The ``DEVICE_PEAKS`` row of a ``jax.Device``. Raises
    ``MXNetError`` for a device that is not in the table — CPU
    included; the CPU tools go through ``peak_flops_per_device``'s
    ``cpu-proxy`` instead."""
    kind = device.device_kind
    if kind not in DEVICE_PEAKS:
        raise MXNetError(
            f"no peak-table row for device_kind={kind!r} "
            f"(platform={device.platform!r}); known: "
            f"{sorted(DEVICE_PEAKS)}. Add a row with its source to "
            f"utils/flops.py:DEVICE_PEAKS — a peak is never assumed.")
    return DEVICE_PEAKS[kind]


_CPU_PEAK_CACHE: Optional[float] = None


def transformer_train_flops(n_matmul_params: int, n_layers: int,
                            units: int, seq_len: int,
                            tokens: int) -> float:
    """Forward+backward FLOPs for ``tokens`` tokens of a transformer
    with ``n_matmul_params`` matmul-visible parameters: ``6·P·tokens``
    for the parameter matmuls plus ``12·L·T·d·tokens`` for attention
    score/value products (fwd 2 + bwd 4 of each (T,d)x(d,T) pair)."""
    return float(tokens) * (6.0 * n_matmul_params
                            + 12.0 * n_layers * seq_len * units)


def count_params(block, trainable_only: bool = True) -> int:
    """Total parameter count of an initialized block."""
    total = 0
    for p in block.collect_params().values():
        if trainable_only and p.grad_req == "null":
            continue
        n = 1
        for s in p.shape:
            n *= s
        total += n
    return total


def _matmul_params(model, embed_names=("word_embed", "position_embed",
                                       "token_type_embed")) -> int:
    """Parameter count entering matmuls: everything except embedding
    lookups (the tied LM head is added back by the caller)."""
    embeds = 0
    for name in embed_names:
        child = getattr(model, name, None)
        if child is None:
            continue
        for p in child.collect_params().values():
            n = 1
            for s in p.shape:
                n *= s
            embeds += n
    return count_params(model, trainable_only=False) - embeds


def gpt_train_flops(model, batch: int, seq_len: int) -> float:
    """Analytic fwd+bwd FLOPs for one ``GPTModel`` training step over a
    ``(batch, seq_len)`` token grid. The tied LM head (logits = x @ Eᵀ)
    is a real d×V matmul, so the word-embedding table re-enters P."""
    p_mm = _matmul_params(model)
    p_mm += model.vocab_size * model._units          # tied LM head
    return transformer_train_flops(p_mm, model.num_layers,
                                   model._units, seq_len,
                                   batch * seq_len)


def bert_train_flops(model, batch: int, seq_len: int,
                     mlm_head: bool = True) -> float:
    """Analytic fwd+bwd FLOPs for one BERT pretraining step
    (``BERTModel`` or ``BERTForPretraining``). The MLM head's decode
    matmul (d×V, tied) dominates the heads; the NSP/pooler terms ride
    in the generic param count."""
    bert = getattr(model, "bert", model)
    p_mm = _matmul_params(bert)
    extra = count_params(model, trainable_only=False) - \
        count_params(bert, trainable_only=False)
    p_mm += max(extra, 0)
    if mlm_head:
        p_mm += bert.vocab_size * bert._units        # tied MLM decode
    return transformer_train_flops(p_mm, bert.num_layers, bert._units,
                                   seq_len, batch * seq_len)


def model_train_flops(model, batch: int, seq_len: int) -> float:
    """Dispatch on the model family (gpt/bert) — the per-model analytic
    FLOPs hook step_bench and trace_summary share."""
    name = type(model).__name__
    if "GPT" in name:
        return gpt_train_flops(model, batch, seq_len)
    if "BERT" in name:
        return bert_train_flops(model, batch, seq_len)
    raise ValueError(
        f"no analytic FLOPs formula for {name}; supported: GPTModel, "
        f"BERTModel/BERTForPretraining (add one in utils/flops.py)")


def _measure_cpu_peak() -> float:
    """Sustained large-matmul f32 rate on the current backend — the CPU
    MFU proxy ceiling (see module docstring). One-time cost ~0.5 s."""
    import time

    import jax
    import jax.numpy as jnp

    n = 1024
    a = jnp.ones((n, n), jnp.float32)
    b = jnp.ones((n, n), jnp.float32)
    f = jax.jit(lambda x, y: x @ y)
    jax.block_until_ready(f(a, b))                   # compile + warm
    reps = 8
    t0 = time.perf_counter()
    out = a
    for _ in range(reps):
        out = f(out, b)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    return reps * 2.0 * n ** 3 / max(dt, 1e-9)


def peak_flops_per_device() -> dict:
    """Per-device peak FLOPs and its provenance:
    ``{"flops": float, "source": "env"|"tpu-datasheet"|"cpu-proxy",
    "device_kind": str}``. ``MXTPU_PEAK_FLOPS`` overrides; an
    accelerator absent from ``DEVICE_PEAKS`` raises."""
    import jax

    dev = jax.devices()[0]
    kind = dev.device_kind
    env = os.environ.get("MXTPU_PEAK_FLOPS")
    if env:
        return {"flops": float(env), "source": "env",
                "device_kind": kind}
    if dev.platform != "cpu":
        return {"flops": device_peaks(dev)["bf16_flops"],
                "source": "tpu-datasheet", "device_kind": kind}
    global _CPU_PEAK_CACHE
    if _CPU_PEAK_CACHE is None:
        _CPU_PEAK_CACHE = _measure_cpu_peak()
    return {"flops": _CPU_PEAK_CACHE, "source": "cpu-proxy",
            "device_kind": kind}


def mfu(step_flops: float, step_seconds: float, n_devices: int,
        peak: Optional[dict] = None) -> dict:
    """Achieved-FLOPs/peak-FLOPs per device for one step: the fields
    every BENCH_MFU arm banks (docs/TRAINING_PERF.md)."""
    peak = peak or peak_flops_per_device()
    achieved = step_flops / max(step_seconds, 1e-12) / max(n_devices, 1)
    return {
        "model_flops_per_step": step_flops,
        "achieved_flops_per_device": achieved,
        "peak_flops_per_device": peak["flops"],
        "peak_source": peak["source"],
        "mfu": achieved / peak["flops"],
    }
