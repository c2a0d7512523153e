"""Benchmark: one training/decode workload on the chip, in this process.

    python bench.py --workload bert|gpt|resnet|ssd|nmt

Runs ONE workload here (no child process: a chip belongs to one process
at a time), prints ONE JSON line naming the device it ran on
(``platform``, ``device_kind``, ``device_count``) and exits 0. A process
that holds no TPU is refused — there is no CPU configuration and nothing
is carried forward from an earlier run — and any failure exits non-zero.
Utilization divides by the one peak table (``utils/flops.py``); a chip
that is not in it is an error. The compile cache is placed by
``utils/compile_cache.py``.

Replacing this single-metric script with the benchmark's cells is
ROADMAP S1.

Workloads:
  bert    — BERT-base/large pretraining, bf16 + Pallas flash attention +
            LAMB with f32 master weights (BASELINE.md config #3; default)
  resnet  — ResNet-50 ImageNet-shaped data-parallel training step,
            img/s/chip (BASELINE.md config #2)
  ssd     — SSD-300 detection training step (MultiBox ops), img/s/chip
            (BASELINE.md config #5)
  nmt     — Transformer KV-cached beam-search decode, tokens/s (config #4)
  gpt     — GPT-2-small causal-LM pretraining, tokens/s/chip + MFU (the
            decoder-side complement: causal dense kernels + packed qkv)
"""

import argparse
import json
import os
import sys
import time

STEPS, WARMUP = 10, 3


def _peak_flops():
    from incubator_mxnet_tpu.utils.flops import peak_flops_per_device
    return peak_flops_per_device()["flops"]


def _bert_flops_per_step(B, T, M, L, units, hidden, vocab):
    """Honest fwd+bwd FLOP count (6x matmul rule: 2x fwd, 4x bwd):
    encoder matmuls + O(T^2) attention + MLM/NSP heads. Embedding
    gathers are excluded (they are not matmul FLOPs)."""
    enc = 6.0 * B * T * L * (4 * units * units + 2 * units * hidden)
    attn = 12.0 * L * B * T * T * units
    heads = 6.0 * B * M * units * (vocab + units) + 6.0 * B * (
        units * units + 2 * units)
    return enc + attn + heads


def _env_remat_dropout(default_remat="0"):
    """Shared MXTPU_BENCH_REMAT / MXTPU_BENCH_DROPOUT parsing:
    "0" off; "1" whole-layer remat; "dots" selective (save matmul
    outputs, recompute elementwise only)."""
    remat_env = os.environ.get("MXTPU_BENCH_REMAT", default_remat)
    remat = {"0": False, "1": True}.get(remat_env, remat_env)
    dropout = float(os.environ.get("MXTPU_BENCH_DROPOUT", "0.1"))
    return remat, dropout


def _measure_steps(step_fn):
    """Shared measurement harness for every training workload: warmup
    (compile), fenced with ``block_until_ready``; the optional
    MXTPU_BENCH_TRACE profiler block (BASELINE.md protocol: trace
    evidence for perf claims); then the timed loop, fenced the same way.
    Returns (dt_seconds, last_loss)."""
    import jax
    loss = None
    for _ in range(WARMUP):
        loss = step_fn()
    jax.block_until_ready(loss._data)
    trace_dir = os.environ.get("MXTPU_BENCH_TRACE")
    if trace_dir:
        import jax.profiler
        with jax.profiler.trace(trace_dir):
            jax.block_until_ready(step_fn()._data)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        loss = step_fn()
    jax.block_until_ready(loss._data)
    return time.perf_counter() - t0, loss


def _resolve_bert_config(size):
    """(B, T, M, dtype, flash, remat, dropout) for one bench run. With
    no env knobs the defaults come from ops.kernel_policy (the
    best-measured config per model size); env knobs override so an A/B
    run can pin a config."""
    from incubator_mxnet_tpu.ops.kernel_policy import training_plan
    T, M = 512, 76
    dims = {"base": (12, 768, 3072), "large": (24, 1024, 4096)}[size]
    plan = training_plan(*dims, vocab=30522, seq_len=T)
    B = int(os.environ.get("MXTPU_BENCH_BATCH", str(plan["batch"])))
    remat, dropout = _env_remat_dropout(default_remat=plan["remat"])
    return B, T, M, "bfloat16", True, remat, dropout


def _run_bert():
    import numpy as np
    import jax
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, parallel
    from incubator_mxnet_tpu.models import bert as bert_mod

    size = os.environ.get("MXTPU_BENCH_MODEL", "base")
    if size not in ("base", "large"):
        raise ValueError(f"MXTPU_BENCH_MODEL must be base|large, got {size!r}")
    B, T, M, dtype, flash, remat, dropout = _resolve_bert_config(size)

    mx.random.seed(0)
    ctor = bert_mod.bert_large if size == "large" else bert_mod.bert_base
    model = ctor(dtype=dtype, max_length=T, flash=flash,
                 remat=remat, dropout=dropout)
    model.initialize()
    pre = bert_mod.BERTForPretraining(model)
    pre.initialize()

    rng = np.random.RandomState(0)
    batch = (
        nd.array(rng.randint(0, 30522, (B, T)), dtype="int32"),
        nd.array(rng.randint(0, 2, (B, T)), dtype="int32"),
        nd.array(np.full((B,), T), dtype="int32"),
        nd.array(rng.randint(0, T, (B, M)), dtype="int32"),
        nd.array(rng.randint(0, 30522, (B, M)), dtype="int32"),
        nd.ones((B, M)),
        nd.array(rng.randint(0, 2, (B,)), dtype="int32"),
    )

    trainer = parallel.SPMDTrainer(
        pre, forward_loss=bert_mod.pretraining_loss, optimizer="lamb",
        optimizer_params={"learning_rate": 1e-4,
                          "multi_precision": dtype != "float32"},
        sharding="replicated")

    dt, loss = _measure_steps(lambda: trainer.step(*batch))

    n_chips = len(jax.devices())
    tokens_per_sec_chip = B * T * STEPS / dt / n_chips
    flops_per_step = _bert_flops_per_step(
        B, T, M, model.num_layers, model._units, model.hidden_size,
        model.vocab_size)
    mfu = (flops_per_step * STEPS / dt) / (_peak_flops() * n_chips)

    return {
        "metric": f"bert_{size}_pretrain_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec_chip, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.45, 4),
        "mfu": round(mfu, 4),
        "batch": B,
        "seq_len": T,
        "dtype": dtype,
        "flash": flash,
    }


def _gpt_flops_per_step(B, T, L, units, hidden, vocab):
    """Honest fwd+bwd FLOP count for causal LM training (6x matmul
    rule): decoder matmuls + causal O(T^2/2) attention + the full-vocab
    LM head (dominant at GPT-2 vocab). Embedding gathers excluded."""
    dec = 6.0 * B * T * L * (4 * units * units + 2 * units * hidden)
    attn = 6.0 * L * B * T * T * units          # causal: half of full
    head = 6.0 * B * T * units * vocab
    return dec + attn + head


def _run_gpt():
    """GPT-2-small causal-LM pretraining throughput (tokens/s/chip +
    MFU). Exercises the CAUSAL dense Pallas kernels + packed-qkv path —
    the decoder-side complement to the BERT (encoder) headline."""
    import numpy as np
    import jax
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, parallel
    from incubator_mxnet_tpu.models import gpt as gpt_mod

    B = int(os.environ.get("MXTPU_BENCH_BATCH", "16"))
    T = 512
    dtype = "bfloat16"
    flash = True
    remat, dropout = _env_remat_dropout()

    mx.random.seed(0)
    # gpt_small pins max_length=1024 (>= the benched T=512)
    model = gpt_mod.gpt_small(dtype=dtype, flash=flash, remat=remat,
                              dropout=dropout)
    model.initialize()

    rng = np.random.RandomState(0)
    V = model.vocab_size
    batch = (
        nd.array(rng.randint(0, V, (B, T)), dtype="int32"),
        nd.array(rng.randint(0, V, (B, T)), dtype="int32"),
    )

    trainer = parallel.SPMDTrainer(
        model, forward_loss=gpt_mod.lm_loss, optimizer="adamw",
        optimizer_params={"learning_rate": 1e-4,
                          "multi_precision": dtype != "float32"},
        sharding="replicated")

    dt, loss = _measure_steps(lambda: trainer.step(*batch))

    n_chips = len(jax.devices())
    tokens_per_sec_chip = B * T * STEPS / dt / n_chips
    flops_per_step = _gpt_flops_per_step(
        B, T, model.num_layers, model._units, model.hidden_size, V)
    mfu = (flops_per_step * STEPS / dt) / (_peak_flops() * n_chips)

    return {
        "metric": "gpt2_small_pretrain_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec_chip, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.45, 4),
        "mfu": round(mfu, 4),
        "batch": B,
        "seq_len": T,
        "dtype": dtype,
        "flash": flash,
    }


def _run_resnet():
    import numpy as np
    import jax
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, parallel
    from incubator_mxnet_tpu.gluon import loss as gloss
    from incubator_mxnet_tpu.gluon.model_zoo.vision import resnet50_v1

    # separate knob from the BERT flagship's MXTPU_BENCH_BATCH: a BERT
    # batch override must not silently change the ResNet config-#2
    # batch (B=64) the metric is defined against
    B = int(os.environ.get("MXTPU_BENCH_RESNET_BATCH", "64"))
    side = 224
    dtype = "bfloat16"

    mx.random.seed(0)
    net = resnet50_v1()
    net.initialize()
    if dtype != "float32":
        # cast params too (the reference's net.cast('float16') recipe) —
        # a bf16 input against f32 weights silently promotes every conv
        # back to f32; multi_precision SGD keeps f32 master weights
        rng0 = np.random.RandomState(0)
        net(nd.array(rng0.rand(1, 3, side, side).astype("float32")))
        net.cast(dtype)

    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(B, 3, side, side).astype("float32"))
    if dtype != "float32":
        x = x.astype(dtype)
    y = nd.array(rng.randint(0, 1000, (B,)), dtype="int32")

    trainer = parallel.SPMDTrainer(
        net, loss=gloss.SoftmaxCrossEntropyLoss(), optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                          "multi_precision": dtype != "float32"},
        sharding="replicated")

    dt, _ = _measure_steps(lambda: trainer.step(x, y))

    n_chips = len(jax.devices())
    img_per_sec_chip = B * STEPS / dt / n_chips
    # ResNet-50 fwd at 224^2 is the standard ~4.1 GFLOP/img (mul+add
    # counted); training ~= 3x fwd (fwd + dgrad + wgrad)
    mfu = (img_per_sec_chip * 3.0 * 4.1e9) / _peak_flops()
    return {
        "metric": "resnet50_train_img_per_sec_per_chip",
        "value": round(img_per_sec_chip, 2),
        "unit": "img/s/chip",
        "vs_baseline": 0.0,
        "mfu": round(mfu, 4),
        "batch": B,
        "dtype": dtype,
    }


def _run_ssd():
    """SSD-300 detection training step (BASELINE.md config #5 —
    validates the contrib/custom-op path under training: MultiBoxPrior
    anchors, MultiBoxTarget matching, masked CE + smooth-L1; upstream
    GluonCV scripts/detection/ssd/train_ssd.py, file-level citation)."""
    import numpy as np
    import jax
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, parallel
    from incubator_mxnet_tpu.models.ssd import ssd_300

    B = int(os.environ.get("MXTPU_BENCH_SSD_BATCH", "32"))
    side = 300

    mx.random.seed(0)
    net = ssd_300(num_classes=20)
    net.initialize()

    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(B, 3, side, side).astype(np.float32))
    labels = np.full((B, 2, 5), -1.0, np.float32)
    for b in range(B):
        for o in range(2):
            x1, y1 = rng.uniform(0.0, 0.6, 2)
            w, h = rng.uniform(0.2, 0.35, 2)
            labels[b, o] = (rng.randint(0, 20), x1, y1,
                            min(x1 + w, 1.0), min(y1 + h, 1.0))
    y = nd.array(labels)

    def fwd_loss(model, xb, yb):
        anchors, cls_preds, box_preds = model(xb)
        box_t, box_m, cls_t = model.training_targets(anchors, cls_preds,
                                                     yb)
        return model.loss(cls_preds, box_preds, box_t, box_m, cls_t)

    trainer = parallel.SPMDTrainer(
        net, forward_loss=fwd_loss, optimizer="sgd",
        optimizer_params={"learning_rate": 0.05, "momentum": 0.9,
                          "wd": 5e-4}, sharding="replicated")

    dt, _ = _measure_steps(lambda: trainer.step(x, y))
    n_chips = len(jax.devices())
    return {
        "metric": "ssd300_train_img_per_sec_per_chip",
        "value": round(B * STEPS / dt / n_chips, 2),
        "unit": "img/s/chip",
        "vs_baseline": 0.0,
        "batch": B,
        "side": side,
    }


def _run_nmt():
    """Transformer KV-cached beam-search decode throughput (BASELINE.md
    config #4, the inference path — upstream scripts/nmt translation)."""
    import numpy as np
    import jax
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.models import transformer as tm

    B, Ts, Tgen, K = 16, 64, 48, 4
    model = tm.transformer_base(max_length=256)
    mx.random.seed(0)
    model.initialize()

    rng = np.random.RandomState(0)
    src = nd.array(rng.randint(3, 1000, (B, Ts)), dtype="int32")

    def run():
        out, scores = tm.beam_search_translate_cached(
            model, src, beam_size=K, max_length=Tgen)
        return float(scores.asnumpy().sum())

    run()  # compile
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        run()
    dt = time.perf_counter() - t0

    # beam search runs on ONE device (no mesh distribution), so per-chip
    # throughput is the single-device rate — do not divide by device count
    return {
        "metric": "nmt_cached_beam_decode_tokens_per_sec_per_chip",
        "value": round(B * Tgen * reps / dt, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": 0.0,
        "batch": B,
        "beam": K,
        "gen_len": Tgen,
    }


_WORKLOADS = {"bert": _run_bert, "resnet": _run_resnet, "nmt": _run_nmt,
              "gpt": _run_gpt, "ssd": _run_ssd}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", choices=sorted(_WORKLOADS),
                    default="bert")
    args = ap.parse_args(argv)

    import jax
    from incubator_mxnet_tpu.utils import compile_cache
    cache_dir = compile_cache.enable()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench.py: no TPU (platform={dev.platform!r}); the "
                 f"workloads are device configurations and have no CPU "
                 f"variant")
    result = _WORKLOADS[args.workload]()
    result.update(platform=dev.platform, device_kind=dev.device_kind,
                  device_count=len(jax.devices()),
                  compile_cache_dir=cache_dir)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
