"""Kernel/remat/batch policy tests (VERDICT r4 item 4): the closed-form
policy must reproduce the hardware-validated ladder configurations."""

import numpy as np
import pytest

from incubator_mxnet_tpu.ops.kernel_policy import (
    flash_kernel_plan, training_plan, usable_hbm_bytes)


def test_bert_base_plan_matches_measured_best():
    plan = training_plan(12, 768, 3072, vocab=30522, seq_len=512)
    assert plan["batch"] == 96          # TPU_RUNS_r04 b96-dots, 25.6% MFU
    assert plan["remat"] == "dots"
    assert plan["dense"] is True        # T=512 -> dense single-tile


def test_bert_large_plan_matches_measured_best():
    plan = training_plan(24, 1024, 4096, vocab=30522, seq_len=512)
    assert plan["batch"] == 32          # TPU_RUNS_r04 large-b32-dots
    assert plan["remat"] == "dots"
    assert plan["dense"] is True


def test_unknown_model_uses_memory_arithmetic():
    # a 2x-deep BERT-large-wide model must get a smaller batch than
    # BERT-large itself (monotone in memory footprint), and never 0
    big = training_plan(48, 1024, 4096, vocab=30522, seq_len=512)
    large = training_plan(24, 1024, 4096, vocab=30522, seq_len=512)
    assert 1 <= big["batch"] <= large["batch"]
    # a tiny model is not anchor-clamped and fills memory
    tiny = training_plan(2, 128, 512, vocab=1000, seq_len=128)
    assert tiny["batch"] == 128


def test_long_context_switches_to_streaming_kernels():
    short = flash_kernel_plan(512, H=12)
    long = flash_kernel_plan(2048, H=12)
    assert short["dense"] is True
    assert short["heads_per_program"] >= 1
    assert long["dense"] is False       # streaming FlashAttention-2
    assert long["heads_per_program"] is None


def test_hbm_budget_scales_batch_down():
    full = training_plan(12, 768, 3072, vocab=30522, seq_len=512)
    half = training_plan(12, 768, 3072, vocab=30522, seq_len=512,
                         hbm_bytes=usable_hbm_bytes() / 2)
    assert half["batch"] < full["batch"]


def test_bench_defaults_follow_policy(monkeypatch):
    """The no-knob bench config is the policy config (VERDICT r4 item 4
    'Done' condition): drive bench's ACTUAL config resolver."""
    import importlib
    import os
    import sys

    monkeypatch.delenv("MXTPU_BENCH_BATCH", raising=False)
    monkeypatch.delenv("MXTPU_BENCH_REMAT", raising=False)
    monkeypatch.delenv("MXTPU_BENCH_DROPOUT", raising=False)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    bench = importlib.import_module("bench")

    B, T, _, dtype, flash, remat, _ = bench._resolve_bert_config("base")
    assert (B, T, dtype, flash, remat) == (96, 512, "bfloat16", True,
                                           "dots")
    B, _, _, _, _, remat, _ = bench._resolve_bert_config("large")
    assert (B, remat) == (32, "dots")
    # env knobs still override the policy (A/B runs)
    monkeypatch.setenv("MXTPU_BENCH_BATCH", "48")
    monkeypatch.setenv("MXTPU_BENCH_REMAT", "0")
    B, _, _, _, _, remat, _ = bench._resolve_bert_config("base")
    assert (B, remat) == (48, False)
