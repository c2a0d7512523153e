"""BERT pretraining with the fused SPMD trainer (BASELINE.md config #3;
reference: the GluonNLP scripts/bert pretraining loop).

Runs a tiny config on synthetic data by default so it works anywhere;
``--size base`` with real TPU hardware is the benchmark configuration
(see bench.py for the measured variant).

    python examples/bert_pretrain.py --steps 10
    python examples/bert_pretrain.py --sharding fsdp --dp 2 --fsdp 2 --tp 2
"""

import argparse

import numpy as np

import _common  # noqa: F401  (compile-cache bootstrap)

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, parallel
from incubator_mxnet_tpu.models import bert as bert_mod
from incubator_mxnet_tpu.parallel import mesh as pmesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", choices=("tiny", "base"), default="tiny")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--sharding", choices=("replicated", "fsdp"),
                    default="replicated")
    ap.add_argument("--dp", type=int, default=-1)
    ap.add_argument("--fsdp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--flash", action="store_true")
    ap.add_argument("--remat", action="store_true")
    args = ap.parse_args()

    mx.random.seed(0)
    if args.size == "tiny":
        model = bert_mod.bert_tiny(vocab_size=1024,
                                   max_length=args.seq_len,
                                   flash=args.flash, remat=args.remat)
        vocab = 1024
    else:
        model = bert_mod.bert_base(max_length=args.seq_len,
                                   dtype="bfloat16", flash=args.flash,
                                   remat=args.remat)
        vocab = model.vocab_size
    model.initialize()
    pre = bert_mod.BERTForPretraining(model)
    pre.initialize()

    mesh = pmesh.build_mesh(axis_sizes={"dp": args.dp, "fsdp": args.fsdp,
                                        "tp": args.tp})
    trainer = parallel.SPMDTrainer(
        pre, forward_loss=bert_mod.pretraining_loss, optimizer="lamb",
        optimizer_params={"learning_rate": args.lr,
                          "multi_precision": args.size == "base"},
        mesh=mesh, sharding=args.sharding)

    B, T, M = args.batch_size, args.seq_len, max(2, args.seq_len // 8)
    rng = np.random.RandomState(0)
    batch = (
        nd.array(rng.randint(0, vocab, (B, T)), dtype="int32"),
        nd.array(rng.randint(0, 2, (B, T)), dtype="int32"),
        nd.array(np.full((B,), T), dtype="int32"),
        nd.array(rng.randint(0, T, (B, M)), dtype="int32"),
        nd.array(rng.randint(0, vocab, (B, M)), dtype="int32"),
        nd.ones((B, M)),
        nd.array(rng.randint(0, 2, (B,)), dtype="int32"),
    )
    for step in range(args.steps):
        loss = trainer.step(*batch)
        if step % max(1, args.steps // 10) == 0 or step == args.steps - 1:
            print(f"step {step}: loss {float(loss.asnumpy()):.4f}")


if __name__ == "__main__":
    main()
