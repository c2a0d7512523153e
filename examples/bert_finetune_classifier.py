"""BERT sentence-classification fine-tuning (reference: GluonNLP
scripts/bert/finetune_classifier.py — the MRPC/SST recipe).

Runs a tiny config on synthetic sentence-pair data by default so it
works anywhere; the structure (BERTClassifier head, slanted-triangular
LR, grad-clip via the optimizer, accuracy metric) mirrors the
reference's loop.

    python examples/bert_finetune_classifier.py --steps 20
    python examples/bert_finetune_classifier.py --sharding fsdp --dp 2
"""

import argparse

import numpy as np

import _common  # noqa: F401  (compile-cache bootstrap)

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import metric as metric_mod
from incubator_mxnet_tpu import nd, parallel
from incubator_mxnet_tpu.gluon import loss as gloss
from incubator_mxnet_tpu.models import BERTClassifier, bert as bert_mod
from incubator_mxnet_tpu.optimizer import lr_scheduler
from incubator_mxnet_tpu.parallel import mesh as pmesh


def synthetic_batches(rng, n, batch_size, seq_len, vocab, num_classes):
    """Sentence pairs whose label is derivable from the tokens (so the
    tiny model can actually learn): label = first token % num_classes."""
    for _ in range(n):
        ids = rng.randint(4, vocab, (batch_size, seq_len))
        tt = np.zeros((batch_size, seq_len), np.int32)
        tt[:, seq_len // 2:] = 1  # second sentence segment
        vl = np.full((batch_size,), seq_len, np.int32)
        y = ids[:, 0] % num_classes
        yield (nd.array(ids, dtype="int32"), nd.array(tt, dtype="int32"),
               nd.array(vl, dtype="int32"), nd.array(y, dtype="int32"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--classes", type=int, default=3)
    ap.add_argument("--lr", type=float, default=5e-4)
    ap.add_argument("--sharding", choices=("replicated", "fsdp"),
                    default="replicated")
    ap.add_argument("--dp", type=int, default=-1)
    args = ap.parse_args()

    mx.random.seed(0)
    vocab = 256
    bert = bert_mod.bert_tiny(vocab_size=vocab, max_length=args.seq_len)
    clf = BERTClassifier(bert, num_classes=args.classes, dropout=0.1)
    clf.initialize()

    mesh = pmesh.build_mesh(axis_sizes={"dp": args.dp})
    sce = gloss.SoftmaxCrossEntropyLoss()

    def clf_loss(model, ids, tt, vl, y):
        return sce(model(ids, tt, vl), y).mean()

    # warmup + polynomial decay, the reference recipe's schedule
    sched = lr_scheduler.PolyScheduler(
        max_update=args.steps, base_lr=args.lr, final_lr=0.0,
        warmup_steps=max(args.steps // 10, 1))

    trainer = parallel.SPMDTrainer(
        clf, forward_loss=clf_loss, optimizer="adam",
        optimizer_params={"learning_rate": args.lr,
                          "lr_scheduler": sched},
        mesh=mesh, sharding=args.sharding)

    acc = metric_mod.Accuracy()
    rng = np.random.RandomState(0)
    for step, batch in enumerate(synthetic_batches(
            rng, args.steps, args.batch_size, args.seq_len, vocab,
            args.classes)):
        loss = trainer.step(*batch)
        if step % 5 == 0 or step == args.steps - 1:
            import incubator_mxnet_tpu.autograd as ag
            with ag.predict_mode():
                logits = clf(*batch[:3])
            acc.reset()
            acc.update(batch[3], logits)
            print(f"step {step:4d}  loss {float(loss.asnumpy()):.4f}  "
                  f"train-acc {acc.get()[1]:.3f}")
    print("done")


if __name__ == "__main__":
    main()
