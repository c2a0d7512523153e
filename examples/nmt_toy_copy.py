"""Transformer NMT on a toy copy/reverse task + jitted beam search
(BASELINE.md config #4; reference: GluonNLP `scripts/nmt` train_transformer
— file-level citation, SURVEY.md caveat).

The task: translate a random token sequence to its REVERSE. Small enough
to train in ~a minute on CPU, while exercising the full encoder-decoder
stack, label smoothing, and the fixed-shape beam-search decode.

    python examples/nmt_toy_copy.py --steps 120
"""

import argparse

import numpy as np

import _common  # noqa: F401  (compile-cache bootstrap)

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, autograd, gluon
from incubator_mxnet_tpu.models.transformer import (TransformerModel,
                                                    beam_search_translate)

PAD, BOS, EOS = 0, 1, 2
VOCAB = 32
SEQ = 8


def batch(rng, n):
    src = rng.randint(3, VOCAB, (n, SEQ))
    tgt = src[:, ::-1].copy()
    tgt_in = np.concatenate([np.full((n, 1), BOS), tgt[:, :-1]], axis=1)
    return (src.astype(np.int32), tgt_in.astype(np.int32),
            tgt.astype(np.int32))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--lr", type=float, default=3e-3)
    args = ap.parse_args()

    mx.random.seed(0)
    rng = np.random.RandomState(0)
    model = TransformerModel(src_vocab=VOCAB, tgt_vocab=VOCAB,
                             units=64, hidden_size=128, num_heads=4,
                             num_layers=2, max_length=SEQ + 4)
    model.initialize()
    trainer = gluon.Trainer(model.collect_params(), "adam",
                            {"learning_rate": args.lr}, kvstore="device")
    lf = gluon.loss.SoftmaxCrossEntropyLoss()

    for step in range(args.steps):
        src, tgt_in, tgt = batch(rng, 32)
        with autograd.record():
            logits = model(nd.array(src), nd.array(tgt_in))
            L = lf(logits.reshape((-1, VOCAB)),
                   nd.array(tgt.reshape(-1))).mean()
        L.backward()
        trainer.step(1)
        if step % 30 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {float(L.asnumpy()):.4f}")

    # beam-search decode and measure exact-reversal accuracy
    src, _, tgt = batch(rng, 16)
    toks, scores = beam_search_translate(model, nd.array(src), beam_size=4,
                                         max_length=SEQ + 2, bos_id=BOS,
                                         eos_id=EOS)
    best = toks.asnumpy()[:, 0, :SEQ]
    acc = float((best == tgt).mean())
    print(f"beam-search token accuracy on reverse task: {acc:.3f}")


if __name__ == "__main__":
    main()
