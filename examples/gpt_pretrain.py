"""GPT causal-LM pretraining + generation with the fused SPMD trainer
(reference class: the GluonNLP language-model scripts; decoder-side
complement to examples/bert_pretrain.py).

Runs a tiny config on synthetic data by default so it works anywhere;
``--size small`` with real TPU hardware is the benchmark configuration
(see bench.py --workload gpt for the measured variant). After training
it greedily decodes a few tokens from a prompt through the KV-cached
incremental path.

    python examples/gpt_pretrain.py --steps 10
    python examples/gpt_pretrain.py --sharding fsdp --dp 2 --tp 2 --flash
"""

import argparse

import numpy as np

import _common  # noqa: F401  (compile-cache bootstrap)

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, parallel
from incubator_mxnet_tpu.models import gpt as gpt_mod
from incubator_mxnet_tpu.parallel import mesh as pmesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", choices=("mini", "small"), default="mini")
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
    if args.size == "mini":
        model = gpt_mod.gpt_mini(vocab_size=512,
                                 max_length=max(args.seq_len, 96),
                                 dropout=0.0, flash=args.flash,
                                 remat=args.remat)
    else:
        model = gpt_mod.gpt_small(dtype="bfloat16", flash=args.flash,
                                  remat=args.remat)
    model.initialize()
    vocab = model.vocab_size

    mesh = pmesh.build_mesh(axis_sizes={"dp": args.dp, "fsdp": args.fsdp,
                                        "tp": args.tp})
    trainer = parallel.SPMDTrainer(
        model, forward_loss=gpt_mod.lm_loss, optimizer="adamw",
        optimizer_params={"learning_rate": args.lr,
                          "multi_precision": args.size == "small"},
        mesh=mesh, sharding=args.sharding)

    rng = np.random.RandomState(0)
    B, T = args.batch_size, args.seq_len
    # a learnable synthetic stream: next token = (token + 1) % vocab
    base = rng.randint(0, vocab, (B, 1))
    ids = (base + np.arange(T + 1)[None, :]) % vocab
    inputs = nd.array(ids[:, :-1], dtype="int32")
    labels = nd.array(ids[:, 1:], dtype="int32")

    for step in range(args.steps):
        loss = trainer.step(inputs, labels)
        if step % max(1, args.steps // 5) == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {float(loss.asnumpy()):.4f}")

    # KV-cached greedy decode from a short prompt
    prompt = nd.array(ids[:2, :8], dtype="int32")
    out = gpt_mod.cached_generate(model, prompt, max_new_tokens=8)
    print("prompt :", np.asarray(prompt.asnumpy())[0].tolist())
    print("decoded:", np.asarray(out.asnumpy())[0, 8:].tolist(),
          "(expect the +1 (mod vocab) continuation after training)")


if __name__ == "__main__":
    main()
