"""LeNet on MNIST with gluon.Trainer — the minimum end-to-end slice
(BASELINE.md config #1; reference: example/gluon/mnist/mnist.py).

Uses the real MNIST files under --data-dir when present, otherwise a
synthetic separable digit problem so the example runs anywhere.

    python examples/gluon_mnist.py --epochs 2
"""

import argparse
import os

import numpy as np

import _common  # noqa: F401  (compile-cache bootstrap)

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, autograd, gluon
from incubator_mxnet_tpu.models import LeNet


def load_data(data_dir, n_synth=2048):
    try:
        ds = gluon.data.vision.MNIST(root=data_dir, train=True)
        X = np.stack([np.asarray(x) for x, _ in ds]).astype(np.float32)
        X = X.reshape(-1, 1, 28, 28) / 255.0
        y = np.asarray([int(l) for _, l in ds])
        return X, y
    except Exception:
        rng = np.random.RandomState(0)
        protos = rng.rand(10, 1, 28, 28).astype(np.float32)
        y = rng.randint(0, 10, n_synth)
        X = protos[y] + 0.1 * rng.randn(n_synth, 1, 28, 28) \
            .astype(np.float32)
        print("MNIST not found — using a synthetic stand-in")
        return X, y


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--data-dir", default=os.path.expanduser("~/.mxtpu/mnist"))
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.005)
    args = ap.parse_args()

    mx.random.seed(0)
    X, y = load_data(args.data_dir)
    net = LeNet(classes=10)
    net.initialize()
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr}, kvstore="device")
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    n = len(X)
    for epoch in range(args.epochs):
        perm = np.random.RandomState(epoch).permutation(n)
        total, correct, lsum, batches = 0, 0, 0.0, 0
        for i in range(0, n - args.batch_size + 1, args.batch_size):
            idx = perm[i:i + args.batch_size]
            data, label = nd.array(X[idx]), nd.array(y[idx])
            with autograd.record():
                out = net(data)
                loss = loss_fn(out, label).mean()
            loss.backward()
            trainer.step(1)
            lsum += float(loss.asnumpy())
            batches += 1
            correct += int((np.argmax(out.asnumpy(), 1) ==
                            y[idx]).sum())
            total += len(idx)
        print(f"epoch {epoch}: loss {lsum / batches:.4f} "
              f"acc {correct / total:.3f}")


if __name__ == "__main__":
    main()
