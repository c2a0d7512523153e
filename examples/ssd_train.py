"""SSD detection training on synthetic boxes (BASELINE.md config #5;
reference: GluonCV `scripts/detection/ssd/train_ssd.py` — file-level
citation, SURVEY.md caveat).

Demonstrates the full detection loop: MultiBoxPrior anchors →
MultiBoxTarget matching → focal-free SSD loss → box_nms decode — all
fixed-shape ops that compile into one XLA program per step.

    python examples/ssd_train.py --steps 20
"""

import argparse

import numpy as np

import _common  # noqa: F401  (compile-cache bootstrap)

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, autograd, gluon
from incubator_mxnet_tpu.models.ssd import ssd_300


def synthetic_batch(rng, batch_size, num_obj=2, num_classes=20):
    """Images with colored rectangles; labels (B, num_obj, 5) [cls x1 y1
    x2 y2] in [0, 1] coords, -1-padded like ImageDetIter emits."""
    imgs = rng.rand(batch_size, 3, 256, 256).astype(np.float32) * 0.1
    labels = np.full((batch_size, num_obj, 5), -1.0, np.float32)
    for b in range(batch_size):
        for o in range(num_obj):
            cls = rng.randint(0, num_classes)
            x1, y1 = rng.uniform(0.0, 0.6, 2)
            w, h = rng.uniform(0.2, 0.35, 2)
            x2, y2 = min(x1 + w, 1.0), min(y1 + h, 1.0)
            xi1, yi1, xi2, yi2 = (int(v * 256) for v in (x1, y1, x2, y2))
            imgs[b, cls % 3, yi1:yi2, xi1:xi2] += 0.8
            labels[b, o] = (cls, x1, y1, x2, y2)
    return imgs, labels


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--lr", type=float, default=0.05)
    args = ap.parse_args()

    mx.random.seed(0)
    rng = np.random.RandomState(0)
    net = ssd_300(num_classes=20)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr, "momentum": 0.9,
                             "wd": 5e-4}, kvstore="device")

    for step in range(args.steps):
        imgs, labels = synthetic_batch(rng, args.batch_size)
        x, y = nd.array(imgs), nd.array(labels)
        with autograd.record():
            anchors, cls_preds, box_preds = net(x)
            box_t, box_m, cls_t = net.training_targets(anchors, cls_preds, y)
            L = net.loss(cls_preds, box_preds, box_t, box_m, cls_t).mean()
        L.backward()
        trainer.step(args.batch_size)
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step:3d}  loss {float(L.asnumpy()):.4f}")

    # inference: decode + NMS
    imgs, _ = synthetic_batch(rng, 2)
    anchors, cls_preds, box_preds = net(nd.array(imgs))
    det = net.detect(cls_preds, box_preds, anchors)
    kept = int((det[:, :, 0].asnumpy() >= 0).sum())
    print(f"detections kept after NMS: {kept} (shape {det.shape})")


if __name__ == "__main__":
    main()
