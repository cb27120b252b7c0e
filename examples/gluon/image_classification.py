#!/usr/bin/env python
"""Image classification with the Gluon vision model zoo (parity:
example/image-classification/ + example/gluon/image_classification.py —
GluonCV's ResNet-50 ImageNet training loop at example scale).

Trains any model-zoo architecture on CIFAR-10 when present under
--data-root, else on a synthetic 10-class image set, with hybridize,
AMP-style bf16 casting (--bf16), Speedometer logging, and checkpointing
— the same knobs the reference example exposes.
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxtpu as mx
from mxtpu import autograd, gluon, nd
from mxtpu.gluon.data import ArrayDataset, DataLoader
from mxtpu.gluon.model_zoo.vision import get_model


def load_data(root, n_train=2048, n_val=512, size=32):
    try:
        from mxtpu.gluon.data.vision import CIFAR10, transforms
        tf = transforms.Compose([
            transforms.ToTensor(),  # HWC uint8 -> CHW float in [0,1]
            transforms.Normalize((0.4914, 0.4822, 0.4465),
                                 (0.2470, 0.2435, 0.2616))])
        return (CIFAR10(root=root, train=True).transform_first(tf),
                CIFAR10(root=root, train=False).transform_first(tf))
    except Exception:
        rng = np.random.RandomState(0)
        centers = rng.rand(10, 3, 1, 1).astype("f")

        def synth(n, seed):
            r = np.random.RandomState(seed)
            ys = r.randint(0, 10, n)
            xs = (centers[ys] +
                  0.15 * r.randn(n, 3, size, size).astype("f")).clip(0, 1)
            return ArrayDataset(nd.array(xs), nd.array(ys.astype("f")))
        return synth(n_train, 1), synth(n_val, 2)


def evaluate(net, loader, metric):
    metric.reset()
    for data, label in loader:
        metric.update([label], [net(data)])
    return metric.get()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet18_v1")
    ap.add_argument("--data-root", default="./data")
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--bf16", action="store_true",
                    help="cast the model to bfloat16 (AMP policy)")
    ap.add_argument("--no-hybridize", action="store_true")
    ap.add_argument("--save-prefix", default=None)
    args = ap.parse_args()

    train_ds, val_ds = load_data(args.data_root)
    train = DataLoader(train_ds, args.batch_size, shuffle=True,
                       last_batch="discard")
    val = DataLoader(val_ds, args.batch_size, last_batch="discard")

    net = get_model(args.model, classes=10)
    net.initialize(mx.init.Xavier(magnitude=2.0))
    if args.bf16:
        net.cast("bfloat16")
    if not args.no_hybridize:
        net.hybridize(static_alloc=True)

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr, "momentum": 0.9,
                             "wd": 1e-4})
    metric = mx.metric.Accuracy()

    for epoch in range(args.epochs):
        tic = time.time()
        metric.reset()
        for i, (data, label) in enumerate(train):
            with autograd.record():
                out = net(data)
                loss = loss_fn(out, label)
            loss.backward()
            trainer.step(data.shape[0])
            metric.update([label], [out])
            if i and i % 20 == 0:
                name, acc = metric.get()
                print("Epoch[%d] Batch [%d]\tSpeed: %.2f samples/sec"
                      "\t%s=%.3f"
                      % (epoch, i,
                         args.batch_size * 20 / max(time.time() - tic,
                                                    1e-9),
                         name, acc))
                tic = time.time()
        name, acc = metric.get()
        print("Epoch[%d] Train-%s=%.4f" % (epoch, name, acc))
        name, vacc = evaluate(net, val, metric)
        print("Epoch[%d] Validation-%s=%.4f" % (epoch, name, vacc))
        if args.save_prefix:
            net.save_parameters("%s-%04d.params"
                                % (args.save_prefix, epoch))


if __name__ == "__main__":
    main()
