#!/usr/bin/env python
"""LeNet/MLP on MNIST, imperative Gluon (parity: example/gluon/mnist/
mnist.py: LeNet on MNIST, the reference's first Gluon example).

Runs against real MNIST files when present under --data-root; otherwise
generates a deterministic synthetic digit-like dataset so the example is
runnable air-gapped (documented divergence from the downloading reference).
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxtpu as mx
from mxtpu import gluon, autograd
from mxtpu.gluon import nn
from mxtpu.gluon.data import ArrayDataset, DataLoader
from mxtpu.gluon.data.vision import transforms


def load_mnist(root, train):
    try:
        from mxtpu.gluon.data.vision import MNIST
        return MNIST(root=root, train=train)
    except Exception:
        # synthetic fallback: blobs per class, fixed seed
        rng = np.random.RandomState(0 if train else 1)
        n = 6000 if train else 1000
        y = rng.randint(0, 10, n)
        X = (rng.rand(n, 28, 28, 1) * 64).astype("uint8")
        for i in range(n):  # class-dependent bright square
            c = y[i]
            X[i, 2 + c * 2:8 + c * 2, 4:24] = 220
        return ArrayDataset(X, y.astype("int32"))


def build_net(arch):
    net = nn.HybridSequential()
    if arch == "mlp":
        net.add(nn.Dense(128, activation="relu"),
                nn.Dense(64, activation="relu"),
                nn.Dense(10))
    else:  # lenet
        net.add(nn.Conv2D(20, kernel_size=5, activation="relu"),
                nn.MaxPool2D(2, 2),
                nn.Conv2D(50, kernel_size=5, activation="relu"),
                nn.MaxPool2D(2, 2),
                nn.Flatten(),
                nn.Dense(500, activation="relu"),
                nn.Dense(10))
    return net


def evaluate(net, loader):
    metric = mx.metric.Accuracy()
    for data, label in loader:
        metric.update([label], [net(data)])
    return metric.get()[1]


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--arch", default="lenet", choices=["mlp", "lenet"])
    parser.add_argument("--batch-size", type=int, default=100)
    parser.add_argument("--epochs", type=int, default=3)
    parser.add_argument("--lr", type=float, default=0.02)
    parser.add_argument("--hybridize", action="store_true")
    parser.add_argument("--data-root",
                        default=os.path.join("~", ".mxtpu", "datasets",
                                             "mnist"))
    args = parser.parse_args()

    t = transforms.Compose([transforms.ToTensor(),
                            transforms.Normalize(0.13, 0.31)])
    train_ds = load_mnist(args.data_root, True).transform_first(t)
    test_ds = load_mnist(args.data_root, False).transform_first(t)
    train_loader = DataLoader(train_ds, args.batch_size, shuffle=True,
                              last_batch="discard")
    test_loader = DataLoader(test_ds, args.batch_size)

    net = build_net(args.arch)
    net.initialize(init=mx.init.Xavier())
    if args.hybridize:
        net.hybridize(static_alloc=True)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr, "momentum": 0.9})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    metric = mx.metric.Accuracy()

    for epoch in range(args.epochs):
        metric.reset()
        tic = time.time()
        samples = 0
        for data, label in train_loader:
            with autograd.record():
                out = net(data)
                loss = loss_fn(out, label)
            loss.backward()
            trainer.step(data.shape[0])
            metric.update([label], [out])
            samples += data.shape[0]
        elapsed = time.time() - tic
        print("Epoch %d: train acc %.4f, %.0f samples/sec" % (
            epoch, metric.get()[1], samples / elapsed))
    acc = evaluate(net, test_loader)
    print("Test accuracy: %.4f" % acc)
    return acc


if __name__ == "__main__":
    main()
