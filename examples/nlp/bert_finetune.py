#!/usr/bin/env python
"""BERT fine-tuning for sentence classification (parity target: the
GluonNLP finetune_classifier.py flow the reference powers with its
contrib fused-MHA ops — GluonNLP's BERT-base, at example scale).

A classifier head goes on BERT's pooled output; the whole thing trains
through SPMDTrainer as one compiled step (fwd+bwd+AdamW) over a dp mesh.
Data is synthetic token sequences with a class-dependent token bias so
the example is runnable air-gapped; plug a real tokenized dataset into
`batches()` for actual use.

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      python examples/nlp/bert_finetune.py --layers 2 --units 128
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxtpu as mx
from mxtpu import gluon, nd
from mxtpu.gluon import HybridBlock, nn
from mxtpu.models.transformer import BERTModel
from mxtpu.parallel import make_mesh, ShardingRules, SPMDTrainer


class BERTClassifier(HybridBlock):
    """BERT + dropout + dense head on the pooled [CLS] output."""

    def __init__(self, bert, num_classes=2, dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.bert = bert
            self.dropout = nn.Dropout(dropout) if dropout else None
            self.classifier = nn.Dense(num_classes,
                                       in_units=bert._units)

    def hybrid_forward(self, F, token_ids):
        _, pooled, _ = self.bert(token_ids)
        if self.dropout is not None:
            pooled = self.dropout(pooled)
        return self.classifier(pooled)


def batches(vocab, seq_len, batch_size, classes, rng):
    """Synthetic classification data: each class biases a token band."""
    while True:
        y = rng.randint(0, classes, batch_size)
        base = rng.randint(4, vocab, (batch_size, seq_len))
        band = 4 + (y[:, None] * 7) % (vocab // 2)
        mask = rng.rand(batch_size, seq_len) < 0.3
        toks = np.where(mask, band + rng.randint(0, 5,
                                                 (batch_size, seq_len)),
                        base)
        yield (nd.array(toks.astype(np.int32), dtype="int32"),
               nd.array(y.astype(np.float32)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--vocab", type=int, default=1000)
    ap.add_argument("--units", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--classes", type=int, default=4)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--lr", type=float, default=5e-4)
    ap.add_argument("--dp", type=int, default=0)
    args = ap.parse_args()

    mesh = make_mesh(dp=args.dp) if args.dp else make_mesh()
    print("mesh:", mesh)

    bert = BERTModel(vocab_size=args.vocab, units=args.units,
                     hidden_size=args.units * 4,
                     num_layers=args.layers, num_heads=args.heads,
                     max_length=args.seq_len, dropout=0.1)
    net = BERTClassifier(bert, num_classes=args.classes)
    net.initialize(mx.init.Xavier())

    trainer = SPMDTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                          "adamw", mesh, ShardingRules(),
                          {"learning_rate": args.lr, "wd": 0.01})

    rng = np.random.RandomState(0)
    data = batches(args.vocab, args.seq_len, args.batch_size,
                   args.classes, rng)
    metric = mx.metric.Accuracy()
    tic = time.time()
    for step in range(args.steps):
        toks, labels = next(data)
        loss = trainer.step(toks, labels)
        if step % 10 == 0 or step == args.steps - 1:
            metric.reset()
            metric.update([labels], [net(toks)])
            _, acc = metric.get()
            print("step %3d loss %.4f acc %.3f (%.1f samples/s)"
                  % (step, float(loss.asnumpy()), acc,
                     args.batch_size * (step + 1) / (time.time() - tic)))
    print("final train-batch accuracy %.3f" % acc)


if __name__ == "__main__":
    main()
