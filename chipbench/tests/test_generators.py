"""The generators are functions of the seed and honour their parameters."""

import numpy as np
import pytest

from chipbench import harness

BACKLOG = harness.load_module("generators", "backlog")
TRAFFIC = harness.load_json(harness.HERE, "traffic", "pretrain-seq512.json")
CFG = harness.load_json(harness.HERE, "configs", "bert-base.json")


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345])
def test_train_batches_are_a_function_of_seed_and_step(seed):
    a = BACKLOG.train_batch(TRAFFIC, CFG, 4, seed, 3)
    b = BACKLOG.train_batch(TRAFFIC, CFG, 4, seed, 3)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    other_step = BACKLOG.train_batch(TRAFFIC, CFG, 4, seed, 4)
    other_seed = BACKLOG.train_batch(TRAFFIC, CFG, 4, seed + 1, 3)
    assert not np.array_equal(a[0], other_step[0])
    assert not np.array_equal(a[0], other_seed[0])


def test_train_batches_honour_shape_and_vocabulary():
    tokens, labels = BACKLOG.train_batch(TRAFFIC, CFG, 8, 5, 0)
    for x in (tokens, labels):
        assert x.shape == (8, TRAFFIC["seq"]) and x.dtype == np.int32
        assert x.min() >= 0 and x.max() < CFG["vocab_size"]
    assert not np.array_equal(tokens, labels)
    # rows all differ
    assert len({row.tobytes() for row in tokens}) == 8


BATCH_LONG = harness.load_json(harness.HERE, "unproven", "traffic", "batch-long.json")
MISTRAL = {"vocab_size": 32000}


@pytest.mark.parametrize("seed", [0, 9, 2 ** 31 + 77])
def test_request_mix_is_seeded_clipped_and_the_same_work_for_every_seed(seed):
    import itertools

    n = BATCH_LONG["cycle"]
    take = lambda s: list(itertools.islice(
        BACKLOG.requests(BATCH_LONG, MISTRAL, s), 2 * n))
    a, b, other = take(seed), take(seed), take(seed + 1)
    assert all(x[0] == y[0] and np.array_equal(x[1], y[1]) and x[2] == y[2]
               for x, y in zip(a, b))
    lengths = [p.shape[1] for _, p, _ in a]
    outputs = [o for _, _, o in a]
    assert min(lengths) >= 512 and max(lengths) <= 3072
    assert min(outputs) >= 64 and max(outputs) <= 256
    assert abs(np.median(lengths) - 1536) < 64
    # the same multiset of lengths in every cycle and for every seed,
    # in another order
    assert sorted(lengths[:n]) == sorted(lengths[n:])
    assert sorted(lengths[:n]) == sorted(p.shape[1] for _, p, _ in other[:n])
    assert lengths[:n] != [p.shape[1] for _, p, _ in other[:n]]
    assert all(p.dtype == np.int32 and p.min() >= 0 and p.max() < 32000
               for _, p, _ in a)
    # no shared prefix, not even one token by chance
    assert len({int(p[0, 0]) for _, p, _ in a}) == len(a)
