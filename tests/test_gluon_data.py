"""Tests for gluon.data (parity model: tests/python/unittest/test_gluon_data.py)."""

import os

import numpy as np
import pytest

import mxtpu as mx
from mxtpu.gluon.data import (ArrayDataset, Dataset, SimpleDataset,
                              DataLoader, BatchSampler, SequentialSampler,
                              RandomSampler)
from mxtpu.gluon.data.vision import transforms


def test_array_dataset():
    X = np.random.uniform(size=(10, 20))
    Y = np.random.uniform(size=(10,))
    dataset = ArrayDataset(X, Y)
    loader = DataLoader(dataset, 2)
    for i, (x, y) in enumerate(loader):
        assert x.shape == (2, 20)
        assert y.shape == (2,)
        np.testing.assert_allclose(x.asnumpy(), X[i * 2:(i + 1) * 2],
                                   rtol=1e-6)
    dataset = ArrayDataset(X)
    loader = DataLoader(dataset, 2)
    for i, x in enumerate(loader):
        assert x.shape == (2, 20)


def test_samplers():
    assert list(SequentialSampler(5)) == [0, 1, 2, 3, 4]
    assert sorted(RandomSampler(5)) == [0, 1, 2, 3, 4]
    bs = BatchSampler(SequentialSampler(10), 3, "keep")
    assert [len(b) for b in bs] == [3, 3, 3, 1]
    assert len(bs) == 4
    bs = BatchSampler(SequentialSampler(10), 3, "discard")
    assert [len(b) for b in bs] == [3, 3, 3]
    assert len(bs) == 3
    bs = BatchSampler(SequentialSampler(10), 3, "rollover")
    assert [len(b) for b in bs] == [3, 3, 3]
    assert [len(b) for b in bs] == [3, 3, 3]  # 1 rolled + 10 = 11 -> 3 full


def test_dataset_transform():
    ds = SimpleDataset(list(range(8))).transform(lambda x: x * 2)
    assert ds[3] == 6
    ds2 = ArrayDataset(np.arange(6), np.arange(6)).transform_first(
        lambda x: x * 10)
    x, y = ds2[2]
    assert x == 20 and y == 2


def test_dataset_shard_take_filter():
    ds = SimpleDataset(list(range(10)))
    shards = [ds.shard(3, i) for i in range(3)]
    assert sum(len(s) for s in shards) == 10
    assert len(ds.take(4)) == 4
    assert len(ds.filter(lambda x: x % 2 == 0)) == 5


class SlowDataset(Dataset):
    """CPU-bound per-item work; module-level so forkserver/spawn workers
    can pickle it."""

    def __len__(self):
        return 64

    def __getitem__(self, idx):
        a = np.random.RandomState(idx).rand(64, 64)
        for _ in range(5):
            a = a @ a.T
            a /= np.abs(a).max()
        return a.astype("float32"), np.float32(idx % 10)


def _assert_same_batches(got, ref):
    assert len(got) == len(ref)
    for (xa, ya), (xb, yb) in zip(got, ref):
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(ya, yb)


def _array16():
    return ArrayDataset(np.arange(64).astype("float32").reshape(16, 4),
                        np.arange(16))


@pytest.mark.parametrize("make_ds, batch", [(_array16, 4),
                                            (SlowDataset, 8)],
                         ids=["array", "cpu_bound"])
def test_multi_worker(make_ds, batch):
    """Worker processes yield the same batches in the same order as the
    loading thread alone.  No rate is asserted: a wall-clock claim on a
    shared CPU host is not a measurement (ROADMAP W6)."""
    ds = make_ds()
    ref, got = ([(x.asnumpy(), y.asnumpy())
                 for x, y in DataLoader(ds, batch, num_workers=workers)]
                for workers in (0, 2))
    assert len(ref) == len(ds) // batch
    _assert_same_batches(got, ref)


def test_multi_worker_thread_pool():
    ds = ArrayDataset(np.arange(32).astype("float32").reshape(8, 4),
                      np.arange(8))
    loader = DataLoader(ds, 2, num_workers=2, thread_pool=True)
    assert sum(1 for _ in loader) == 4


def test_transforms_totensor_normalize():
    img = (np.random.rand(28, 26, 3) * 255).astype("uint8")
    t = transforms.ToTensor()
    out = t(mx.nd.array(img, dtype="uint8"))
    assert out.shape == (3, 28, 26)
    np.testing.assert_allclose(out.asnumpy(),
                               img.transpose(2, 0, 1) / 255.0, rtol=1e-5)
    norm = transforms.Normalize(mean=(0.5, 0.5, 0.5), std=(0.1, 0.2, 0.3))
    out2 = norm(out)
    expect = (img.transpose(2, 0, 1) / 255.0 -
              np.array([0.5, 0.5, 0.5]).reshape(3, 1, 1)) / \
        np.array([0.1, 0.2, 0.3]).reshape(3, 1, 1)
    np.testing.assert_allclose(out2.asnumpy(), expect, rtol=1e-4)


def test_transforms_geometry():
    img = mx.nd.array((np.random.rand(48, 40, 3) * 255).astype("uint8"),
                      dtype="uint8")
    assert transforms.Resize(20)(img).shape == (20, 20, 3)
    assert transforms.Resize((30, 20))(img).shape == (20, 30, 3)
    assert transforms.CenterCrop(16)(img).shape == (16, 16, 3)
    assert transforms.RandomCrop(16)(img).shape == (16, 16, 3)
    assert transforms.RandomResizedCrop(24)(img).shape == (24, 24, 3)
    assert transforms.RandomFlipLeftRight(1.0)(img).asnumpy().shape == \
        (48, 40, 3)
    np.testing.assert_array_equal(
        transforms.RandomFlipLeftRight(1.0)(img).asnumpy(),
        img.asnumpy()[:, ::-1])


def test_transforms_color():
    img = mx.nd.array((np.random.rand(8, 8, 3) * 255).astype("uint8"),
                      dtype="uint8")
    for t in (transforms.RandomBrightness(0.5), transforms.RandomContrast(0.5),
              transforms.RandomSaturation(0.5), transforms.RandomHue(0.1),
              transforms.RandomColorJitter(0.1, 0.1, 0.1, 0.1),
              transforms.RandomLighting(0.1), transforms.RandomGray(1.0)):
        out = t(img)
        assert out.shape == (8, 8, 3)


def test_transforms_compose_in_loader():
    data = (np.random.rand(10, 16, 16, 3) * 255).astype("uint8")
    label = np.arange(10)
    t = transforms.Compose([transforms.ToTensor(),
                            transforms.Normalize(0.5, 0.5)])
    ds = ArrayDataset(data, label).transform_first(t)
    loader = DataLoader(ds, 5)
    for x, y in loader:
        assert x.shape == (5, 3, 16, 16)


def test_dataloader_shm_transport_and_abandonment():
    """Shared-memory worker batches round-trip; abandoning iteration mid-
    epoch must not leak segments or hang (review findings r3)."""
    import numpy as onp
    from mxtpu.gluon.data.dataloader import _to_shared, _from_shared

    big = onp.random.RandomState(0).rand(300, 1200).astype("float32")
    shipped = _to_shared((big, {"small": onp.ones(3)}))
    assert shipped[0][0] == "__shm__"
    back = _from_shared(shipped)
    onp.testing.assert_array_equal(back[0], big)
    onp.testing.assert_array_equal(back[1]["small"], onp.ones(3))

    # object/structured dtypes skip shm (pickle path) instead of crashing
    obj = onp.empty(300000, dtype=object)
    assert _to_shared(obj) is obj
    rec = onp.zeros(300000, dtype=[("a", "<f4"), ("b", "<i8")])
    shipped = _to_shared(rec)
    back = _from_shared(shipped)
    assert back.dtype == rec.dtype

    # abandonment: break mid-epoch, drop the loader, force GC — returns
    # promptly (the 60s-per-result hang would trip the suite timeout)
    import gc
    import mxtpu as mx
    from mxtpu.gluon.data import DataLoader, ArrayDataset
    ds = ArrayDataset(mx.nd.array(onp.random.rand(64, 8)),
                      mx.nd.array(onp.arange(64)))
    dl = DataLoader(ds, batch_size=8, num_workers=2)
    for i, _ in enumerate(dl):
        break
    del dl
    gc.collect()


def test_dataloader_forkserver_regression():
    """Round-1 regression: forking a JAX-initialized parent deadlocked the
    worker pool.  The fix (forkserver/spawn + sanitized child env,
    dataloader.py) must (a) not deadlock — guarded by SIGALRM here,
    (b) leave the parent env untouched, (c) give bit-identical batches to
    the single-process path, with the runtime demonstrably live first."""
    import os
    import signal

    import jax

    jax.numpy.ones(8).block_until_ready()  # JAX runtime live in parent
    watched = ("JAX_PLATFORMS", "XLA_FLAGS")
    env_before = {k: os.environ.get(k) for k in watched}

    ds = ArrayDataset(np.random.RandomState(0).rand(48, 6).astype("float32"),
                      np.arange(48).astype("float32"))
    old = signal.signal(signal.SIGALRM,
                        lambda *a: (_ for _ in ()).throw(
                            TimeoutError("DataLoader deadlocked")))
    signal.alarm(180)
    try:
        got = [(x.asnumpy(), y.asnumpy())
               for x, y in DataLoader(ds, 8, num_workers=2)]
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)

    assert {k: os.environ.get(k) for k in watched} == env_before
    ref = [(x.asnumpy(), y.asnumpy())
           for x, y in DataLoader(ds, 8, num_workers=0)]
    assert len(ref) == 6
    _assert_same_batches(got, ref)
