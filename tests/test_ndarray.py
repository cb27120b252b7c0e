"""NDArray tests (parity model: tests/python/unittest/test_ndarray.py)."""

import jax
import numpy as np
import pytest

import mxtpu as mx
from mxtpu import nd


def test_creation():
    assert nd.zeros((2, 3)).shape == (2, 3)
    assert nd.ones((4,)).asnumpy().sum() == 4
    assert nd.full((2, 2), 7.0).asnumpy()[0, 0] == 7
    a = nd.array([[1, 2], [3, 4]])
    assert a.dtype == np.float32  # MXNet default dtype
    assert nd.array(np.arange(6, dtype=np.int32)).dtype == np.int32
    assert nd.arange(5).shape == (5,)
    assert nd.eye(3).asnumpy()[1, 1] == 1


def test_arithmetic():
    a = nd.array([1.0, 2.0, 3.0])
    b = nd.array([4.0, 5.0, 6.0])
    np.testing.assert_allclose((a + b).asnumpy(), [5, 7, 9])
    np.testing.assert_allclose((b - a).asnumpy(), [3, 3, 3])
    np.testing.assert_allclose((a * b).asnumpy(), [4, 10, 18])
    np.testing.assert_allclose((b / a).asnumpy(), [4, 2.5, 2])
    np.testing.assert_allclose((a ** 2).asnumpy(), [1, 4, 9])
    np.testing.assert_allclose((2 + a).asnumpy(), [3, 4, 5])
    np.testing.assert_allclose((-a).asnumpy(), [-1, -2, -3])
    np.testing.assert_allclose(abs(nd.array([-1.0, 2.0])).asnumpy(), [1, 2])


def test_inplace():
    a = nd.ones((3,))
    a += 2
    np.testing.assert_allclose(a.asnumpy(), [3, 3, 3])
    a *= 2
    np.testing.assert_allclose(a.asnumpy(), [6, 6, 6])
    a[1] = 0
    np.testing.assert_allclose(a.asnumpy(), [6, 0, 6])
    a[:] = 1.5
    np.testing.assert_allclose(a.asnumpy(), [1.5, 1.5, 1.5])


def test_indexing():
    a = nd.array(np.arange(12).reshape(3, 4))
    assert a[1].shape == (4,)
    assert a[1, 2].asscalar() == 6
    assert a[0:2].shape == (2, 4)
    assert a[:, 1:3].shape == (3, 2)
    idx = nd.array([0, 2], dtype="int32")
    assert a[idx].shape == (2, 4)
    # boolean-style via where
    m = a > 5
    assert m.asnumpy().sum() == 6


def test_reshape_transpose():
    a = nd.array(np.arange(24).reshape(2, 3, 4))
    assert a.reshape(6, 4).shape == (6, 4)
    assert a.reshape((-1, 4)).shape == (6, 4)
    assert a.reshape((0, -1)).shape == (2, 12)  # MXNet code 0 = keep
    assert a.T.shape == (4, 3, 2)
    assert a.transpose((0, 2, 1)).shape == (2, 4, 3)
    assert a.swapaxes(0, 1).shape == (3, 2, 4)
    assert a.flatten().shape == (2, 12)
    assert nd.expand_dims(a, axis=0).shape == (1, 2, 3, 4)
    assert nd.squeeze(nd.ones((1, 3, 1))).shape == (3,)


def test_reduce():
    a = nd.array(np.arange(6, dtype=np.float32).reshape(2, 3))
    assert a.sum().asscalar() == 15
    np.testing.assert_allclose(a.sum(axis=0).asnumpy(), [3, 5, 7])
    np.testing.assert_allclose(a.mean(axis=1).asnumpy(), [1, 4])
    assert a.max().asscalar() == 5
    assert a.min().asscalar() == 0
    assert a.argmax(axis=1).asnumpy().tolist() == [2, 2]
    assert float(a.norm().asscalar()) == pytest.approx(np.sqrt(55), rel=1e-5)


def test_dot():
    a = nd.array(np.random.rand(3, 4).astype(np.float32))
    b = nd.array(np.random.rand(4, 5).astype(np.float32))
    np.testing.assert_allclose(
        nd.dot(a, b).asnumpy(), a.asnumpy() @ b.asnumpy(), rtol=1e-5)
    # transpose flags
    np.testing.assert_allclose(
        nd.dot(a, b.T, transpose_b=True).asnumpy().shape, (3, 5))
    c = nd.array(np.random.rand(2, 3, 4).astype(np.float32))
    d = nd.array(np.random.rand(2, 4, 5).astype(np.float32))
    np.testing.assert_allclose(
        nd.batch_dot(c, d).asnumpy(), c.asnumpy() @ d.asnumpy(), rtol=1e-5)


def test_concat_split_stack():
    a, b = nd.ones((2, 3)), nd.zeros((2, 3))
    assert nd.concat(a, b, dim=0).shape == (4, 3)
    assert nd.concat(a, b, dim=1).shape == (2, 6)
    assert nd.stack(a, b, axis=0).shape == (2, 2, 3)
    parts = nd.split(nd.ones((4, 6)), num_outputs=2, axis=1)
    assert len(parts) == 2 and parts[0].shape == (4, 3)


def test_broadcast():
    a = nd.ones((1, 3))
    assert nd.broadcast_to(a, (4, 3)).shape == (4, 3)
    assert nd.broadcast_add(nd.ones((2, 1)), nd.ones((1, 3))).shape == (2, 3)
    assert nd.broadcast_like(a, nd.zeros((5, 3))).shape == (5, 3)


def test_take_pick_gather():
    a = nd.array(np.arange(12, dtype=np.float32).reshape(3, 4))
    t = nd.take(a, nd.array([0, 2], dtype="int32"))
    assert t.shape == (2, 4)
    p = nd.pick(a, nd.array([0, 1, 2], dtype="int32"), axis=1)
    np.testing.assert_allclose(p.asnumpy(), [0, 5, 10])
    oh = nd.one_hot(nd.array([0, 2], dtype="int32"), depth=3)
    np.testing.assert_allclose(oh.asnumpy(), [[1, 0, 0], [0, 0, 1]])


def test_elementwise_math():
    a = nd.array([1.0, 4.0, 9.0])
    np.testing.assert_allclose(nd.sqrt(a).asnumpy(), [1, 2, 3], rtol=1e-6)
    np.testing.assert_allclose(
        nd.log(nd.exp(nd.array([1.0]))).asnumpy(), [1], rtol=1e-4)
    np.testing.assert_allclose(
        nd.clip(nd.array([-1.0, 0.5, 2.0]), 0, 1).asnumpy(), [0, 0.5, 1])
    np.testing.assert_allclose(
        nd.sigmoid(nd.zeros((2,))).asnumpy(), [0.5, 0.5])
    np.testing.assert_allclose(nd.relu(nd.array([-1.0, 2.0])).asnumpy(), [0, 2])


def test_sort_topk():
    a = nd.array([[3.0, 1.0, 2.0]])
    np.testing.assert_allclose(nd.sort(a).asnumpy(), [[1, 2, 3]])
    np.testing.assert_allclose(
        nd.topk(a, k=2, ret_typ="value").asnumpy(), [[3, 2]])
    idx = nd.topk(a, k=1)
    assert idx.asnumpy()[0, 0] == 0


def test_cast_copy_context():
    a = nd.ones((2, 2))
    b = a.astype("float16")
    assert b.dtype == np.float16
    c = a.copy()
    c += 1
    assert a.asnumpy()[0, 0] == 1  # copy is deep
    d = a.as_in_context(mx.cpu())
    assert d.context.device_type == "cpu"
    assert mx.cpu() == mx.cpu() and mx.cpu() != mx.tpu()


def test_tpu_context_without_the_device_raises(monkeypatch):
    """An accelerator that was asked for and is not there is a typed
    error (the reference raises for mx.gpu(i) without that GPU) — never
    a silent CPU array, never chip 3 aliased onto the last chip."""
    from mxtpu import context

    # this host has no accelerator at all
    for ctx in (mx.tpu(), mx.gpu(0)):
        with pytest.raises(context.DeviceNotFoundError,
                           match=r"tpu\(0\) was asked for.* 0 accel"):
            nd.ones((2, 2), ctx=ctx)
    # a host with two chips has no chip 99 (and no chip -1)
    chips = jax.devices("cpu")[:2]
    monkeypatch.setattr(context, "_accel_cache", chips)
    assert mx.tpu(1).to_jax_device() is chips[1]
    for bad in (99, 2, -1):
        with pytest.raises(context.DeviceNotFoundError,
                           match=r"tpu\(%d\) was asked for.* 2 accel"
                           % bad):
            mx.tpu(bad).to_jax_device()
    # the lazy default was asked for nothing: it may pick the CPU
    monkeypatch.setattr(context, "_accel_cache", [])
    assert nd.ones((1,)).context.device_type == "cpu"


def test_where_comparison():
    a = nd.array([1.0, 5.0])
    b = nd.array([2.0, 2.0])
    np.testing.assert_allclose((a > b).asnumpy(), [0, 1])
    np.testing.assert_allclose((a <= b).asnumpy(), [1, 0])
    w = nd.where(a > b, a, b)
    np.testing.assert_allclose(w.asnumpy(), [2, 5])


def test_save_load_roundtrip(tmp_path):
    f = str(tmp_path / "x.params")
    data = {"w": nd.random.normal(shape=(3, 4)),
            "b": nd.arange(5, dtype="int32")}
    nd.save(f, data)
    back = nd.load(f)
    assert set(back) == {"w", "b"}
    np.testing.assert_allclose(back["w"].asnumpy(), data["w"].asnumpy())
    assert back["b"].dtype == np.int32
    nd.save(f, [nd.ones((2,))])
    lst = nd.load(f)
    assert isinstance(lst, list) and lst[0].shape == (2,)


def test_random_reproducible():
    mx.random.seed(42)
    a = nd.random.uniform(shape=(5,)).asnumpy()
    mx.random.seed(42)
    b = nd.random.uniform(shape=(5,)).asnumpy()
    np.testing.assert_allclose(a, b)
    c = nd.random.normal(loc=2.0, scale=0.1, shape=(1000,)).asnumpy()
    assert abs(c.mean() - 2.0) < 0.05


def test_wait_sync_mode():
    a = nd.ones((8, 8))
    (a * 2).wait_to_read()
    nd.waitall()
    mx.engine.set_sync(True)
    try:
        b = a @ a.T
        assert b.shape == (8, 8)
    finally:
        mx.engine.set_sync(False)


def test_sequence_ops():
    data = nd.array(np.arange(12, dtype=np.float32).reshape(3, 2, 2))  # (T,B,*)
    length = nd.array([2, 3], dtype="int32")
    masked = nd.SequenceMask(data, sequence_length=length,
                             use_sequence_length=True, value=-1.0)
    out = masked.asnumpy()
    assert (out[2, 0] == -1).all() and (out[2, 1] != -1).all()
