"""Op bulking (engine.bulk): lazy eager dispatch with fused, cached
segment compilation.

Covers the PR-3 tentpole contract:
- bulked-vs-sync bit-exactness over an op-sweep slice (ops whose fused
  lowering introduces no FP contraction are asserted BIT-identical;
  mul->add adjacent chains are asserted to ulp tolerance — XLA contracts
  those into FMA inside the fused program, which is strictly MORE
  accurate; docs/engine.md "Numerics"),
- the flush-on-every-sync-point matrix (asnumpy/item/float/print/shape-
  branch/bool/in-place/backward/wait_all/set_sync),
- exception surfacing at the flush site (+ poisoned-handle replay),
- nested and zero-size bulk() contexts, size-exceeded auto-flush,
- autograd interplay: a recorded segment enters the tape as ONE fused
  vjp node, non-differentiable ops stay gradient barriers,
- the eager-replay fallback for jit-hostile segments (never wrong
  answers) and its negative cache,
- the fused multi_sgd trainer routing and its fallbacks,
- segment-cache hit/miss counters and the ambient env opt-in.
"""

import subprocess
import sys

import numpy as onp
import pytest

import mxtpu as mx
from mxtpu import autograd, engine
from mxtpu.base import _OP_REGISTRY, register_op
from mxtpu.gluon import nn
from mxtpu import gluon
from mxtpu.ndarray.ndarray import NDArray, invoke_op


@pytest.fixture(autouse=True)
def _clean_engine_state():
    """Every test starts unbulked and in async mode, and leaves no
    pending segment behind."""
    engine.set_sync(False)
    engine.flush_bulk()
    yield
    engine.flush_bulk()
    engine.set_sync(False)


def _sync_run(fn):
    engine.set_sync(True)
    try:
        return fn()
    finally:
        engine.set_sync(False)


def _bulked_run(fn, size=64):
    with engine.bulk(size):
        return fn()


# ---------------------------------------------------------------- sweep

_R = onp.random.RandomState(7)
_A = _R.rand(5, 6).astype(onp.float32) + 0.5
_B = _R.rand(5, 6).astype(onp.float32) + 0.5
_SQ = _R.rand(4, 4).astype(onp.float32)

# (name, args-builder, kwargs): single-op segments; each fused program is
# one op, whose jit lowering is contraction-free -> BIT-identical to the
# MXTPU_SYNC=1 per-op execution
_SWEEP = [
    ("add", lambda: (mx.nd.array(_A), mx.nd.array(_B)), {}),
    ("subtract", lambda: (mx.nd.array(_A), mx.nd.array(_B)), {}),
    ("multiply", lambda: (mx.nd.array(_A), mx.nd.array(_B)), {}),
    ("divide", lambda: (mx.nd.array(_A), mx.nd.array(_B)), {}),
    ("power", lambda: (mx.nd.array(_A), mx.nd.array(_B)), {}),
    ("maximum", lambda: (mx.nd.array(_A), mx.nd.array(_B)), {}),
    ("minimum", lambda: (mx.nd.array(_A), mx.nd.array(_B)), {}),
    ("relu", lambda: (mx.nd.array(_A - 1.0),), {}),
    ("sigmoid", lambda: (mx.nd.array(_A),), {}),
    ("tanh", lambda: (mx.nd.array(_A),), {}),
    ("exp", lambda: (mx.nd.array(_A),), {}),
    ("log", lambda: (mx.nd.array(_A),), {}),
    ("sqrt", lambda: (mx.nd.array(_A),), {}),
    ("square", lambda: (mx.nd.array(_A),), {}),
    ("abs", lambda: (mx.nd.array(_A - 1.0),), {}),
    ("negative", lambda: (mx.nd.array(_A),), {}),
    ("sum", lambda: (mx.nd.array(_A),), {"axis": 1}),
    ("mean", lambda: (mx.nd.array(_A),), {"axis": 0}),
    ("max", lambda: (mx.nd.array(_A),), {"axis": 1}),
    ("min", lambda: (mx.nd.array(_A),), {}),
    ("prod", lambda: (mx.nd.array(_A),), {"axis": 1}),
    ("argmax", lambda: (mx.nd.array(_A),), {"axis": 1}),
    ("argsort", lambda: (mx.nd.array(_A),), {"axis": 1}),
    ("softmax", lambda: (mx.nd.array(_A),), {"axis": -1}),
    ("log_softmax", lambda: (mx.nd.array(_A),), {"axis": -1}),
    ("dot", lambda: (mx.nd.array(_SQ), mx.nd.array(_SQ)), {}),
    ("transpose", lambda: (mx.nd.array(_A),), {"axes": (1, 0)}),
    ("reshape", lambda: (mx.nd.array(_A),), {"shape": (3, 10)}),
    ("expand_dims", lambda: (mx.nd.array(_A),), {"axis": 1}),
    ("flatten", lambda: (mx.nd.array(_A),), {}),
    ("clip", lambda: (mx.nd.array(_A),), {"a_min": 0.6, "a_max": 1.1}),
    ("tile", lambda: (mx.nd.array(_A),), {"reps": (2, 1)}),
    ("one_hot", lambda: (mx.nd.array(onp.array([0, 2, 1],
                                               onp.float32)),),
     {"depth": 4}),
    ("equal", lambda: (mx.nd.array(_A), mx.nd.array(_A)), {}),
    ("lesser", lambda: (mx.nd.array(_A), mx.nd.array(_B)), {}),
]


@pytest.mark.parametrize("name,builder,kwargs",
                         _SWEEP, ids=[c[0] for c in _SWEEP])
def test_bulk_bit_exact_vs_sync(name, builder, kwargs):
    ref = _sync_run(lambda: invoke_op(name, builder(), dict(kwargs)))
    got = _bulked_run(lambda: invoke_op(name, builder(), dict(kwargs)))
    refs = ref if isinstance(ref, tuple) else (ref,)
    gots = got if isinstance(got, tuple) else (got,)
    for r, g in zip(refs, gots):
        r, g = r.asnumpy(), g.asnumpy()
        assert r.dtype == g.dtype
        assert onp.array_equal(r, g), "op %r diverged bulked" % name


def test_bulk_multi_output_op():
    """Declared-arity multi-output ops return the same tuple shape
    bulked; values agree to ulp (sgd_mom_update's internal mul->add
    chain FMA-contracts under the fused jit)."""
    w, g, m = (mx.nd.array(_R.rand(8).astype(onp.float32))
               for _ in range(3))
    call = lambda: invoke_op(  # noqa: E731
        "sgd_mom_update", (w, g, m, 0.1), {"momentum": 0.9, "wd": 0.0})
    ref = _sync_run(call)
    got = _bulked_run(call)
    assert isinstance(got, tuple) and len(got) == 2
    for r, b in zip(ref, got):
        onp.testing.assert_allclose(r.asnumpy(), b.asnumpy(),
                                    rtol=1e-6, atol=1e-7)


def test_bulk_chain_matches_sync_to_ulp():
    """A 60-op mixed chain: XLA may contract mul->add into FMA inside the
    fused program (strictly more accurate), so the contract here is
    ulp-level agreement — and determinism: two bulked runs (compile miss
    then cache hit) are bit-identical to each other."""
    x0 = mx.nd.array(_A)

    def chain():
        x = x0
        for _ in range(15):
            x = ((x * 1.001 + 0.003).relu() - 0.001)
        return x.asnumpy()

    ref = _sync_run(chain)
    b1 = _bulked_run(chain, size=128)
    b2 = _bulked_run(chain, size=128)
    onp.testing.assert_allclose(ref, b1, rtol=1e-5, atol=1e-7)
    assert onp.array_equal(b1, b2), "bulked runs must be deterministic"


def test_bulk_seeded_rng_op_bit_exact():
    """RNG keys are consumed at record time in program order, so a
    seeded dropout is bit-identical bulked vs sync."""
    x = mx.nd.array(onp.ones((64, 64), onp.float32))

    def run():
        mx.random.seed(11)
        return invoke_op("Dropout", (x,),
                         {"p": 0.5, "mode": "always"}).asnumpy()

    assert onp.array_equal(_sync_run(run), _bulked_run(run))


def test_fallthrough_rng_op_does_not_burn_a_key():
    """An RNG op that falls through (here: out= requested) must consume
    exactly one key, like per-op dispatch — a key drawn during the
    abandoned record attempt would shift every later seeded draw."""
    x = mx.nd.array(onp.ones((32, 32), onp.float32))
    dst = mx.nd.array(onp.zeros((32, 32), onp.float32))

    def run():
        mx.random.seed(23)
        invoke_op("Dropout", (x,),
                  {"p": 0.5, "mode": "always", "out": dst})
        first = dst.asnumpy().copy()
        second = invoke_op("Dropout", (x,),
                           {"p": 0.5, "mode": "always"}).asnumpy()
        return first, second

    ref = _sync_run(run)
    got = _bulked_run(run)
    for r, g in zip(ref, got):
        assert onp.array_equal(r, g)


# ---------------------------------------------------- sync-point matrix

def test_flush_matrix_asnumpy_item_float_print_bool():
    x = mx.nd.array(onp.array([2.0], onp.float32))
    with engine.bulk(64):
        y = x * 3.0
        assert y._lazy_ is not None
        assert y.asnumpy()[0] == 6.0        # trace-ok: the test subject
        z = x + 1.0
        assert z.item() == 3.0              # trace-ok: the test subject
        w = x - 1.0
        assert float(w) == 1.0              # trace-ok: the test subject
        p = x * 2.0
        assert "4." in repr(p)              # print/repr
        assert p._lazy_ is None
        b = x > 1.0
        assert bool(b)                      # trace-ok: the test subject
        i = x + 2.0
        assert int(i) == 4                  # trace-ok: the test subject


def test_flush_matrix_shape_branch_and_numpy_conversion():
    x = mx.nd.array(_A)
    with engine.bulk(64):
        y = invoke_op("transpose", (x,), {"axes": (1, 0)})
        assert y._lazy_ is not None
        # shape-dependent python control flow forces the flush
        if y.shape[0] == 6:
            assert y._lazy_ is None
        z = x * 2.0
        arr = onp.asarray(z)  # __array__ protocol
        assert z._lazy_ is None and arr.shape == (5, 6)


def test_flush_matrix_inplace_and_setitem():
    x = mx.nd.array(onp.zeros(4, onp.float32))
    with engine.bulk(64):
        y = x + 1.0
        y += 1.0                   # in-place arithmetic reads _data
        assert y._lazy_ is None
        assert onp.array_equal(y.asnumpy(), [2, 2, 2, 2])  # trace-ok
        z = x + 3.0
        z[1] = 9.0                 # __setitem__ reads/rebinds the buffer
        assert z._lazy_ is None
        assert z.asnumpy()[1] == 9.0                       # trace-ok


def test_wait_all_flushes_pending_segment():
    x = mx.nd.array(onp.ones(3, onp.float32))
    with engine.bulk(64):
        y = x * 7.0
        assert y._lazy_ is not None
        engine.wait_all()          # trace-ok: the test subject
        assert y._lazy_ is None
    assert onp.array_equal(y.asnumpy(), [7, 7, 7])


def test_set_sync_mid_bulk_flushes_then_disables():
    x = mx.nd.array(onp.ones(3, onp.float32))
    with engine.bulk(64):
        y = x * 2.0
        assert y._lazy_ is not None
        engine.set_sync(True)
        assert y._lazy_ is None    # flushed, not stale
        z = x * 4.0
        assert z._lazy_ is None    # bulking disabled under sync
    engine.set_sync(False)
    assert onp.array_equal(z.asnumpy(), [4, 4, 4])


def test_backward_flushes_and_records_fused_node():
    a = mx.nd.array(onp.full((3, 3), 2.0, onp.float32))
    a.attach_grad()
    engine.reset_bulk_stats()
    with autograd.record():
        with engine.bulk(64):
            z = ((a * a) + a).sum()
            assert z._lazy_ is not None
            z.backward()           # sync point: flush + reverse pass
    st = engine.bulk_stats()
    assert st["eager_replays"] == 0, "fused vjp path must compile"
    # d/da (a^2 + a) = 2a + 1 = 5
    assert onp.array_equal(a.grad.asnumpy(), onp.full((3, 3), 5.0))


# ------------------------------------------------------------ autograd

def test_recorded_bulk_grads_match_per_op():
    def grads(bulked):
        a = mx.nd.array(_A)
        b = mx.nd.array(_B)
        a.attach_grad()
        b.attach_grad()
        with autograd.record():
            if bulked:
                with engine.bulk(64):
                    loss = ((a * b).sigmoid() + a).sum()
            else:
                loss = ((a * b).sigmoid() + a).sum()
        loss.backward()
        return a.grad.asnumpy(), b.grad.asnumpy()

    (ga, gb), (ga_b, gb_b) = grads(False), grads(True)
    onp.testing.assert_allclose(ga, ga_b, rtol=1e-6, atol=1e-7)
    onp.testing.assert_allclose(gb, gb_b, rtol=1e-6, atol=1e-7)


def test_bulk_nondiff_op_stays_gradient_barrier():
    def run(bulked):
        c = mx.nd.array(onp.array([[1., 5.], [3., 2.]], onp.float32))
        c.attach_grad()
        with autograd.record():
            if bulked:
                with engine.bulk(64):
                    idx = c.argmax(axis=1)
                    y = (c * c).sum() + idx.astype("float32").sum()
            else:
                idx = c.argmax(axis=1)
                y = (c * c).sum() + idx.astype("float32").sum()
        y.backward()
        return c.grad.asnumpy()

    assert onp.array_equal(run(False), run(True))


def test_record_boundary_flushes_segment():
    x = mx.nd.array(onp.ones(3, onp.float32))
    with engine.bulk(64):
        y = x * 2.0
        assert y._lazy_ is not None
        with autograd.record():      # recording transition = sync point
            assert y._lazy_ is None
            z = x * 3.0
            assert z._lazy_ is not None
        assert z._lazy_ is None      # exiting record flushed again
    assert onp.array_equal(z.asnumpy(), [3, 3, 3])


# ----------------------------------------------- errors / edge contexts

def test_exception_surfaces_at_flush_site_and_poisons_handles():
    bad = mx.nd.array(onp.ones((2, 3), onp.float32))
    with engine.bulk(64):
        c = mx.nd.dot(bad, bad)          # invalid shapes, deferred
        d = c + 1.0
        with pytest.raises(Exception):
            c.asnumpy()                  # trace-ok: the test subject
        # the segment is poisoned: dependent handles re-raise, they do
        # not hang or return garbage
        with pytest.raises(Exception):
            d.asnumpy()                  # trace-ok: the test subject
    # a fresh segment afterwards works
    with engine.bulk(64):
        ok = (bad + 1.0).asnumpy()       # trace-ok: the test subject
    assert onp.array_equal(ok, onp.full((2, 3), 2.0))


def test_exception_surfaces_at_context_exit_when_unread():
    bad = mx.nd.array(onp.ones((2, 3), onp.float32))
    with pytest.raises(Exception):
        with engine.bulk(64):
            mx.nd.dot(bad, bad)          # nobody reads it: exit flushes


def test_nested_and_zero_size_bulk():
    x = mx.nd.array(onp.ones(3, onp.float32))
    with engine.bulk(8):
        n1 = x + 1.0
        with engine.bulk(0):             # zero size: eager inside
            n2 = x + 2.0
            assert n2._lazy_ is None
        assert n1._lazy_ is None         # nested entry flushed outer
        n3 = x + 3.0
        assert n3._lazy_ is not None
        with engine.bulk(4):             # nested non-zero
            n4 = x + 4.0
            assert n4._lazy_ is not None
        assert n4._lazy_ is None
    assert n3._lazy_ is None
    for n, v in ((n1, 2), (n2, 3), (n3, 4), (n4, 5)):
        assert onp.array_equal(n.asnumpy(), [v] * 3)


def test_bulk_size_exceeded_autoflushes():
    x = mx.nd.array(onp.ones(3, onp.float32))
    with engine.bulk(3):
        a = x + 1.0
        b = a * 2.0
        c = b - 1.0                      # 3rd op: segment flushes
        assert c._lazy_ is None
        d = c / 3.0                      # lands in a NEW segment
        assert d._lazy_ is not None
    assert onp.array_equal(d.asnumpy(), [1, 1, 1])


def test_dead_intermediate_handles_are_not_materialized():
    x = mx.nd.array(onp.ones(3, onp.float32))
    with engine.bulk(64):
        y = ((x + 1.0) * 2.0 - 1.0)      # intermediates die immediately
        out = y.asnumpy()                # trace-ok: the test subject
    assert onp.array_equal(out, [3, 3, 3])


def test_eager_replay_for_jit_hostile_ops_and_negative_cache():
    import jax.numpy as jnp

    if "_test_bulk_host_round" not in _OP_REGISTRY:
        @register_op("_test_bulk_host_round", differentiable=False)
        def _host_round(x):
            # eager-valid, but concretizes under jit: forces the
            # replay fallback
            return jnp.asarray(onp.asarray(x) * 2.0)

    try:
        x = mx.nd.array(onp.arange(4, dtype=onp.float32))
        engine.reset_bulk_stats()
        outs = []
        for _ in range(2):
            with engine.bulk(16):
                y = invoke_op("_test_bulk_host_round", (x + 1.0,), {})
                z = y - 0.5
                outs.append(z.asnumpy())  # trace-ok: the test subject
        assert onp.array_equal(
            outs[0], onp.arange(4, dtype=onp.float32) * 2 + 1.5)
        assert onp.array_equal(outs[0], outs[1])
        st = engine.bulk_stats()
        assert st["eager_replays"] == 2
        # the second, identical segment hit the negative cache (no
        # second compile attempt)
        assert st["cache_hits"] == 1 and st["cache_misses"] == 0
    finally:
        _OP_REGISTRY.pop("_test_bulk_host_round", None)


def test_bulk_cache_counters():
    x = mx.nd.array(onp.ones(4, onp.float32))
    engine.reset_bulk_stats()

    def seg():
        with engine.bulk(16):
            y = (x * 2.0 + 1.0)
            return y.asnumpy()           # trace-ok: the test subject

    seg()
    st = engine.bulk_stats()
    assert st == {**st, "flushes": 1, "cache_misses": 1, "cache_hits": 0,
                  "bulked_ops": 2}
    seg()
    st = engine.bulk_stats()
    assert st["cache_hits"] == 1 and st["cache_misses"] == 1
    assert st["flushes"] == 2 and st["bulked_ops"] == 4
    assert st["cache_size"] >= 1


def test_out_kwarg_falls_through():
    x = mx.nd.array(onp.ones(3, onp.float32))
    dst = mx.nd.array(onp.zeros(3, onp.float32))
    engine.reset_bulk_stats()
    with engine.bulk(16):
        invoke_op("add", (x, x), {"out": dst})
        assert dst._lazy_ is None        # dispatched per-op, not bulked
    assert engine.bulk_stats()["fallthroughs"] >= 1
    assert onp.array_equal(dst.asnumpy(), [2, 2, 2])


def test_ambient_env_opt_in():
    code = (
        "import numpy as onp, mxtpu as mx\n"
        "from mxtpu import engine\n"
        "x = mx.nd.array(onp.ones(3, onp.float32))\n"
        "y = x + 1.0\n"
        "assert y._lazy_ is not None, 'ambient bulking should be on'\n"
        "assert onp.array_equal(y.asnumpy(), [2., 2., 2.])\n"
        "assert engine.bulk_stats()['bulked_ops'] >= 1\n"
    )
    import os
    env = dict(os.environ, MXTPU_ENGINE_BULK_SIZE="32",
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]


# ------------------------------------------------------- trainer fusion

_X = mx.nd.array(onp.random.RandomState(0).rand(4, 10).astype(onp.float32))
_Y = mx.nd.array(onp.random.RandomState(1).rand(4, 2).astype(onp.float32))


def _make_net(seed=7, dtype=None):
    mx.random.seed(seed)
    net = nn.Sequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(2))
    net.initialize()
    if dtype:
        net(_X.astype(dtype) if dtype else _X)  # materialize, then cast
        net.cast(dtype)
    return net


def _train(net, optname, steps=3, bulk_size=0, X=None, **okw):
    X = _X if X is None else X
    loss_fn = gluon.loss.L2Loss()
    tr = gluon.Trainer(net.collect_params(), optname, okw)
    for _ in range(steps):
        with autograd.record():
            loss = loss_fn(net(X), _Y)
        loss.backward()
        if bulk_size:
            with engine.bulk(bulk_size):
                tr.step(4)
        else:
            tr.step(4)
    return [p.data().asnumpy() for p in net.collect_params().values()]


@pytest.mark.parametrize("okw", [
    {"learning_rate": 0.05, "wd": 0.01},
    {"learning_rate": 0.05, "momentum": 0.9},
    {"learning_rate": 0.05, "momentum": 0.9, "wd": 0.01},
], ids=["plain", "momentum", "momentum+wd"])
def test_trainer_fused_sgd_matches_per_param(okw, monkeypatch):
    from mxtpu.gluon.trainer import Trainer

    r_fused = _train(_make_net(), "sgd", **okw)
    monkeypatch.setattr(Trainer, "_fusable_sgd",
                        lambda self, local: False)
    r_plain = _train(_make_net(), "sgd", **okw)
    for a, b in zip(r_fused, r_plain):
        # ulp-level: the fused multi-tensor op runs eagerly while the
        # per-param rule is jitted; XLA FMA contraction differs
        onp.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_trainer_fused_sgd_bulked_step(monkeypatch):
    r_unbulked = _train(_make_net(), "sgd", learning_rate=0.05)
    engine.reset_bulk_stats()
    r_bulked = _train(_make_net(), "sgd", bulk_size=64,
                      learning_rate=0.05)
    st = engine.bulk_stats()
    assert st["bulked_ops"] >= 3          # one fused op per step
    assert st["eager_replays"] == 0
    for a, b in zip(r_bulked, r_unbulked):
        onp.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_trainer_fallback_subclass_and_non_sgd():
    """NAG (an SGD subclass with a different rule) and Adam must take
    the per-param path — and still train."""
    from mxtpu.gluon.trainer import Trainer

    called = {"fused": 0}
    orig = Trainer._fused_sgd_update

    def spy(self, local):
        r = orig(self, local)
        called["fused"] += bool(r)
        return r

    Trainer._fused_sgd_update = spy
    try:
        before = [p.copy() for p in
                  _train(_make_net(), "nag", steps=1,
                         learning_rate=0.05, momentum=0.9)]
        assert called["fused"] == 0
        _train(_make_net(), "adam", steps=1, learning_rate=0.01)
        assert called["fused"] == 0
        assert before  # parameters did update (no exception path)
    finally:
        Trainer._fused_sgd_update = orig


def test_trainer_fused_respects_lr_mult():
    def run(fused):
        from mxtpu.gluon.trainer import Trainer
        net = _make_net()
        params = net.collect_params()
        tr = gluon.Trainer(params, "sgd", {"learning_rate": 0.05})
        # per-index lr multipliers exercise the per-param lrs vector
        tr._optimizer.set_lr_mult({0: 0.5, 1: 2.0})
        if not fused:
            tr._fusable_sgd = lambda local: False
        loss_fn = gluon.loss.L2Loss()
        with autograd.record():
            loss = loss_fn(net(_X), _Y)
        loss.backward()
        tr.step(4)
        return [p.data().asnumpy() for p in params.values()]

    for a, b in zip(run(True), run(False)):
        onp.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_rebound_handle_not_overwritten_by_flush():
    """A lazy handle rebound to a NEW buffer before the flush (copyto /
    out= / _rebind) must keep the new buffer — the flush must not
    resurrect the stale segment value."""
    a = mx.nd.array(onp.array([1., 2., 3.], onp.float32))
    b = mx.nd.array(onp.array([9., 9., 9.], onp.float32))
    with engine.bulk(8):
        y = a * 2.0
        b.copyto(y)              # rebinds y to b's buffer
    assert onp.array_equal(y.asnumpy(), [9., 9., 9.])


def test_replay_uses_record_time_input_values():
    """The eager-replay fallback computes with the record-time input
    snapshot, even if an input was mutated in place before the flush —
    identical to what the compiled path (and per-op dispatch) sees."""
    import jax.numpy as jnp

    if "_test_bulk_host_round2" not in _OP_REGISTRY:
        @register_op("_test_bulk_host_round2", differentiable=False)
        def _host_round2(x):
            return jnp.asarray(onp.asarray(x) + 0.0)

    try:
        x = mx.nd.array(onp.array([1., 2.], onp.float32))
        with engine.bulk(8):
            q = invoke_op("_test_bulk_host_round2", (x * 2.0,), {})
            x += 100.0           # in-place on a concrete input
            out = q.asnumpy()    # trace-ok: the test subject
        assert onp.array_equal(out, [2., 4.]), out
    finally:
        _OP_REGISTRY.pop("_test_bulk_host_round2", None)


def test_explicit_none_out_ctx_still_bulk():
    """out=None / ctx=None are dispatch directives; they must be
    stripped, not passed into the fused trace as op kwargs (mx.nd.empty
    & friends pass ctx=None unconditionally)."""
    engine.reset_bulk_stats()
    with engine.bulk(8):
        y = invoke_op("zeros", (), {"shape": (3,), "dtype": "float32",
                                    "ctx": None})
        z = invoke_op("add", (y, y), {"out": None})
        out = z.asnumpy()        # trace-ok: the test subject
    assert onp.array_equal(out, [0., 0., 0.])
    st = engine.bulk_stats()
    assert st["eager_replays"] == 0 and st["cache_misses"] == 1, st


def test_split_like_kwarg_arity_ops_bulk_correctly():
    """Ops whose output arity depends on a kwarg (split/split_v2/topk)
    declare callable num_outputs, so bulked calls return the same tuple
    shape as eager ones."""
    x = mx.nd.array(_A)  # (5, 6)

    def run():
        a, b = invoke_op("split", (x,), {"num_outputs": 2, "axis": 1})
        v, i = invoke_op("topk", (x,), {"axis": 1, "k": 2,
                                        "ret_typ": "both"})
        return a.asnumpy(), b.asnumpy(), v.asnumpy(), i.asnumpy()

    for r, g in zip(_sync_run(run), _bulked_run(run)):
        assert onp.array_equal(r, g)


def test_aliased_tape_inputs_get_distinct_grads():
    """Two NDArrays sharing one buffer are distinct autograd leaves;
    the segment must not collapse them into one tape input."""
    def run(bulked):
        x = mx.nd.array(onp.ones(3, onp.float32))
        y = NDArray(x.data)  # same buffer, different leaf
        autograd.mark_variables(
            [x, y], [mx.nd.array(onp.zeros(3, onp.float32)),
                     mx.nd.array(onp.zeros(3, onp.float32))])
        with autograd.record():
            if bulked:
                with engine.bulk(8):
                    c = x * 2.0 + y * 3.0
            else:
                c = x * 2.0 + y * 3.0
        c.backward()
        return x.grad.asnumpy(), y.grad.asnumpy()

    ref, got = run(False), run(True)
    for r, g in zip(ref, got):
        assert onp.array_equal(r, g), (ref, got)
    assert onp.array_equal(ref[0], [2., 2., 2.])
    assert onp.array_equal(ref[1], [3., 3., 3.])


def test_nondiff_only_tape_input_keeps_its_grad():
    """An on-tape input consumed ONLY by non-differentiable ops inside a
    recorded segment is never a vjp primal — per-op dispatch would not
    record it, so backward must not overwrite its .grad with zeros."""
    def run(bulked):
        x = mx.nd.array(onp.ones(3, onp.float32))
        z = mx.nd.array(onp.ones(3, onp.float32))
        x.attach_grad()
        z.attach_grad()
        z._grad = mx.nd.array(onp.full(3, 3.0, onp.float32))  # prior grad
        with autograd.record():
            if bulked:
                with engine.bulk(8):
                    y = (x * 2.0).sum()
                    invoke_op("argmax", (z,), {"axis": 0})
            else:
                y = (x * 2.0).sum()
                invoke_op("argmax", (z,), {"axis": 0})
        y.backward()
        return x.grad.asnumpy(), z.grad.asnumpy()

    ref, got = run(False), run(True)
    for r, g in zip(ref, got):
        assert onp.array_equal(r, g), (ref, got)
    assert onp.array_equal(ref[1], [3., 3., 3.])  # untouched


def test_aborted_record_rolls_back_inputs():
    """A fallthrough mid-record (unfreezable numpy positional) must not
    leave orphan inputs in the segment: grads and the cache signature
    stay identical to a segment that never saw the aborted op."""
    def run(bulked):
        z = mx.nd.array(onp.ones(3, onp.float32))
        z.attach_grad()
        z._grad = mx.nd.array(onp.full(3, 3.0, onp.float32))
        x = mx.nd.array(onp.ones(3, onp.float32))
        x.attach_grad()
        with autograd.record():
            if bulked:
                with engine.bulk(8):
                    y = (x * 2.0).sum()
                    # numpy positional arg: unfreezable -> fallthrough,
                    # but z was already appended as a segment input
                    invoke_op("broadcast_add",
                              (z, onp.ones(3, onp.float32)), {})
            else:
                y = (x * 2.0).sum()
                invoke_op("broadcast_add",
                          (z, onp.ones(3, onp.float32)), {})
        y.backward()
        return x.grad.asnumpy(), z.grad.asnumpy()

    ref, got = run(False), run(True)
    for r, g in zip(ref, got):
        assert onp.array_equal(r, g), (ref, got)


def test_static_scalar_type_distinguishes_cache_entries():
    """2 == 2.0 == True in python; the segment cache must NOT collide
    segments differing only in a static scalar's type (they compile to
    different result dtypes)."""
    xi = mx.nd.array(onp.array([1, 2, 3], onp.int32))
    with engine.bulk(4):
        a = (xi * 2).asnumpy()       # trace-ok: the test subject
    with engine.bulk(4):
        b = (xi * 2.0).asnumpy()     # trace-ok: the test subject
    with engine.bulk(4):
        c = (xi * True).asnumpy()    # trace-ok: the test subject
    engine.set_sync(True)
    ra = (xi * 2).asnumpy()
    rb = (xi * 2.0).asnumpy()
    rc = (xi * True).asnumpy()
    engine.set_sync(False)
    for got, ref in ((a, ra), (b, rb), (c, rc)):
        assert got.dtype == ref.dtype, (got.dtype, ref.dtype)
        assert onp.array_equal(got, ref)


def test_random_ops_never_replay_frozen_keys():
    """random_* ops draw their key INSIDE the impl, so bulking them
    would bake the key into the cached program and replay identical
    'randomness' on every cache hit — they are bulkable=False, and the
    seeded stream matches per-op dispatch exactly."""
    from mxtpu.base import get_op
    for op in ("random_uniform", "random_normal", "shuffle",
               "_sample_multinomial"):
        assert get_op(op).bulkable is False, op

    def draws(bulked):
        mx.random.seed(9)
        out = []
        for _ in range(2):
            with engine.bulk(16 if bulked else 0):
                out.append(invoke_op("random_uniform", (),
                                     {"shape": (4,)}).asnumpy())
        return out

    per_op, bulked = draws(False), draws(True)
    assert not onp.array_equal(bulked[0], bulked[1]), "draws frozen"
    for r, g in zip(per_op, bulked):
        assert onp.array_equal(r, g)


def test_rebind_from_transfers_laziness():
    x = mx.nd.array(onp.ones(3, onp.float32))
    dst = mx.nd.array(onp.zeros(3, onp.float32))
    with engine.bulk(16):
        y = x * 5.0
        dst._rebind_from(y)
        assert dst._lazy_ is not None     # no flush on transfer
    assert onp.array_equal(dst.asnumpy(), [5, 5, 5])
    assert onp.array_equal(y.asnumpy(), [5, 5, 5])
