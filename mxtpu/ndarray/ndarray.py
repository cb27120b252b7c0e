"""NDArray: the imperative tensor (parity: src/ndarray/ndarray.cc +
python/mxnet/ndarray/ndarray.py).

Reference design: NDArray::Chunk = engine variable + Storage handle;
mutation goes through the dependency engine, reads block via WaitToRead.
TPU design: an NDArray is a mutable *slot* holding an immutable jax.Array.
"Mutation" (+=, [:]=, set_data) rebinds the slot to a new functional value —
old buffers stay valid for any recorded autograd residuals, which is exactly
the guarantee the reference's VersionedVarBlock write-serialisation provides,
delivered here for free by value semantics.  Async execution is PJRT's
native dispatch; ``wait_to_read`` = block_until_ready.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as onp

from .. import autograd, engine
from ..base import MXTPUError, get_op
from ..context import Context, current_context

__all__ = ["NDArray", "invoke_op", "array", "waitall"]


_PY_SCALARS = (int, float, bool)


def _place(arr, ctx: Optional[Context]):
    if ctx is None:
        return arr
    dev = ctx.to_jax_device()
    if dev is None:
        return arr
    return jax.device_put(arr, dev)


class NDArray:
    """Imperative tensor wrapping a jax.Array (or tracer, under hybridize).

    Under ``engine.bulk`` an NDArray can be *lazy*: ``_lazy_`` points at
    one output of a pending bulk segment and ``_data_`` is None until the
    segment flushes.  Every read of ``_data`` (the property below) is
    therefore a sync point — asnumpy/item/float()/printing/shape access/
    in-place arithmetic all force the owning segment to compile and run
    before returning a concrete buffer.  Code that never bulks pays one
    attribute check."""

    __slots__ = ("_data_", "_lazy_", "_ctx", "_grad", "_grad_req",
                 "_tape_node", "__weakref__")

    # numpy interop priority (parity: __array_priority__ in reference)
    __array_priority__ = 1000.0

    def __init__(self, data, ctx: Optional[Context] = None, dtype=None):
        if isinstance(data, NDArray):
            data = data._data
        if not isinstance(data, jax.Array) or dtype is not None:
            data = jnp.asarray(data, dtype=jnp.dtype(dtype) if dtype else None)
        self._data = _place(data, ctx)
        self._ctx = ctx
        self._grad = None
        self._grad_req = "null"
        self._tape_node = None

    # -- raw access ------------------------------------------------------
    @property
    def _data(self):
        if self._lazy_ is not None:
            self._force()
        return self._data_

    @_data.setter
    def _data(self, value):
        self._data_ = value
        self._lazy_ = None

    def _force(self):
        """Flush the bulk segment backing this lazy handle (sync point)."""
        lz = self._lazy_
        if lz is not None:
            lz.segment.flush()
            if self._lazy_ is not None:  # defensive: flush must bind us
                self._lazy_ = None
                raise MXTPUError(
                    "bulk segment flush did not materialize this NDArray")

    @property
    def data(self):
        return self._data

    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return onp.dtype(str(self._data.dtype)) if not hasattr(
            self._data.dtype, "type") else self._data.dtype

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def size(self):
        return int(self._data.size)

    @property
    def context(self) -> Context:
        if self._ctx is not None:
            return self._ctx
        try:
            # deterministic for sharded arrays: lowest device id
            dev = min(self._data.devices(), key=lambda d: d.id)
            # Context ids are process-LOCAL (multi-process jax assigns
            # global ids like 2048*process_index to local devices); reuse
            # context.py's cached local lists so the two stay consistent
            from ..context import _accel_devices, _devices_for
            locals_ = (_devices_for("cpu") if dev.platform == "cpu"
                       else _accel_devices())
            try:
                local_id = locals_.index(dev)
            except ValueError:
                local_id = dev.id
            if dev.platform == "cpu":
                return Context("cpu", local_id)
            return Context("tpu", local_id)
        except Exception:  # tracers have no device
            return current_context()

    @property
    def is_sharded(self) -> bool:
        """True when the buffer spans multiple devices (SPMD array)."""
        try:
            return len(self._data.devices()) > 1
        except Exception:
            return False

    ctx = context

    @property
    def stype(self):
        return "default"  # sparse storage descoped v1 (SURVEY §7 hard-part 6)

    # -- host transfer ---------------------------------------------------
    def asnumpy(self) -> onp.ndarray:
        return onp.asarray(self._data)

    def asscalar(self):
        if self.size != 1:
            raise ValueError("the array is not scalar-sized")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __bool__(self):
        if self.size == 0:
            return False
        if self.size == 1:
            return bool(self.asscalar())
        raise ValueError("ambiguous truth value of multi-element NDArray")

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of 0-d NDArray")
        return self.shape[0]

    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    def wait_to_read(self):
        self._data.block_until_ready()

    def wait_to_write(self):
        self._data.block_until_ready()

    # -- autograd --------------------------------------------------------
    def attach_grad(self, grad_req: str = "write", stype=None):
        # Parity: attach_grad detaches the array from any recorded graph,
        # making it a fresh autograd leaf.
        self._tape_node = None
        self._grad = NDArray(jnp.zeros(self.shape, self._data.dtype))
        self._grad_req = grad_req

    @property
    def grad(self) -> Optional["NDArray"]:
        return self._grad

    def detach(self) -> "NDArray":
        out = NDArray(self._data)
        return out

    def as_np_ndarray(self):
        """The mx.np flavour of this array (shares the buffer AND the
        autograd state, so gradients flow through the conversion; parity:
        NDArray.as_np_ndarray in the 1.6+ reference)."""
        from ..numpy import ndarray as np_ndarray
        return self._as_flavour(np_ndarray)

    def _as_flavour(self, cls):
        out = cls(self._data, ctx=self._ctx)
        out._grad = self._grad
        out._grad_req = self._grad_req
        out._tape_node = self._tape_node
        return out

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    # -- placement -------------------------------------------------------
    def as_in_context(self, ctx: Context) -> "NDArray":
        if ctx == self.context:
            return self
        return NDArray(self._data, ctx=ctx)

    as_in_ctx = as_in_context

    def copyto(self, other):
        if isinstance(other, Context):
            return NDArray(self._data + 0, ctx=other)
        other._check_inplace_record()
        return other._rebind(_place(self._data + 0, other._ctx))

    def copy(self) -> "NDArray":
        return NDArray(self._data + 0, ctx=self._ctx)

    def astype(self, dtype, copy=True) -> "NDArray":
        return NDArray(self._data.astype(jnp.dtype(dtype)), ctx=self._ctx)

    def tostype(self, stype):
        if stype == "default":
            return self
        if stype == "row_sparse":
            from .sparse import _dense_to_row_sparse
            return _dense_to_row_sparse(self)
        if stype == "csr":
            from .sparse import csr_matrix
            return csr_matrix(self)
        raise MXTPUError(f"unknown storage type {stype!r}")

    # -- mutation --------------------------------------------------------
    def _check_inplace_record(self):
        # Parity: the reference raises when an array in the autograd graph
        # is mutated while recording (would corrupt the gradient graph).
        if autograd.is_recording() and autograd._on_tape(self):
            raise MXTPUError(
                "in-place mutation of an NDArray that is part of the "
                "recorded autograd graph is not allowed inside "
                "autograd.record(); use functional ops instead")

    def _rebind(self, new_data):
        """In-place semantic: swap the buffer in the slot."""
        self._data = new_data
        if engine.is_sync():
            try:
                new_data.block_until_ready()
            except AttributeError:
                pass
        return self

    def _rebind_from(self, other: "NDArray"):
        """Adopt ``other``'s buffer, lazily when possible: a pending bulk
        result transfers to this slot without forcing a flush (the fused
        trainer update path stays lazy end-to-end).  Not for use inside
        autograd.record() — tape identity stays with ``other``."""
        lz = other._lazy_
        if lz is not None:
            try:
                lz.segment.add_ref(lz.node, lz.out, self)
            except engine._SegmentClosed:
                return self._rebind(other._data)
            self._data_ = None
            self._lazy_ = lz
            return self
        return self._rebind(other._data_)

    def __setitem__(self, key, value):
        self._check_inplace_record()
        key = _translate_index(key)
        if isinstance(value, NDArray):
            value = value._data
        self._rebind(self._data.at[key].set(value))

    def __getitem__(self, key):
        # routed through the op registry so the autograd tape records the
        # gather (a bare self._data[key] would silently break the chain)
        key = _translate_index(key)
        return invoke_op("_internal_getitem", (self,), {"key": key})

    # -- shape ops (method forms) ---------------------------------------
    def reshape(self, *shape, **kwargs):
        if not shape and "shape" in kwargs:
            shape = tuple(kwargs.pop("shape"))
        elif len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return invoke_op("reshape", (self,), {"shape": shape})

    def reshape_like(self, other):
        return invoke_op("reshape_like", (self, other), {})

    def flatten(self):
        return invoke_op("flatten", (self,), {})

    def expand_dims(self, axis):
        return invoke_op("expand_dims", (self,), {"axis": axis})

    def squeeze(self, axis=None):
        return invoke_op("squeeze", (self,), {"axis": axis})

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        return invoke_op("transpose", (self,), {"axes": axes or None})

    @property
    def T(self):
        return invoke_op("transpose", (self,), {"axes": None})

    def swapaxes(self, dim1, dim2):
        return invoke_op("swapaxes", (self,), {"dim1": dim1, "dim2": dim2})

    def broadcast_to(self, shape):
        return invoke_op("broadcast_to", (self,), {"shape": shape})

    def broadcast_like(self, other):
        return invoke_op("broadcast_like", (self, other), {})

    def tile(self, reps):
        return invoke_op("tile", (self,), {"reps": reps})

    def repeat(self, repeats, axis=None):
        return invoke_op("repeat", (self,), {"repeats": repeats, "axis": axis})

    def flip(self, axis):
        return invoke_op("flip", (self,), {"axis": axis})

    def slice_axis(self, axis, begin, end):
        return invoke_op("slice_axis", (self,),
                         {"axis": axis, "begin": begin, "end": end})

    def take(self, indices, axis=0, mode="clip"):
        return invoke_op("take", (self, indices), {"axis": axis, "mode": mode})

    def one_hot(self, depth, **kw):
        return invoke_op("one_hot", (self,), dict(depth=depth, **kw))

    # -- reductions ------------------------------------------------------
    def sum(self, axis=None, keepdims=False):
        return invoke_op("sum", (self,), {"axis": axis, "keepdims": keepdims})

    def mean(self, axis=None, keepdims=False):
        return invoke_op("mean", (self,), {"axis": axis, "keepdims": keepdims})

    def max(self, axis=None, keepdims=False):
        return invoke_op("max", (self,), {"axis": axis, "keepdims": keepdims})

    def min(self, axis=None, keepdims=False):
        return invoke_op("min", (self,), {"axis": axis, "keepdims": keepdims})

    def prod(self, axis=None, keepdims=False):
        return invoke_op("prod", (self,), {"axis": axis, "keepdims": keepdims})

    def norm(self, ord=2, axis=None, keepdims=False):
        return invoke_op("norm", (self,),
                         {"ord": ord, "axis": axis, "keepdims": keepdims})

    def argmax(self, axis=None, keepdims=False):
        return invoke_op("argmax", (self,), {"axis": axis, "keepdims": keepdims})

    def argmin(self, axis=None, keepdims=False):
        return invoke_op("argmin", (self,), {"axis": axis, "keepdims": keepdims})

    def argsort(self, axis=-1, is_ascend=True):
        return invoke_op("argsort", (self,), {"axis": axis, "is_ascend": is_ascend})

    def topk(self, axis=-1, k=1, **kw):
        return invoke_op("topk", (self,), dict(axis=axis, k=k, **kw))

    # -- elementwise method forms ---------------------------------------
    def abs(self):
        return invoke_op("abs", (self,), {})

    def sqrt(self):
        return invoke_op("sqrt", (self,), {})

    def square(self):
        return invoke_op("square", (self,), {})

    def exp(self):
        return invoke_op("exp", (self,), {})

    def log(self):
        return invoke_op("log", (self,), {})

    def relu(self):
        return invoke_op("relu", (self,), {})

    def sigmoid(self):
        return invoke_op("sigmoid", (self,), {})

    def tanh(self):
        return invoke_op("tanh", (self,), {})

    def clip(self, a_min=None, a_max=None):
        return invoke_op("clip", (self,), {"a_min": a_min, "a_max": a_max})

    def round(self):
        return invoke_op("round", (self,), {})

    def sign(self):
        return invoke_op("sign", (self,), {})

    def softmax(self, axis=-1):
        return invoke_op("softmax", (self,), {"axis": axis})

    def log_softmax(self, axis=-1):
        return invoke_op("log_softmax", (self,), {"axis": axis})

    def dot(self, other, **kw):
        return invoke_op("dot", (self, other), kw)

    def zeros_like(self):
        return invoke_op("zeros_like", (self,), {})

    def ones_like(self):
        return invoke_op("ones_like", (self,), {})

    # -- arithmetic dunders ---------------------------------------------
    def __add__(self, other):
        return invoke_op("add", (self, other), {})

    def __radd__(self, other):
        return invoke_op("add", (other, self), {})

    def __sub__(self, other):
        return invoke_op("subtract", (self, other), {})

    def __rsub__(self, other):
        return invoke_op("subtract", (other, self), {})

    def __mul__(self, other):
        return invoke_op("multiply", (self, other), {})

    def __rmul__(self, other):
        return invoke_op("multiply", (other, self), {})

    def __truediv__(self, other):
        return invoke_op("divide", (self, other), {})

    def __rtruediv__(self, other):
        return invoke_op("divide", (other, self), {})

    def __mod__(self, other):
        return invoke_op("mod", (self, other), {})

    def __rmod__(self, other):
        return invoke_op("mod", (other, self), {})

    def __pow__(self, other):
        return invoke_op("power", (self, other), {})

    def __rpow__(self, other):
        return invoke_op("power", (other, self), {})

    def __neg__(self):
        return invoke_op("negative", (self,), {})

    def __abs__(self):
        return invoke_op("abs", (self,), {})

    def __matmul__(self, other):
        return invoke_op("dot", (self, other), {})

    def __iadd__(self, other):
        self._check_inplace_record()
        o = other._data if isinstance(other, NDArray) else other
        return self._rebind(self._data + o)

    def __isub__(self, other):
        self._check_inplace_record()
        o = other._data if isinstance(other, NDArray) else other
        return self._rebind(self._data - o)

    def __imul__(self, other):
        self._check_inplace_record()
        o = other._data if isinstance(other, NDArray) else other
        return self._rebind(self._data * o)

    def __itruediv__(self, other):
        self._check_inplace_record()
        o = other._data if isinstance(other, NDArray) else other
        return self._rebind(self._data / o)

    def __eq__(self, other):
        if other is None:
            return False
        return invoke_op("equal", (self, other), {})

    def __ne__(self, other):
        if other is None:
            return True
        return invoke_op("not_equal", (self, other), {})

    def __gt__(self, other):
        return invoke_op("greater", (self, other), {})

    def __ge__(self, other):
        return invoke_op("greater_equal", (self, other), {})

    def __lt__(self, other):
        return invoke_op("lesser", (self, other), {})

    def __le__(self, other):
        return invoke_op("lesser_equal", (self, other), {})

    def __hash__(self):
        return id(self)

    def __repr__(self):
        try:
            arr = self.asnumpy()
            return f"{arr}\n<NDArray {self.shape} @{self.context}>"
        except Exception:
            return f"<NDArray {self.shape} {self._data.dtype} (traced)>"

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]


def _translate_index(key):
    if isinstance(key, NDArray):
        return key._data
    if isinstance(key, tuple):
        return tuple(k._data if isinstance(k, NDArray) else k for k in key)
    return key


def _wrap_result(res, ctx, cls=None):
    """Wrap raw jax results; `cls` propagates NDArray subclasses (mx.np
    ndarray results stay np ndarrays through every registry op)."""
    cls = cls or NDArray
    if isinstance(res, (tuple, list)):
        return tuple(cls(r, ctx=ctx) for r in res)
    return cls(res, ctx=ctx)


from jax.core import Tracer as _Tracer  # noqa: E402

# sentinel: "this op was not bulked, dispatch it normally"
_NOT_BULKED = object()


def _new_lazy_handle(cls, lazyref):
    """A lazy NDArray bound to one pending bulk-segment output.  Bypasses
    __init__ (there is no buffer yet); both NDArray flavours are
    slots+methods only, so direct slot initialization is complete."""
    h = cls.__new__(cls)
    h._data_ = None
    h._lazy_ = lazyref
    h._ctx = None
    h._grad = None
    h._grad_req = "null"
    h._tape_node = None
    return h


def _bulk_record(seg, name: str, spec, args: tuple, kwargs: dict):
    """Append one eager op to the open bulk segment and return lazy
    handles, or _NOT_BULKED when the op must dispatch per-op (out=/ctx=
    requested, tracer inputs, unfreezable statics, ...).  Fallthrough
    needs no explicit flush: a fallthrough op reading a lazy input forces
    the segment through the ``_data`` property."""
    if kwargs.get("out") is not None or kwargs.get("ctx") is not None:
        engine._STATS["fallthroughs"] += 1
        return _NOT_BULKED

    n_outs = spec.num_outputs
    if callable(n_outs):
        try:
            n_outs = int(n_outs({k: v for k, v in kwargs.items()
                                 if not isinstance(v, NDArray)}))
        except Exception:
            engine._STATS["fallthroughs"] += 1
            return _NOT_BULKED
        if n_outs == 1:
            # a declared-arity op returning a 1-tuple is indistinguishable
            # from a bare-array op post-hoc; keep per-op dispatch for the
            # tuple-shaped return
            engine._STATS["fallthroughs"] += 1
            return _NOT_BULKED
    elif n_outs is None:
        # registry invariant (audit rule R002): an op that declares no
        # num_outputs returns exactly one array
        n_outs = 1

    recording = autograd.is_recording()
    kwargs = dict(kwargs)
    # explicit out=None / ctx=None are dispatch directives, not op
    # params — strip them exactly like the per-op path's pops (leaving
    # them would hand the op fn an unexpected kwarg inside the trace)
    kwargs.pop("out", None)
    kwargs.pop("ctx", None)
    # resolve runtime-state injection at RECORD time: the train flag is
    # the record-time truth, and the RNG key stream is consumed in
    # program order exactly as per-op dispatch would (bit-exact seeded
    # runs).  The key itself is drawn only AFTER every bulkability check
    # passes — a fallthrough op must not burn a key the normal dispatch
    # path will draw again.
    rng_wanted = _RNG_GATE.get(name, _ALWAYS)(kwargs)
    if name in _NEEDS_TRAIN_FLAG and rng_wanted:
        kwargs.setdefault("_training", autograd.is_training())
    need_key = (name in _NEEDS_KEY and rng_wanted
                and kwargs.get("_key") is None
                and (kwargs.get("_training")
                     or kwargs.get("mode") == "always"))

    # pre-force foreign lazies OUTSIDE our segment lock (taking another
    # segment's lock while holding ours could deadlock against a thread
    # doing the reverse)
    for a in args:
        if isinstance(a, NDArray):
            lz = a._lazy_
            if lz is not None and lz.segment is not seg:
                a._force()
    for v in kwargs.values():
        if isinstance(v, NDArray):
            lz = v._lazy_
            if lz is not None and lz.segment is not seg:
                v._force()

    run_args, sig_args = [], []
    res_cls = NDArray
    node_on_tape = False
    tape_inputs = []   # ext input indices whose source NDArray is on tape
    n_inputs0 = None
    try:
        # the whole record commits atomically against a cross-thread
        # flush: ops must not land in a flushed segment (they would
        # never run), and flush's snapshot must not tear mid-append
        with seg._lock:
            if seg.closed:
                raise engine._SegmentClosed
            n_inputs0 = len(seg.inputs)
            for a in args:
                if isinstance(a, NDArray):
                    if type(a) is not NDArray and res_cls is NDArray:
                        res_cls = type(a)
                    lz = a._lazy_
                    if lz is not None and lz.segment is seg:
                        run_args.append(("r", lz.node, lz.out))
                        sig_args.append(("r", lz.node, lz.out))
                        node_on_tape |= (recording
                                         and a._tape_node is not None)
                        continue
                    if lz is not None:
                        # a foreign lazy raced in after the pre-pass:
                        # bail, the per-op path forces it lock-free
                        raise engine._SegmentClosed
                    d = a._data_
                    if isinstance(d, _Tracer):
                        raise engine._Unfreezable("tracer input")
                    on_tape = recording and autograd._on_tape(a)
                    idx = seg.add_input(d, a, on_tape)
                    run_args.append(("x", idx))
                    sig_args.append(("x", idx))
                    if on_tape:
                        tape_inputs.append(idx)
                    node_on_tape |= on_tape
                elif isinstance(a, _Tracer):
                    raise engine._Unfreezable("tracer input")
                elif isinstance(a, jax.Array):
                    idx = seg.add_input(a, None, False)
                    run_args.append(("x", idx))
                    sig_args.append(("x", idx))
                else:
                    run_args.append(("c", a))
                    sig_args.append(("c", engine._freeze_static(a)))

            kw_run, kw_sig, statics, statics_sig = [], [], {}, []
            for k, v in kwargs.items():
                if isinstance(v, NDArray):
                    lz = v._lazy_
                    if lz is not None and lz.segment is seg:
                        kw_run.append((k, ("r", lz.node, lz.out)))
                        kw_sig.append((k, ("r", lz.node, lz.out)))
                        continue
                    if lz is not None:
                        raise engine._SegmentClosed
                    d = v._data_
                    if isinstance(d, _Tracer):
                        raise engine._Unfreezable("tracer input")
                    idx = seg.add_input(d, None, False)
                    kw_run.append((k, ("x", idx)))
                    kw_sig.append((k, ("x", idx)))
                elif isinstance(v, _Tracer):
                    raise engine._Unfreezable("tracer input")
                elif isinstance(v, jax.Array):
                    idx = seg.add_input(v, None, False)
                    kw_run.append((k, ("x", idx)))
                    kw_sig.append((k, ("x", idx)))
                else:
                    statics[k] = v
                    statics_sig.append((k, engine._freeze_static(v)))

            if need_key:
                # all checks passed — the op IS bulked — so consuming
                # the key here cannot double-draw with a fallthrough
                from .. import random as _rnd
                idx = seg.add_input(_rnd.next_key(), None, False)
                kw_run.append(("_key", ("x", idx)))
                kw_sig.append(("_key", ("x", idx)))

            eligible = recording and spec.differentiable and node_on_tape
            if eligible:
                seg.mark_diff_inputs(tape_inputs)
            node_sig = (name, tuple(sig_args), tuple(sorted(kw_sig)),
                        tuple(sorted(statics_sig)), n_outs, eligible)
            prog = engine._NodeProg(spec.fn, name, run_args, kw_run,
                                    statics, n_outs, eligible, node_sig)
            node_idx = seg.add_node(prog)

            handles = []
            for j in range(n_outs):
                h = _new_lazy_handle(
                    res_cls, engine._LazyRef(seg, node_idx, j))
                if eligible:
                    h._tape_node = engine.PENDING_TAPE
                seg.add_ref(node_idx, j, h)
                handles.append(h)
    except (engine._Unfreezable, engine._SegmentClosed):
        if n_inputs0 is not None:
            # drop inputs this aborted record appended — orphans would
            # pollute the segment's cache signature and vjp primal set
            seg.rollback_inputs(n_inputs0)
        engine._STATS["fallthroughs"] += 1
        return _NOT_BULKED

    if seg.full:
        engine.flush_bulk()
    return handles[0] if n_outs == 1 else tuple(handles)


def invoke_op(name: str, args: tuple, kwargs: dict):
    """The imperative dispatch path (parity: MXImperativeInvokeEx →
    Imperative::Invoke → PushFCompute → Engine::PushAsync; see SURVEY.md
    §3.1).  Here: unwrap → jax op (PJRT async dispatch) → wrap; when the
    autograd tape is recording, compute through jax.vjp and record a
    TapeNode (parity: Imperative::RecordOp).

    Under ``engine.bulk`` the op is not dispatched: it records into the
    thread's BulkSegment and returns lazy handles (see engine.py) —
    genuine op bulking, compiled once per segment signature.
    """
    spec = get_op(name)

    seg = engine.current_segment()
    if seg is not None and spec.bulkable and not _OUTPUT_MONITORS:
        res = _bulk_record(seg, name, spec, args, kwargs)
        if res is not _NOT_BULKED:
            return res

    out = kwargs.pop("out", None)
    ctx = kwargs.pop("ctx", None)

    nd_args = []
    raw_args = []
    for a in args:
        if isinstance(a, NDArray):
            nd_args.append(a)
            raw_args.append(a._data)
        else:
            raw_args.append(a)
    # array-valued keyword params (e.g. sequence_length) are non-diff inputs
    kwargs = {k: (v._data if isinstance(v, NDArray) else v)
              for k, v in kwargs.items()}

    recording = (autograd.is_recording() and spec.differentiable
                 and any(autograd._on_tape(a) for a in nd_args))
    # result class follows the inputs: mx.np ndarrays beget mx.np ndarrays;
    # any subclass operand wins regardless of operand order
    res_cls = next((type(a) for a in nd_args if type(a) is not NDArray),
                   NDArray)

    # inject runtime-state kwargs some ops need.  _RNG_GATE ops consume
    # RNG conditionally (switch_moe: only when router_jitter > 0) —
    # gating the injection keeps the global key stream, and so seeded
    # reproducibility of jitter-free MoE runs, identical to a model
    # without MoE layers.  The gated params are keyword-only in the op
    # signatures, so kwargs is the complete truth here.
    fn = spec.fn
    rng_wanted = _RNG_GATE.get(name, _ALWAYS)(kwargs)
    if name in _NEEDS_TRAIN_FLAG and rng_wanted:
        kwargs.setdefault("_training", autograd.is_training())
    if name in _NEEDS_KEY and rng_wanted:
        from .. import random as _rnd
        if kwargs.get("_key") is None and (
                kwargs.get("_training") or kwargs.get("mode") == "always"):
            kwargs["_key"] = _rnd.next_key()

    if recording:
        # differentiate wrt the NDArray positional args only
        diff_idx = [i for i, a in enumerate(args) if isinstance(a, NDArray)]

        def f(*diff_arrays):
            call = list(raw_args)
            for i, arr in zip(diff_idx, diff_arrays):
                call[i] = arr
            return fn(*call, **kwargs)

        primals = tuple(a._data for a in nd_args)
        res, vjp_fn = jax.vjp(f, *primals)
        outs = _wrap_result(res, ctx, res_cls)
        out_list = list(outs) if isinstance(outs, tuple) else [outs]
        autograd.record_node(vjp_fn, nd_args, out_list, name)
    else:
        res = fn(*raw_args, **kwargs)
        outs = _wrap_result(res, ctx, res_cls)
        out_list = list(outs) if isinstance(outs, tuple) else [outs]

    if engine.is_sync():
        for o in out_list:
            try:
                o._data.block_until_ready()
            except AttributeError:
                pass  # tracer

    if _OUTPUT_MONITORS:
        for cb in list(_OUTPUT_MONITORS):
            for o in out_list:
                cb(name, o)

    if out is not None:
        if isinstance(outs, tuple):
            raise MXTPUError("out= with multi-output op unsupported")
        if recording:
            raise MXTPUError(
                "out= is not supported inside autograd.record() (the tape "
                "tracks functional outputs only; parity with reference)")
        out._rebind(outs._data)
        return out
    return outs


# ops whose behavior depends on autograd train/predict mode or RNG
_NEEDS_TRAIN_FLAG = {"Dropout", "dropout", "BatchNorm", "batch_norm",
                     "RNN", "rnn", "switch_moe"}
_NEEDS_KEY = {"Dropout", "dropout", "RNN", "rnn", "switch_moe"}
_ALWAYS = lambda kw: True  # noqa: E731
# per-op predicate deciding whether the RNG state kwargs get injected
_RNG_GATE = {"switch_moe": lambda kw: bool(kw.get("router_jitter"))}

# op-output taps installed by mx.monitor.Monitor (parity: executor monitor
# callback — the reference taps op outputs in the engine)
_OUTPUT_MONITORS: list = []


def array(source, ctx: Optional[Context] = None, dtype=None) -> NDArray:
    """Parity: mx.nd.array."""
    if isinstance(source, NDArray):
        # always a copy (parity: mx.nd.array never aliases its source)
        data = source._data.astype(jnp.dtype(dtype)) if dtype else (
            source._data + 0)
        return NDArray(data, ctx=ctx)
    keep_dtype = isinstance(source, onp.ndarray) or hasattr(source, "dtype")
    a = onp.asarray(source, dtype=dtype)
    if dtype is None and not keep_dtype:
        a = a.astype(onp.float32)  # MXNet default dtype for python lists
    elif dtype is None and a.dtype == onp.float64:
        a = a.astype(onp.float32)
    return NDArray(jnp.asarray(a), ctx=ctx)


def waitall():
    engine.wait_all()
