"""Neural-network parameters (parity: python/mxnet/gluon/parameter.py).

Reference semantics kept: a Parameter owns one NDArray copy per context,
deferred initialization via unknown (0) shape dims resolved at first forward,
grad_req in {write, add, null}, and ParameterDict with prefix-scoped names.

TPU-native deltas: per-ctx copies are per-*device* jax arrays; under a mesh
the canonical copy is a sharded global array (set by mxtpu.parallel); grads
live beside data and are attached to the autograd tape exactly like NDArray
leaves.
"""

from __future__ import annotations

import functools
import threading
from collections import OrderedDict
from typing import List, Optional

import jax.numpy as jnp
import numpy as onp

from ..base import MXTPUError
from ..context import Context, current_context, cpu
from ..ndarray import NDArray
from ..observability.trace import get_tracer
from .. import autograd, initializer

__all__ = ["DeferredInitializationError", "Parameter", "Constant",
           "ParameterDict", "tensor_types"]

tensor_types = (NDArray,)


class DeferredInitializationError(MXTPUError):
    """Error for unfinished deferred initialization (parity: same name)."""


def _shape_known(shape) -> bool:
    return shape is not None and all(s > 0 for s in shape)


#: ``.count``: [parameters, bytes] materialised so far by the
#: ``initialize`` call open on this thread
_initializing = threading.local()


def initialize_span(method):
    """For ``Block``, ``ParameterDict`` and ``Parameter.initialize``: the
    outermost such call on a thread is one ``block.initialize`` boundary
    span whose end carries the ``params`` it materialised (a parameter
    whose shape is deferred is not among them) and their ``bytes``; the
    calls inside it open nothing."""
    @functools.wraps(method)
    def initialize(self, *args, **kwargs):
        if getattr(_initializing, "count", None) is not None:
            return method(self, *args, **kwargs)
        count = _initializing.count = [0, 0]
        try:
            with get_tracer().span("block.initialize") as span:
                try:
                    return method(self, *args, **kwargs)
                finally:
                    span.set(params=count[0], bytes=count[1])
        finally:
            _initializing.count = None
    return initialize


class Parameter:
    """A Block parameter (parity: gluon.Parameter).

    Supports deferred init: any 0 in ``shape`` means "infer at first
    forward"; layers call ``_finish_deferred_init`` once shapes are known
    (mirrors the reference's _finish_deferred_init driven by infer_shape).
    """

    def __init__(self, name, grad_req="write", shape=None, dtype="float32",
                 lr_mult=1.0, wd_mult=1.0, init=None,
                 allow_deferred_init=False, differentiable=True,
                 stype="default", grad_stype="default"):
        self._var = None
        self._data = None          # list[NDArray] aligned with self._ctx_list
        self._grad = None
        self._ctx_list = None
        self._deferred_init = ()
        self.name = name
        self._shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._differentiable = differentiable
        if not differentiable:
            grad_req = "null"
        self._grad_req = None
        self.grad_req = grad_req
        # row_sparse grad: gradients ACCUMULATE densely (XLA scatter-add is
        # the TPU fast path) but are EXPOSED sparsely — grad() compacts to
        # the touched rows recorded by the producing layer (Embedding
        # sparse_grad), and the SGD update applies only those rows.
        self._grad_stype = grad_stype
        self._sparse_row_ids = None
        if stype != "default":
            import warnings
            warnings.warn("sparse parameter stype is dense-backed in mxtpu "
                          "(row_sparse grads ARE supported; SURVEY.md §7)")
        if grad_stype not in ("default", "row_sparse"):
            raise ValueError(f"unsupported grad_stype {grad_stype!r}")

    def __repr__(self):
        return f"Parameter {self.name} (shape={self.shape}, dtype={self.dtype})"

    # -- grad_req ---------------------------------------------------------
    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        if req not in ("write", "add", "null"):
            raise ValueError(f"grad_req must be write/add/null, got {req}")
        if not self._differentiable:
            req = "null"
        if self._grad_req == req:
            return
        self._grad_req = req
        if req == "null":
            self._grad = None
            if self._data is not None:
                for d in self._data:
                    d._grad = None
                    d._grad_req = "null"
        elif self._data is not None:
            self._init_grad()

    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is None:
            self._shape = tuple(new_shape) if new_shape is not None else None
            return
        if new_shape is None:
            return
        unknown_ok = len(self._shape) == len(new_shape) and all(
            s == 0 or s == n for s, n in zip(self._shape, new_shape))
        if not unknown_ok:
            raise AssertionError(
                f"Expected shape {new_shape} is incompatible with given "
                f"shape {self._shape} for Parameter {self.name}")
        self._shape = tuple(new_shape)

    # -- init -------------------------------------------------------------
    @initialize_span
    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False):
        """Materialize (or defer) this parameter on the given context(s)."""
        if default_init is None:
            default_init = initializer.Uniform()
        if self._data is not None and not force_reinit:
            return
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        if init is None:
            init = default_init if self.init is None else self.init
        if not _shape_known(self._shape):
            if self.allow_deferred_init:
                self._deferred_init = (init, ctx, default_init, None)
                return
            raise ValueError(
                f"Cannot initialize Parameter {self.name} because it has "
                f"invalid shape: {self._shape}. Please specify in_units/"
                "in_channels/etc for the layer or set allow_deferred_init.")
        self._deferred_init = (init, ctx, default_init, None)
        self._finish_deferred_init()

    def _finish_deferred_init(self):
        if not self._deferred_init:
            return
        init, ctx, default_init, data = self._deferred_init
        self._deferred_init = ()
        if not _shape_known(self._shape):
            raise DeferredInitializationError(
                f"Parameter {self.name} has unknown shape {self._shape}; "
                "run a forward pass or call infer_shape first")
        with autograd.pause():
            if data is None:
                data = NDArray(jnp.zeros(self._shape, jnp.dtype(self.dtype)))
                initializer.create(init if init is not None else default_init)(
                    initializer.InitDesc(self.name), data)
            if str(data.dtype) != str(self.dtype):
                # initializers fill in fp32; honor a cast() that happened
                # before the deferred init resolved
                data = NDArray(data.data.astype(jnp.dtype(self.dtype)))
            self._init_impl(data, ctx)
        count = getattr(_initializing, "count", None)
        if count is not None:
            count[0] += 1
            count[1] += sum(d.data.nbytes for d in self._data)

    def _init_impl(self, data, ctx_list):
        self._ctx_list = list(ctx_list)
        self._data = [data.as_in_context(c).copy() if i else
                      data.as_in_context(ctx_list[0])
                      for i, c in enumerate(self._ctx_list)]
        if self._grad_req != "null":
            self._init_grad()

    def _init_grad(self):
        self._grad = [NDArray(jnp.zeros(d.shape, d.data.dtype))
                      for d in self._data]
        for d, g in zip(self._data, self._grad):
            d._grad = g
            d._grad_req = self._grad_req

    def release_grad(self):
        """Give up the eager gradient buffers (one more copy of the
        parameter on the device) while ``grad_req`` stays as it is: for
        an owner that takes its gradients elsewhere (``SPMDTrainer``:
        inside its compiled step).  The data stay leaves of the eager
        tape; whoever asks for the gradient next finds what a backward
        pass has written since, or zeros."""
        self._grad = None
        for d in self._data or ():
            d._grad = None

    def _ensure_grad(self):
        """The buffers again after ``release_grad``: what an eager
        backward pass wrote on the data in the meantime is adopted, not
        zeroed."""
        if self._grad is not None or self._data is None \
                or self._grad_req == "null":
            return
        self._grad = [d._grad if d._grad is not None
                      else NDArray(jnp.zeros(d.shape, d.data.dtype))
                      for d in self._data]
        for d, g in zip(self._data, self._grad):
            d._grad = g
            d._grad_req = self._grad_req

    # -- access -----------------------------------------------------------
    def _check_and_get(self, arr_list, ctx):
        if arr_list is not None:
            if ctx is list:
                return arr_list
            if ctx is None:
                if len(arr_list) == 1:
                    return arr_list[0]
                ctx = current_context()
            for c, a in zip(self._ctx_list, arr_list):
                if c == ctx:
                    return a
            # a mesh-sharded parameter serves every device in its mesh
            # (SPMD path: there is one logical copy, XLA owns placement)
            if len(arr_list) == 1 and arr_list[0].is_sharded:
                return arr_list[0]
            raise MXTPUError(
                f"Parameter {self.name} was not initialized on context {ctx}; "
                f"it is on {self._ctx_list}")
        if self._deferred_init:
            raise DeferredInitializationError(
                f"Parameter {self.name} has not been initialized yet because "
                "initialization was deferred. Actual initialization happens "
                "during the first forward pass.")
        raise MXTPUError(
            f"Parameter {self.name} has not been initialized. You should "
            "initialize parameters and create Trainer with Block.collect_params() "
            "instead of Block.params")

    def data(self, ctx=None) -> NDArray:
        return self._check_and_get(self._data, ctx)

    def list_data(self) -> List[NDArray]:
        return self._check_and_get(self._data, list)

    def grad(self, ctx=None) -> NDArray:
        self._ensure_grad()
        if self._data is not None and self._grad is None:
            raise MXTPUError(
                f"Cannot get gradient array for Parameter {self.name} "
                "because grad_req='null'")
        g = self._check_and_get(self._grad, ctx)
        return self._sparsify_grad(g)

    def list_grad(self) -> List[NDArray]:
        self._ensure_grad()
        if self._data is not None and self._grad is None:
            raise MXTPUError(
                f"Cannot get gradient array for Parameter {self.name} "
                "because grad_req='null'")
        return [self._sparsify_grad(g)
                for g in self._check_and_get(self._grad, list)]

    def _accumulate_sparse_row_ids(self, ids):
        """Union newly touched rows into the pending id set (called by the
        producing layer on every recorded eager forward; consumed —
        reset — by the optimizer step / zero_grad)."""
        import jax.numpy as jnp
        if self._sparse_row_ids is None:
            self._sparse_row_ids = NDArray(jnp.asarray(ids, jnp.int32))
        else:
            self._sparse_row_ids = NDArray(jnp.union1d(
                self._sparse_row_ids.data, jnp.asarray(ids, jnp.int32)))

    def _consume_sparse_row_ids(self):
        self._sparse_row_ids = None

    def _sparsify_grad(self, g):
        """row_sparse grad view: compact the dense buffer onto the union
        of rows touched since the last consume (exact — untouched rows
        accumulated zero).  With no recorded ids (e.g. hybridized forward:
        tracing records none) the dense buffer is returned — always
        exact, just not compact."""
        if self._grad_stype != "row_sparse" or self._sparse_row_ids is None:
            return g
        from ..ndarray.sparse import RowSparseNDArray
        import jax.numpy as jnp
        ids_j = self._sparse_row_ids.data
        vals = jnp.take(g.data, ids_j, axis=0)
        return RowSparseNDArray(NDArray(vals), NDArray(ids_j), g.shape)

    def _list_dense_grad(self):
        """Dense grad buffers for kvstore allreduce (the reduced result is
        written back in place; sparse views are re-derived afterwards)."""
        self._ensure_grad()
        return self._check_and_get(self._grad, list)

    def list_ctx(self) -> List[Context]:
        if self._data is None:
            if self._deferred_init:
                return self._deferred_init[1]
            raise MXTPUError(
                f"Parameter {self.name} has not been initialized")
        return self._ctx_list

    def set_data(self, data):
        """Set value on all contexts (parity: Parameter.set_data)."""
        self.shape = tuple(data.shape)
        if self._data is None:
            if not self._deferred_init:
                raise MXTPUError(
                    f"Parameter {self.name} has not been initialized")
            init, ctx, default_init, _ = self._deferred_init
            if not isinstance(data, NDArray):
                data = NDArray(jnp.asarray(data))
            self._deferred_init = (init, ctx, default_init, data)
            return
        src = data.data if isinstance(data, NDArray) else jnp.asarray(data)
        for d in self._data:
            d._rebind(jnp.asarray(src, d.data.dtype))

    def zero_grad(self):
        if self._grad is None \
                and all(d._grad is None for d in self._data or ()):
            return      # none asked for, or released and none written since
        self._ensure_grad()
        self._consume_sparse_row_ids()
        for g in self._grad:
            g._rebind(jnp.zeros(g.shape, g.data.dtype))

    def reset_ctx(self, ctx):
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        if self._data:
            data = self._data[0]
            with autograd.pause():
                self._init_impl(data, ctx)
        elif self._deferred_init:
            init, _, default_init, data = self._deferred_init
            self._deferred_init = (init, ctx, default_init, data)
        else:
            raise MXTPUError(
                f"Cannot reset context for Parameter {self.name} because it "
                "has not been initialized")

    def cast(self, dtype):
        self.dtype = dtype
        if self._data is None:
            return
        with autograd.pause():
            self._data = [NDArray(d.data.astype(jnp.dtype(dtype)))
                          for d in self._data]
            for d in self._data:        # leaves still, buffers or none
                d._grad_req = self._grad_req
            if self._grad is not None:
                self._grad = [NDArray(g.data.astype(jnp.dtype(dtype)))
                              for g in self._grad]
                for d, g in zip(self._data, self._grad):
                    d._grad = g
                    d._grad_req = self._grad_req

    def var(self):
        """Symbolic variable for this parameter (parity: Parameter.var)."""
        if self._var is None:
            from .. import symbol
            self._var = symbol.var(self.name, shape=self.shape,
                                   dtype=self.dtype,
                                   lr_mult=self.lr_mult,
                                   wd_mult=self.wd_mult)
        return self._var

    # sparse API kept for surface parity; dense behavior
    def row_sparse_data(self, row_id):
        return self.data()

    def list_row_sparse_data(self, row_id):
        return self.list_data()


class Constant(Parameter):
    """Non-updating parameter (parity: gluon.Constant)."""

    def __init__(self, name, value):
        if not isinstance(value, NDArray):
            value = NDArray(jnp.asarray(onp.asarray(value, dtype=onp.float32)))
        self.value = value

        class Init(initializer.Initializer):
            def _init_weight(_, desc, arr):
                arr._rebind(jnp.asarray(value.data, arr.data.dtype))

        init_name = f"Constant_{name}_{id(self)}"
        initializer._INIT_REGISTRY[init_name.lower()] = Init
        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=str(value.data.dtype), init=init_name,
                         differentiable=False)


class ParameterDict:
    """Prefix-scoped dict of Parameters (parity: gluon.ParameterDict)."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params = OrderedDict()
        self._shared = shared

    def __getitem__(self, key):
        return self._params[key]

    def __iter__(self):
        return iter(self._params)

    def __repr__(self):
        s = "\n".join(f"  {v}" for v in self.values())
        return f"{type(self).__name__} '{self._prefix}' (\n{s}\n)"

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def __len__(self):
        return len(self._params)

    def __contains__(self, key):
        return key in self._params

    @property
    def prefix(self):
        return self._prefix

    def _get_impl(self, name):
        if name in self._params:
            return self._params[name]
        if self._shared is not None and name in self._shared._params:
            self._params[name] = self._shared._params[name]
            return self._params[name]
        return None

    def get(self, name, **kwargs):
        """Get or create parameter ``prefix+name`` (parity: get)."""
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            param = Parameter(name, **kwargs)
            self._params[name] = param
        else:
            for k, v in kwargs.items():
                if hasattr(param, k) and getattr(param, k) is not None:
                    existing = getattr(param, k)
                    if k == "shape" and v is not None and existing is not None:
                        # merge partial shapes (parity: shape unification)
                        v = tuple(v)
                        if len(v) == len(existing):
                            merged = tuple(
                                e if e else n for e, n in zip(existing, v))
                            ok = all(e == 0 or n == 0 or e == n
                                     for e, n in zip(existing, v))
                            if not ok:
                                raise AssertionError(
                                    f"Cannot retrieve Parameter {name} "
                                    f"because shapes mismatch: {existing} vs {v}")
                            param._shape = merged
                            continue
                    if v is not None and v != existing and k != "init":
                        raise AssertionError(
                            f"Cannot retrieve Parameter {name} because "
                            f"attribute {k} mismatch: {existing} vs {v}")
                else:
                    setattr(param, k, v)
        return param

    def get_constant(self, name, value=None):
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            if value is None:
                raise MXTPUError(
                    f"No constant named {name}; provide value=")
            param = Constant(name, value)
            self._params[name] = param
        elif value is not None:
            if not isinstance(param, Constant):
                raise MXTPUError(f"Parameter {name} exists but is not a Constant")
        return param

    def update(self, other):
        for k, v in other.items():
            if k in self._params:
                if self._params[k] is not v:
                    raise ValueError(
                        f"Cannot update self with other because they have "
                        f"different Parameters with the same name {k}")
            else:
                self._params[k] = v

    @initialize_span
    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        if init is None:
            init = initializer.Uniform()
        for _, v in self.items():
            v.initialize(None, ctx, init, force_reinit=force_reinit)

    def zero_grad(self):
        for v in self.values():
            v.zero_grad()

    def reset_ctx(self, ctx):
        for v in self.values():
            v.reset_ctx(ctx)

    def list_ctx(self):
        assert self._params, "ParameterDict is empty"
        block = set()
        for v in self.values():
            try:
                for c in v.list_ctx():
                    block.add(c)
            except MXTPUError:
                pass
        return sorted(block, key=str)

    def setattr(self, name, value):
        for v in self.values():
            setattr(v, name, value)

    def save(self, filename, strip_prefix=""):
        """Save to the NDArray name→array container format
        (parity: ParameterDict.save → .params file)."""
        from ..ndarray import serialization

        arg_dict = {}
        for param in self.values():
            weight = param._reduce() if hasattr(param, "_reduce") else (
                param.data().asnumpy() if param._data else None)
            if weight is None:
                continue
            if not param.name.startswith(strip_prefix):
                raise ValueError(
                    f"Prefix {strip_prefix} is to be striped before saving, "
                    f"but Parameter {param.name} does not start with it")
            arg_dict[param.name[len(strip_prefix):]] = NDArray(
                jnp.asarray(weight))
        serialization.save(filename, arg_dict)

    def load(self, filename, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix=""):
        from ..ndarray import serialization

        loaded = serialization.load(filename)
        if isinstance(loaded, dict):
            arg_dict = {restore_prefix + k.replace("arg:", "").replace(
                "aux:", ""): v for k, v in loaded.items()}
        else:
            raise MXTPUError(f"{filename} does not contain a name→array dict")
        if not allow_missing:
            for name in self.keys():
                if name not in arg_dict:
                    raise MXTPUError(
                        f"Parameter {name} is missing in file {filename}")
        for name in arg_dict:
            if name not in self._params:
                if ignore_extra:
                    continue
                raise MXTPUError(
                    f"Parameter {name} loaded from file {filename} is not "
                    "present in this ParameterDict")
            self[name]._load_init(arg_dict[name], ctx)


def _param_load_init(self, data, ctx):
    """Parameter._load_init (parity): set data, honoring deferred state."""
    if self._shape is not None:
        unknown_ok = len(self._shape) == len(data.shape) and all(
            s == 0 or s == d for s, d in zip(self._shape, data.shape))
        if not unknown_ok:
            raise MXTPUError(
                f"Failed loading Parameter {self.name} from saved params: "
                f"shape incompatible expected {self._shape} vs saved "
                f"{tuple(data.shape)}")
        self._shape = tuple(data.shape)
    if self.dtype is not None and jnp.dtype(self.dtype) != data.data.dtype:
        data = NDArray(data.data.astype(jnp.dtype(self.dtype)))
    if ctx is None:
        ctx = [current_context()]
    if isinstance(ctx, Context):
        ctx = [ctx]
    if self._data is None:
        if self._deferred_init:
            ctx = self._deferred_init[1]
        self._init_impl(data, ctx)
        self._deferred_init = ()
    else:
        self.set_data(data)


Parameter._load_init = _param_load_init
