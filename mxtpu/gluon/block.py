"""Block / HybridBlock (parity: python/mxnet/gluon/block.py).

Block is the imperative NN container: child registry via ``__setattr__``,
prefix/name scopes, parameter collection, save/load, hooks.  HybridBlock adds
``hybridize()`` — in the reference this traces ``hybrid_forward`` to a Symbol
graph executed by CachedOp (src/imperative/cached_op.cc); here it
functionalizes the block over its parameter pytree and hands it to
``jax.jit`` via mxtpu.cached_op.CachedOp.  `static_alloc`/`static_shape`
flags are accepted: XLA always plans memory statically, so they are
documented no-ops rather than modes.

Divergence note (deferred shape inference, SURVEY §7 hard-part 2): the
reference resolves unknown param shapes with symbolic whole-graph shape
inference; here every built-in layer overrides ``infer_shape`` to infer its
own param shapes from the input, which covers the model zoo.  Custom blocks
with deferred-shape params must override ``infer_shape`` (a clear error says
so).
"""

from __future__ import annotations

import copy
import re
import threading
import warnings
from collections import OrderedDict

import jax.numpy as jnp
import numpy as onp

from .. import autograd, ndarray
from ..base import MXTPUError
from ..context import Context, current_context
from ..ndarray import NDArray
from ..ops import remat as _kept
from .parameter import (Parameter, ParameterDict, DeferredInitializationError,
                        Constant, initialize_span)

__all__ = ["Block", "HybridBlock", "SymbolBlock", "remat_scope"]


# -- recomputation per unit --------------------------------------------------
#
# A block whose class (or instance) sets ``remat_unit = True`` — a decoder
# or encoder layer, or half of one — is a unit of recomputation: called
# inside ``remat_scope()`` (``SPMDTrainer(remat=True)`` opens it around
# the traced forward) its forward is wrapped in ``jax.checkpoint`` over
# the unit's own parameters and inputs, so the backward pass keeps the
# unit's input, and what the unit's ops marked, and forms everything
# else inside it again.  An op marks a value with ``ops.remat.keep`` where
# forming it a second time is dear and holding it cheap: flash
# attention's output and logsumexp, the KDA mixer's output — as much
# again as the unit's input, and one forward of the kernel less.  A unit
# whose ops mark nothing keeps its input alone.  Units inside a unit are
# not wrapped a second time.

_REMAT = threading.local()


class remat_scope:
    """Inside it, every ``remat_unit`` block recomputes its forward in
    the backward pass.  Only meaningful while a program is traced (under
    ``jax.grad``); eager calls just run.  Opening it starts the count of
    what the units keep (``remat.kept_outputs``, ``remat.kept_bytes``)
    anew."""

    def __init__(self, active=True):
        self._active = bool(active)

    def __enter__(self):
        self._was = getattr(_REMAT, "active", False)
        _REMAT.active = self._active
        if self._active and not self._was:
            _kept.reset()
        return self

    def __exit__(self, *exc):
        _REMAT.active = self._was
        return False


class _BlockScope:
    """Name scope for automatic prefixes (parity: _BlockScope)."""

    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None
        self._name_scope = None

    @staticmethod
    def create(prefix, params, hint):
        """Create prefix and params for new Block."""
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                if not hasattr(_NameManager._current, "value"):
                    _NameManager._current.value = _NameManager()
                prefix = _NameManager._current.value.get(None, hint) + "_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            prefix = f"{hint}{count}_"
            current._counter[hint] = count + 1
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        self._name_scope = _name_prefix_scope(self._block.prefix)
        self._name_scope.__enter__()
        return self

    def __exit__(self, ptype, value, trace):
        if self._block._empty_prefix:
            return
        self._name_scope.__exit__(ptype, value, trace)
        self._name_scope = None
        _BlockScope._current.value = self._old_scope


class _NameManager:
    """Global name counter (parity: mxnet.name.NameManager)."""

    _current = threading.local()

    def __init__(self):
        self._counter = {}

    def get(self, name, hint):
        if name is not None:
            return name
        if hint not in self._counter:
            self._counter[hint] = 0
        name = f"{hint}{self._counter[hint]}"
        self._counter[hint] += 1
        return name


class _name_prefix_scope:
    def __init__(self, prefix):
        self.prefix = prefix

    def __enter__(self):
        return self

    def __exit__(self, *a):
        pass


class Block:
    """Base class for all neural network layers and models
    (parity: gluon.Block)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(
            prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else (
            self._prefix)
        self._scope = _BlockScope(self)
        self._children = OrderedDict()
        self._reg_params = {}
        self._forward_hooks = OrderedDict()
        self._forward_pre_hooks = OrderedDict()

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join(
            f"  ({key}): {_indent(repr(block), 2)}"
            for key, block in self.__dict__.items()
            if isinstance(block, Block))
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __setattr__(self, name, value):
        if hasattr(self, name):
            existing = getattr(self, name)
            if isinstance(existing, (Parameter, Block)) and not isinstance(
                    value, type(existing)):
                raise TypeError(
                    f"Changing attribute type for {self.name} from "
                    f"{type(existing)} to {type(value)} is not allowed.")
        if isinstance(value, Block):
            self.register_child(value, name)
        elif isinstance(value, Parameter):
            assert name not in self._reg_params or \
                self._reg_params[name] is value, (
                    "Overriding Parameter attribute %s is not allowed. "
                    "If you want to share parameters between blocks, please "
                    "set 'params' at Block construction instead." % name)
            self._reg_params[name] = value
        super().__setattr__(name, value)

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        """Prefix-scope context manager (parity: Block.name_scope)."""
        return self._scope

    @property
    def params(self):
        """This block's own ParameterDict (no children)."""
        return self._params

    def collect_params(self, select=None) -> ParameterDict:
        """All parameters of self and children (parity: collect_params)."""
        self._check_container_with_block()
        ret = ParameterDict(self._params.prefix)
        if not select:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({name: value for name, value in self.params.items()
                        if pattern.match(name)})
        for cld in self._children.values():
            ret.update(cld.collect_params(select=select))
        return ret

    def _check_container_with_block(self):
        children = set(self._children.values())
        for k, v in self.__dict__.items():
            if isinstance(v, (list, tuple, dict)) and k != "_children":
                leaves = v.values() if isinstance(v, dict) else v
                if any(isinstance(i, Block) and i not in children
                       for i in leaves):
                    warnings.warn(
                        f'"{k}" is an unregistered container with Blocks. '
                        "Note that Blocks inside the list, tuple or dict will "
                        "not be registered automatically. Make sure to "
                        "register them using register_child() or switching "
                        "to nn.Sequential/nn.HybridSequential instead.",
                        stacklevel=3)

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + key: val for key, val in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def save_parameters(self, filename, deduplicate=False):
        """Save parameters with structural names (parity: save_parameters)."""
        from ..ndarray import serialization

        params = self._collect_params_with_prefix()
        if deduplicate:
            reverse = {}
            for k, v in params.items():
                reverse.setdefault(id(v), []).append(k)
            params = {ks[0]: params[ks[0]].data() if params[ks[0]]._data
                      else None for ks in reverse.values()}
            params = {k: v for k, v in params.items() if v is not None}
        else:
            params = {k: v.data() for k, v in params.items()
                      if v._data is not None}
        serialization.save(filename, params)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        """Load parameters saved by save_parameters (parity)."""
        from ..ndarray import serialization

        loaded = serialization.load(filename)
        params = self._collect_params_with_prefix()
        if not loaded and not params:
            return
        # detect full-name format (ParameterDict.save / export) vs structural
        if not any("." in k for k in loaded.keys()) and any(
                k.startswith(self.prefix) for k in loaded.keys()):
            # parameter-name keyed: strip prefix and route via collect_params
            self.collect_params().load(
                filename, ctx, allow_missing, ignore_extra, self.prefix)
            return
        if not allow_missing:
            for name in params.keys():
                if name not in loaded:
                    raise MXTPUError(
                        f"Parameter '{name}' is missing in file '{filename}', "
                        "which contains parameters: %s" % _brief_print(loaded))
        for name in loaded:
            if name not in params:
                if ignore_extra:
                    continue
                raise MXTPUError(
                    f"Parameter '{name}' loaded from file '{filename}' is "
                    "not present in this Block")
            value = loaded[name]
            if cast_dtype:
                if dtype_source == "current" and params[name].dtype:
                    value = NDArray(value.data.astype(
                        jnp.dtype(params[name].dtype)))
                elif dtype_source == "saved":
                    params[name].dtype = str(value.data.dtype)
            params[name]._load_init(value, ctx)

    # legacy names kept (parity: deprecated save_params/load_params)
    def save_params(self, filename):
        warnings.warn("save_params is deprecated. Please use save_parameters.")
        self.save_parameters(filename)

    def load_params(self, filename, ctx=None, allow_missing=False,
                    ignore_extra=False):
        warnings.warn("load_params is deprecated. Please use load_parameters.")
        self.load_parameters(filename, ctx, allow_missing, ignore_extra)

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def register_forward_pre_hook(self, hook):
        handle = _HookHandle(self._forward_pre_hooks)
        self._forward_pre_hooks[handle.id] = hook
        return handle

    def register_forward_hook(self, hook):
        handle = _HookHandle(self._forward_hooks)
        self._forward_hooks[handle.id] = hook
        return handle

    def apply(self, fn):
        """Apply fn recursively to self and children (parity: apply)."""
        for cld in self._children.values():
            cld.apply(fn)
        fn(self)
        return self

    @initialize_span
    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        from .. import initializer as _init

        self.collect_params().initialize(
            init or _init.Uniform(), ctx, verbose, force_reinit)

    def hybridize(self, active=True, **kwargs):
        """Activate compiled execution for HybridBlock children."""
        for cld in self._children.values():
            cld.hybridize(active, **kwargs)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)

    #: a unit of recomputation under ``remat_scope`` (see the top of
    #: this file); layers of the model zoo set it
    remat_unit = False

    def __call__(self, *args):
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        if self.remat_unit and getattr(_REMAT, "active", False):
            out = self._forward_rematerialized(*args)
        else:
            out = self.forward(*args)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def _forward_rematerialized(self, *args):
        """``forward`` under ``jax.checkpoint``, as a function of this
        unit's parameters and its array arguments.  What the forward
        rebinds of non-trained parameters (running statistics, counters)
        leaves the checkpoint as an output and is rebound outside it."""
        import jax

        params = [p for _, p in sorted(self.collect_params().items())]
        holders = [p.data() for p in params]
        is_array = [isinstance(a, NDArray) for a in args]
        structure = []

        def fn(leaves, arrays):
            saved = [h._data for h in holders]
            for h, leaf in zip(holders, leaves):
                h._data = leaf
            arrays = iter(arrays)
            try:
                with remat_scope(False):
                    out = self.forward(*[
                        NDArray(next(arrays)) if nd else a
                        for a, nd in zip(args, is_array)])
                after = tuple(h._data for h in holders)
            finally:
                for h, data in zip(holders, saved):
                    h._data = data
            flat, tree = jax.tree_util.tree_flatten(
                out, is_leaf=lambda x: isinstance(x, NDArray))
            structure.append((tree, [isinstance(x, NDArray) for x in flat]))
            return tuple(x._data if isinstance(x, NDArray) else x
                         for x in flat), after

        flat, after = jax.checkpoint(fn, policy=_kept.policy)(
            tuple(h._data for h in holders),
            tuple(a._data for a, nd in zip(args, is_array) if nd))
        for p, h, new in zip(params, holders, after):
            if p.grad_req == "null":
                h._data = new
        tree, was_array = structure[-1]
        return jax.tree_util.tree_unflatten(
            tree, [NDArray(x) if nd else x
                   for x, nd in zip(flat, was_array)])

    def forward(self, *args):
        raise NotImplementedError

    def summary(self, *inputs):
        """Print per-layer output shapes and param counts (parity: summary)."""
        summary = OrderedDict()
        hooks = []

        def _register(block):
            def _hook(blk, inp, out):
                name = f"{blk.__class__.__name__}-{len(summary) + 1}"
                entry = OrderedDict()
                out0 = out[0] if isinstance(out, (list, tuple)) else out
                entry["output_shape"] = tuple(out0.shape)
                n_params = 0
                for p in blk.params.values():
                    if p._data is not None:
                        n_params += int(onp.prod(p.shape))
                entry["n_params"] = n_params
                summary[name] = entry

            hooks.append(block.register_forward_hook(_hook))

        self.apply(_register)
        try:
            self(*inputs)
            print("-" * 64)
            print(f"{'Layer':<32}{'Output Shape':<20}{'Params':<12}")
            print("=" * 64)
            total = 0
            for name, entry in summary.items():
                print(f"{name:<32}{str(entry['output_shape']):<20}"
                      f"{entry['n_params']:<12}")
                total += entry["n_params"]
            print("=" * 64)
            print(f"Total params: {total}")
            print("-" * 64)
        finally:
            for h in hooks:
                h.detach()


class _HookHandle:
    _next_id = [0]

    def __init__(self, hooks_dict):
        self._hooks_dict = hooks_dict
        self.id = _HookHandle._next_id[0]
        _HookHandle._next_id[0] += 1

    def detach(self):
        self._hooks_dict.pop(self.id, None)


class HybridBlock(Block):
    """Block with a compilable forward (parity: gluon.HybridBlock).

    Subclasses implement ``hybrid_forward(self, F, x, *args, **params)``
    where F is the op namespace (mxtpu.ndarray imperatively; also
    mxtpu.ndarray under jit trace — NDArrays then carry tracers) and params
    arrive as keyword arrays, exactly like the reference.
    """

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._flags = {}
        self._cached_op = None

    def __setattr__(self, name, value):
        super().__setattr__(name, value)
        if isinstance(value, HybridBlock):
            self._clear_cached_op()

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  **kwargs):
        self._active = active
        self._flags = dict(static_alloc=static_alloc,
                           static_shape=static_shape, **kwargs)
        self._clear_cached_op()
        # children run inside the parent's compiled graph; they do NOT build
        # their own CachedOps (parity: only the outermost call is cached)
        for cld in self._children.values():
            cld.hybridize(False, static_alloc=static_alloc,
                          static_shape=static_shape, **kwargs)
        if active:
            self._active = True

    def _clear_cached_op(self):
        self._cached_op = None

    def cast(self, dtype):
        self._clear_cached_op()
        super().cast(dtype)

    def infer_shape(self, *args):
        """Infer deferred parameter shapes from inputs.

        Built-in layers override this; custom blocks with deferred-shape
        parameters must too (divergence from the reference's symbolic
        whole-graph inference — see module docstring)."""
        if any(p._deferred_init for p in self._reg_params.values()):
            raise MXTPUError(
                f"{type(self).__name__} has deferred-shape parameters but "
                "does not override infer_shape(); specify full shapes "
                "(in_units/in_channels) or implement infer_shape")

    def infer_type(self, *args):
        pass

    def _deferred_infer_and_init(self, *args):
        self.infer_shape(*args)
        for p in self._reg_params.values():
            if p._deferred_init:
                p._finish_deferred_init()

    def _get_param_arrays(self, ctx):
        try:
            return {name: p.data(ctx)
                    for name, p in self._reg_params.items()
                    if not name.startswith("_")}
        except DeferredInitializationError:
            raise

    def forward(self, x, *args):
        """Dispatch: cached-op path when hybridized, imperative otherwise."""
        if not isinstance(x, NDArray):
            import numpy as _onp
            if isinstance(x, (_onp.ndarray, _onp.generic)):
                x = ndarray.array(x)
            else:
                from ..symbol import Symbol
                if isinstance(x, Symbol):
                    return self._symbolic_forward(x, *args)
                raise TypeError(
                    f"HybridBlock input must be NDArray, got {type(x)}")
        if args and any(isinstance(a, _np_types()) for a in args):
            args = tuple(ndarray.array(a) if isinstance(a, _np_types())
                         else a for a in args)
        if self._active:
            if self._cached_op is None:
                from ..cached_op import CachedOp
                self._cached_op = CachedOp(self, self._flags)
            return self._cached_op(x, *args)
        return self._imperative_forward(x, *args)

    def _imperative_forward(self, x, *args):
        """The un-cached forward path (also the trace body under jit)."""
        ctx = x.context
        try:
            params = self._get_param_arrays(ctx)
        except DeferredInitializationError:
            self._deferred_infer_and_init(x, *args)
            params = self._get_param_arrays(ctx)
        return self.hybrid_forward(ndarray, x, *args, **params)

    def _symbolic_forward(self, x, *args):
        from .. import symbol
        params = {name: p.var() for name, p in self._reg_params.items()
                  if not name.startswith("_")}
        return self.hybrid_forward(symbol, x, *args, **params)

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    def export(self, path, epoch=0):
        """Export model to ``path-symbol.json`` + ``path-%04d.params``
        (parity: HybridBlock.export; loadable by SymbolBlock.imports)."""
        from ..cached_op import export_block
        return export_block(self, path, epoch)

    def optimize_for(self, x, *args, backend=None, **kwargs):
        # subgraph backends (oneDNN/TRT) have no TPU analogue; XLA is the
        # whole-graph compiler. Accept and hybridize.
        self.hybridize(True)
        return self(x, *args)


class SymbolBlock(HybridBlock):
    """Build a block from a saved symbolic graph (parity: gluon.SymbolBlock).

    Construct via SymbolBlock.imports(symbol_file, input_names, param_file).
    The jaxpr-backed symbol program replays through mxtpu.symbol.
    """

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix=None, params=None)
        from .. import symbol as _sym

        if isinstance(outputs, (list, tuple)) and len(outputs) == 1:
            outputs = outputs[0]
        if not isinstance(inputs, (list, tuple)):
            inputs = [inputs]
        self._sym_outputs = outputs
        self._sym_inputs = inputs
        input_names = {i.name for i in inputs}
        # register every non-input graph argument as a parameter
        for name in outputs.list_arguments():
            if name not in input_names:
                p = Parameter(name, allow_deferred_init=True)
                self._params._params[name] = p
        for name in outputs.list_auxiliary_states():
            p = Parameter(name, grad_req="null", allow_deferred_init=True)
            self._params._params[name] = p
        if params is not None:
            for name, arr in params.items():
                clean = name.replace("arg:", "").replace("aux:", "")
                if clean in self._params:
                    p = self._params[clean]
                    # adopt the stored dtype — int8 quantized weights
                    # must NOT be silently upcast to the fp32 default
                    p.dtype = arr.dtype
                    p._load_init(arr, None)

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from .. import symbol as _sym
        from ..ndarray import serialization

        sym = _sym.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [_sym.var(n) for n in input_names]
        params = serialization.load(param_file) if param_file else None
        ret = SymbolBlock(sym, inputs, params)
        if ctx is not None:
            ret.collect_params().reset_ctx(ctx)
        return ret

    def forward(self, x, *args):
        from .. import symbol as _sym

        args_map = {}
        for inp, val in zip(self._sym_inputs, (x,) + args):
            args_map[inp.name] = val
        for name, p in self._params.items():
            if p._data is not None:
                args_map[name] = p.data(x.context)
        outs = self._sym_outputs.eval(**args_map)
        if isinstance(outs, (list, tuple)) and len(outs) == 1:
            return outs[0]  # single-output symbols yield one NDArray
        return outs

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError  # forward is overridden


def _indent(s, num_spaces):
    lines = s.split("\n")
    if len(lines) == 1:
        return s
    first = lines.pop(0)
    return first + "\n" + "\n".join(" " * num_spaces + line for line in lines)


def _brief_print(d):
    keys = sorted(d.keys())
    if len(keys) > 10:
        keys = keys[:10] + ["..."]
    return ", ".join(keys)


def _np_types():
    import numpy as _onp
    return (_onp.ndarray, _onp.generic)
