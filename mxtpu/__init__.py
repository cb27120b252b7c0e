"""mxtpu — a TPU-native deep-learning framework with Apache MXNet 1.x's
capabilities (reference: jlcontreras/incubator-mxnet), built on JAX/XLA/
Pallas rather than ported from the reference's C++/CUDA engine.

Import surface mirrors ``import mxnet as mx``:

    import mxtpu as mx
    x = mx.nd.ones((2, 3), ctx=mx.tpu(0))
    with mx.autograd.record():
        y = (x * 2).sum()
    y.backward()

See SURVEY.md for the architecture map against the reference.
"""

from time import perf_counter_ns as _now
_t0 = _now()    # before the first import: where ``process.start`` ends
import sys as _sys
_jax_imported = "jax" in _sys.modules

from .observability import trace as _trace
_import = _trace.package_import(_t0, _jax_imported)     # mxtpu.import, open
from . import base
from .context import Context, cpu, cpu_pinned, gpu, tpu, num_gpus, num_tpus, current_context
from . import engine
from . import random
from . import ndarray
from . import ndarray as nd
from . import autograd
from .ndarray import NDArray

_trace.attach_jax()     # jax is imported by now: annotations, xla.compile

__version__ = "0.1.0"

# Subpackages that pull heavier deps load lazily via attribute access.
_LAZY = {
    "gluon": ".gluon",
    "optimizer": ".optimizer",
    "lr_scheduler": ".optimizer.lr_scheduler",
    "initializer": ".initializer",
    "init": ".initializer",
    "metric": ".metric",
    "kvstore": ".kvstore",
    "kv": ".kvstore",
    "io": ".io",
    "image": ".image",
    "recordio": ".recordio",
    "profiler": ".profiler",
    "runtime": ".runtime",
    "callback": ".callback",
    "monitor": ".monitor",
    "visualization": ".visualization",
    "symbol": ".symbol",
    "sym": ".symbol",
    "analysis": ".analysis",
    "module": ".module",
    "mod": ".module",
    "model": ".model",
    "parallel": ".parallel",
    "serving": ".serving",
    "amp": ".amp",
    "test_utils": ".test_utils",
    "util": ".util",
    "np": ".numpy",
    "numpy": ".numpy",
    "npx": ".numpy_extension",
    "numpy_extension": ".numpy_extension",
    "contrib": ".contrib",
    "preemption": ".preemption",
    "resilience": ".resilience",
    "operator": ".operator",
    "horovod": ".horovod",
}


def __getattr__(name):
    import importlib

    target = _LAZY.get(name)
    if target is None:
        raise AttributeError(f"module 'mxtpu' has no attribute {name!r}")
    with _trace.importing(__name__ + target):
        mod = importlib.import_module(target, __name__)
    globals()[name] = mod
    return mod


def __dir__():
    return sorted(list(globals()) + list(_LAZY))


_import.__exit__(None, None, None)      # the package's mxtpu.import ends
del _now, _t0, _sys, _jax_imported, _import
