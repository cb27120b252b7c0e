"""mxtpu operator library.

TPU-native replacement for the reference's ~350k-LoC ``src/operator/**``
(NNVM-registered C++/CUDA kernels, cuDNN/oneDNN glue, mshadow expression
templates).  Here every operator is a pure function over jax arrays: XLA is
the kernel library and the fusion engine, so an "operator" is just the
semantic definition.  Hot paths that XLA cannot fuse well (flash attention)
get Pallas kernels under mxtpu/ops/pallas/.

Importing this package populates the registry (mxtpu.base._OP_REGISTRY) from
which the ``mx.nd.*`` namespace is generated — mirroring how the reference
generates Python op stubs from the C registry at import time
(python/mxnet/ndarray/register.py _init_ndarray_module).
"""

from . import tensor  # noqa: F401
from . import nn  # noqa: F401
from . import contrib  # noqa: F401
from . import control_flow  # noqa: F401
from . import custom  # noqa: F401
from . import moe  # noqa: F401
from . import random_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import pallas  # noqa: F401
from . import dsa  # noqa: F401
from . import short_conv  # noqa: F401
