"""Device context (parity: python/mxnet/context.py, include/mxnet/base.h Context).

In the reference a Context names a CUDA device and every NDArray/op carries
one; the threaded engine owns one worker + stream set per context
(src/engine/threaded_engine_perdevice.cc).  On TPU the executor is PJRT: a
Context here resolves to a ``jax.Device``.  ``mx.gpu(i)`` is aliased to the
accelerator backend (TPU) so reference user code runs unchanged; ``mx.cpu()``
maps to the JAX CPU backend.  Asking for an accelerator that is not there
raises :class:`DeviceNotFoundError`, as ``mx.gpu(i)`` does in the reference
on a host without that GPU; only the lazy *default* context picks the CPU
on a CPU-only host, because nobody asked it for anything else.
"""

from __future__ import annotations

import threading
from typing import Optional

import jax

from .base import MXTPUError

__all__ = ["Context", "DeviceNotFoundError", "cpu", "gpu", "tpu",
           "cpu_pinned", "num_gpus", "num_tpus", "current_context"]


class DeviceNotFoundError(MXTPUError):
    """An accelerator context names a device this process does not have."""


class Context:
    """A device context.  devtype 'cpu' or 'tpu' ('gpu' accepted as alias)."""

    devtype2str = {1: "cpu", 2: "tpu", 3: "cpu_pinned"}
    devstr2type = {"cpu": 1, "tpu": 2, "gpu": 2, "cuda": 2, "cpu_pinned": 3}

    _default_ctx = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        self._jax_device = None

    @property
    def device_type(self) -> str:
        return Context.devtype2str[self.device_typeid]

    def __hash__(self):
        # via device_type property so the lazy default resolves first
        return hash((self.device_type, self.device_id))

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __str__(self):
        return f"{self.device_type}({self.device_id})"

    __repr__ = __str__

    # -- jax resolution -------------------------------------------------
    def to_jax_device(self) -> Optional["jax.Device"]:
        """Resolve lazily to a jax.Device (None = let jax use its default)."""
        if self._jax_device is not None:
            return self._jax_device
        if self.device_typeid in (1, 3):  # cpu / cpu_pinned
            # cpu ids carry no meaning in the reference either
            devs = _devices_for("cpu")
            if not devs:
                return None
            self._jax_device = devs[min(self.device_id, len(devs) - 1)]
            return self._jax_device
        devs = _accel_devices()
        if not 0 <= self.device_id < len(devs):
            raise DeviceNotFoundError(
                "%s was asked for, but this process has %d accelerator "
                "device(s)%s (jax platform: %s)"
                % (self, len(devs),
                   " " + ", ".join(str(d) for d in devs) if devs else "",
                   jax.default_backend()))
        self._jax_device = devs[self.device_id]
        return self._jax_device

    def __enter__(self):
        if not hasattr(Context._default_ctx, "stack"):
            Context._default_ctx.stack = []
        Context._default_ctx.stack.append(self)
        return self

    def __exit__(self, *a):
        Context._default_ctx.stack.pop()

    @classmethod
    def default_ctx(cls) -> "Context":
        stack = getattr(cls._default_ctx, "stack", None)
        if stack:
            return stack[-1]
        return _DEFAULT


_platform_cache: dict = {}


def _devices_for(platform: str):
    """Process-LOCAL devices: in a multi-process (jax.distributed) world a
    Context must name a device this worker can address, like the reference
    where each worker owns its local GPUs."""
    if platform not in _platform_cache:
        try:
            _platform_cache[platform] = jax.local_devices(backend=platform)
        except RuntimeError:
            _platform_cache[platform] = []
    return _platform_cache[platform]


_accel_cache = None


def _accel_devices():
    """Local non-CPU jax devices (TPU in production; empty on CPU-only
    hosts)."""
    global _accel_cache
    if _accel_cache is None:
        _accel_cache = [d for d in jax.local_devices()
                        if d.platform != "cpu"]
    return _accel_cache


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Alias: reference scripts using mx.gpu(i) land on TPU chip i."""
    return Context("tpu", device_id)


def num_tpus() -> int:
    return len(_accel_devices())


def num_gpus() -> int:
    """Parity alias for mx.context.num_gpus()."""
    return num_tpus()


def current_context() -> Context:
    return Context.default_ctx()


# Default context: accelerator if present else cpu — chosen at first use so
# importing mxtpu never forces backend init.
class _LazyDefault(Context):
    def __init__(self):
        super().__init__("cpu", 0)
        self._resolved = False

    def _resolve(self):
        if not self._resolved:
            self.device_typeid = 2 if _accel_devices() else 1
            self._resolved = True

    @property
    def device_type(self):
        self._resolve()
        return Context.devtype2str[self.device_typeid]

    def to_jax_device(self):
        self._resolve()
        return super().to_jax_device()


_DEFAULT = _LazyDefault()
