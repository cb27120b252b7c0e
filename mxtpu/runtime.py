"""Runtime feature detection (parity: python/mxnet/runtime.py over
src/libinfo.cc EnumerateFeatures).

The reference reports compile-time flags (CUDA, CUDNN, MKLDNN, …); here
features reflect the live jax backend (TPU presence, platform version,
pallas availability, distributed init state).
"""

from __future__ import annotations

import collections
import os

import jax

__all__ = ["Feature", "feature_list", "Features", "enable_compile_cache"]


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache and return its
    directory.  For entry points only (chip_smoke.py, chipbench, the
    serving worker, tools/) — never at ``import mxtpu`` and never from
    tests.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
    itself and the cache is there and nowhere else; otherwise it is
    ``<checkout>/.jax_cache``, fixed by the package's location because
    the path is part of the cache key — a directory that moves never
    hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

Feature = collections.namedtuple("Feature", ["name", "enabled"])


def _detect():
    feats = {}

    def add(name, enabled):
        feats[name] = Feature(name, bool(enabled))

    platforms = set()
    try:
        platforms = {d.platform for d in jax.devices()}
    except Exception:
        pass
    add("TPU", any(p not in ("cpu",) for p in platforms))
    add("CPU", True)
    add("CUDA", False)          # parity names from libinfo: not this stack
    add("CUDNN", False)
    add("MKLDNN", False)
    add("XLA", True)
    add("PALLAS", True)         # ships with the one JAX this repo runs on
    add("BF16", True)
    add("INT64_TENSOR_SIZE", True)
    add("DIST_KVSTORE", True)   # dist_tpu_sync (jax.distributed)
    add("SIGNAL_HANDLER", False)
    add("PROFILER", True)
    add("OPENCV", _has_cv2())
    return feats


def _has_cv2():
    try:
        import cv2  # noqa: F401
        return True
    except Exception:
        return False


class Features(collections.OrderedDict):
    """Map of runtime features (parity: mx.runtime.Features)."""

    instance = None

    def __new__(cls):
        if cls.instance is None:
            cls.instance = super().__new__(cls)
            collections.OrderedDict.__init__(cls.instance, _detect())
        return cls.instance

    def __init__(self):
        pass

    def __repr__(self):
        return "[%s]" % ", ".join(
            "✔ %s" % n if f.enabled else "✖ %s" % n
            for n, f in self.items())

    def is_enabled(self, feature_name):
        feature_name = feature_name.upper()
        if feature_name not in self:
            raise RuntimeError(f"Feature '{feature_name}' is unknown")
        return self[feature_name].enabled


def feature_list():
    """(parity: runtime.feature_list)"""
    return list(Features().values())
