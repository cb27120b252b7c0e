"""One metrics registry across serving and training.

Telemetry used to be scattered over five uncoordinated surfaces —
engine ``stats``, ``resilience.counters()``, the CompileLedger,
gateway/router/supervisor stats, guardian counters — that
``tools/diagnose.py`` hand-stitched.  The
:class:`MetricsRegistry` is the one collection point: named SOURCES
(callables returning nested dicts) are pulled LAZILY at
:meth:`~MetricsRegistry.snapshot` time and flattened into a single
``{"source.key.subkey": number}`` dict, with :meth:`~MetricsRegistry.
delta` for before/after reads and Prometheus-text + JSON exposition.

Built-in sources of the process registry (:func:`get_registry`):

==================  ====================================================
source              pulls
==================  ====================================================
``resilience``      :func:`mxtpu.resilience.counters` (process-wide
                    fault/retry/quarantine/guardian counters)
``compile_ledger``  per-site compiled-program counts from the
                    :class:`~mxtpu.analysis.compile_ledger.CompileLedger`
                    (``compile_ledger.<site>.programs`` — the key shape
                    the O001 obs_check pass cross-checks)
``engine_bulk``     :func:`mxtpu.engine.bulk_stats` (segment cache)
``profiler``        :func:`mxtpu.profiler.counter_values` (the parity
                    Counter API's values — ``profiler.dumps`` reads
                    them back through this registry)
``tracer``          :meth:`~mxtpu.observability.trace.Tracer.stats`
``flight``          :meth:`~mxtpu.observability.flight.FlightRecorder
                    .stats`
``kernel_invocations``  :func:`mxtpu.ops.pallas.counters.counts` —
                    trace-time Pallas kernel invocation counters
                    (``kernel_invocations.<kernel_name>``)
``remat``           :func:`mxtpu.ops.remat.counts` — what the units
                    of recomputation kept beside their inputs in the
                    newest program traced under ``remat_scope``
                    (``remat.kept_outputs``, ``remat.kept_bytes``)
``lifecycle``       page-sanitizer shadow-accounting stats from the
                    serving-lifecycle pass (``lifecycle.armed``,
                    ``lifecycle.pages_tracked``,
                    ``lifecycle.violations_ever`` — see
                    ``analysis/lifecycle_check.py``)
``moe``             load of every live expert layer that holds a share
                    of the experts (``moe.<layer>.held.<i>``,
                    ``.elsewhere``, ``.held_sum.<i>``,
                    ``.elsewhere_sum`` — ``ExpertShare``)
``mtp``             the multi-token-prediction module's counters
                    (``mtp.positions``, ``mtp.loss_sum``,
                    ``mtp.main_loss_sum`` — ``PredictionModule``)
``dsa``             the indexed-attention layers' counters
                    (``dsa.selected_pairs``, ``dsa.kl_sum``,
                    ``dsa.<layer>.kept_mean`` — ``IndexedAttention``)
``conv``            the gated short convolutions' counter
                    (``conv.positions`` — ``ShortConv``); these four
                    read a few numbers a block from the device, from
                    the one registry of models/parts.py
                    (``read_counters``)
==================  ====================================================

Live objects (engines, gateways, supervisors, routers) register with
:meth:`~MetricsRegistry.register_stats`, which accepts anything with a
``stats`` property/method; unregister when the object retires.  All
values are numbers (bools coerce to 0/1); non-numeric leaves and
non-string keys are skipped during flattening.

Determinism: a snapshot is plain host counters — two runs of the same
seed + fault plan produce identical deltas, which is what lets bench
records cite registry deltas as evidence instead of wall clocks.
"""

from __future__ import annotations

import functools
import json
import re
from typing import Any, Callable, Dict, Iterable, List, Optional

__all__ = ["MetricsRegistry", "get_registry", "default_registry"]


def _flatten(prefix: str, value: Any, out: Dict[str, float]) -> None:
    if isinstance(value, dict):
        for k, v in value.items():
            if isinstance(k, str):
                _flatten(prefix + "." + k, v, out)
        return
    if isinstance(value, bool):
        out[prefix] = int(value)
    elif isinstance(value, (int, float)):
        out[prefix] = value
    # non-numeric leaves (status strings, error records) are observable
    # through the owning object's own API; the registry is numeric


class MetricsRegistry:
    """Named lazy sources -> one flat numeric snapshot (module
    docstring)."""

    def __init__(self):
        self._sources: Dict[str, Callable[[], dict]] = {}

    # -- registration ----------------------------------------------------
    def register_source(self, name: str, fn: Callable[[], dict],
                        replace: bool = False) -> None:
        """Register ``fn() -> nested dict`` under ``name``.  Sources
        evaluate lazily at snapshot time; a raising source contributes
        one ``<name>.source_error = 1`` key instead of killing the
        snapshot (telemetry must never take the service down)."""
        if name in self._sources and not replace:
            raise ValueError(
                "metrics source %r already registered (pass "
                "replace=True to swap it)" % (name,))
        if not callable(fn):
            raise TypeError("metrics source must be a callable "
                            "returning a dict, got %r" % (fn,))
        self._sources[name] = fn

    def register_stats(self, name: str, obj: Any,
                       replace: bool = False) -> None:
        """Register a live object exposing ``stats`` (property, method,
        or plain dict attribute) — engines, gateways, supervisors,
        routers."""
        if not hasattr(obj, "stats"):
            raise TypeError(
                "register_stats needs an object with a `stats` "
                "property/method, got %r" % (type(obj).__name__,))

        def _pull(o=obj):
            st = o.stats
            return st() if callable(st) else st

        self.register_source(name, _pull, replace=replace)

    def unregister(self, name: str) -> None:
        self._sources.pop(name, None)

    def sources(self) -> List[str]:
        return sorted(self._sources)

    # -- collection ------------------------------------------------------
    def snapshot(self, sources: Optional[Iterable[str]] = None
                 ) -> Dict[str, float]:
        """One flat ``{"source.key": number}`` dict over the selected
        (default: all) sources, pulled lazily now."""
        names = self.sources() if sources is None else list(sources)
        out: Dict[str, float] = {}
        for name in names:
            fn = self._sources.get(name)
            if fn is None:
                raise KeyError(
                    "unknown metrics source %r (registered: %r)"
                    % (name, self.sources()))
            try:
                val = fn()
            except Exception:  # noqa: BLE001 — a broken source must
                out[name + ".source_error"] = 1   # not kill telemetry
                continue
            _flatten(name, val if isinstance(val, dict) else
                     {"value": val}, out)
        return out

    def delta(self, before: Dict[str, float],
              after: Optional[Dict[str, float]] = None,
              include_zero: bool = False) -> Dict[str, float]:
        """``after - before`` per key (``after`` defaults to a fresh
        snapshot).  Keys absent from ``before`` count from 0; keys
        absent from ``after`` are dropped (their object retired)."""
        if after is None:
            after = self.snapshot()
        out = {}
        for k, v in after.items():
            d = v - before.get(k, 0)
            if d or include_zero:
                out[k] = d
        return out

    # -- exposition ------------------------------------------------------
    @staticmethod
    def _prom_name(key: str) -> str:
        return "mxtpu_" + re.sub(r"[^a-zA-Z0-9_]", "_", key)

    def to_prometheus(self,
                      snapshot: Optional[Dict[str, float]] = None) -> str:
        """Prometheus text exposition (all gauges — these are live
        counters/levels read at scrape time)."""
        snap = self.snapshot() if snapshot is None else snapshot
        lines = []
        for key in sorted(snap):
            name = self._prom_name(key)
            lines.append("# TYPE %s gauge" % name)
            val = snap[key]
            lines.append("%s %s" % (
                name, ("%d" % val) if isinstance(val, int)
                else repr(float(val))))
        return "\n".join(lines) + "\n"

    def to_json(self, snapshot: Optional[Dict[str, float]] = None,
                indent: Optional[int] = None) -> str:
        snap = self.snapshot() if snapshot is None else snapshot
        return json.dumps(snap, sort_keys=True,
                          separators=(",", ":"), indent=indent)


# -- built-in sources ----------------------------------------------------

def _src_resilience() -> dict:
    from ..resilience.counters import counters
    return counters()


def _src_compile_ledger() -> dict:
    from ..analysis.compile_ledger import get_ledger
    out: Dict[str, dict] = {}
    for site, s in get_ledger().stats().items():
        out[site] = {"programs": s["misses"], "hits": s["hits"],
                     "lookups": s["lookups"]}
    return out


def _src_engine_bulk() -> dict:
    from .. import engine
    return engine.bulk_stats()


def _src_profiler() -> dict:
    from .. import profiler
    return {k: v for k, v in profiler.counter_values().items()
            if isinstance(v, (int, float))}


def _src_tracer() -> dict:
    from .trace import get_tracer
    return get_tracer().stats()


def _src_flight() -> dict:
    from .flight import get_flight
    return get_flight().stats()


def _src_lifecycle() -> dict:
    """Page-sanitizer shadow-accounting stats from the lifecycle pass
    (``lifecycle.armed``, ``lifecycle.pages_tracked``,
    ``lifecycle.violations_ever`` ...) — all plain host ints, so a
    scrape never arms or perturbs the sanitizer
    (analysis/lifecycle_check.py)."""
    from ..analysis.lifecycle_check import get_sanitizer
    return get_sanitizer().stats()


def _src_kernel_invocations() -> dict:
    """Pallas kernel trace-time invocation counters: one bump per
    pallas_call traced into a compiled program, keyed by kernel name
    (``kernel_invocations.paged_attention`` etc.) — the counter that
    proves the fast path is actually riding the kernel, not the XLA
    fallback (ops/pallas/counters.py)."""
    from ..ops.pallas import counters
    return counters.counts()


def _src_remat() -> dict:
    """What the units of recomputation kept beside their inputs in the
    newest program traced under ``remat_scope``: ``remat.kept_outputs``
    values an op marked and ``remat.kept_bytes`` their bytes, as the
    units' checkpoint policy ruled while the backward pass was traced
    (ops/remat.py)."""
    from ..ops import remat
    return remat.counts()


#: the sources of the counters that the models' blocks keep on the
#: device and the training step hands back (models/parts.py)
_COUNTED_SOURCES = ("moe", "mtp", "dsa", "conv")


def _src_counted(source: str) -> dict:
    """Every live block counted under ``source``, read now
    (models/parts.py ``read_counters``; module docstring for the keys),
    a list's entries keyed by their index."""
    from ..models.parts import read_counters

    def keyed(value):
        if isinstance(value, dict):
            return {k: keyed(v) for k, v in value.items()}
        if isinstance(value, list):
            return {str(i): x for i, x in enumerate(value)}
        return value
    return keyed(read_counters(source))


def default_registry() -> MetricsRegistry:
    """A fresh registry pre-loaded with the built-in process-wide
    sources (module docstring table)."""
    reg = MetricsRegistry()
    reg.register_source("resilience", _src_resilience)
    reg.register_source("compile_ledger", _src_compile_ledger)
    reg.register_source("engine_bulk", _src_engine_bulk)
    reg.register_source("profiler", _src_profiler)
    reg.register_source("tracer", _src_tracer)
    reg.register_source("flight", _src_flight)
    reg.register_source("kernel_invocations", _src_kernel_invocations)
    reg.register_source("remat", _src_remat)
    reg.register_source("lifecycle", _src_lifecycle)
    for source in _COUNTED_SOURCES:
        reg.register_source(source, functools.partial(_src_counted, source))
    return reg


_REGISTRY = default_registry()


def get_registry() -> MetricsRegistry:
    """The process-wide registry (built-in sources pre-registered; add
    live engines/gateways with :meth:`MetricsRegistry.register_stats`)."""
    return _REGISTRY
