"""Deterministic structured tracing: typed spans/events on a tick clock,
with one wall clock beside it.

The reference MXNet's engine-integrated profiler stamps every engine op
with wall-clock timestamps and emits chrome://tracing JSON.  At serving
scale the question a trace must answer — "which replica/tier/fault ate
my latency?" — has to be answerable from telemetry that REPLAYS: this
tracer therefore stamps every event with a process-wide COUNTER tick,
so the trace of a seeded run under a fault plan is bit-reproducible and
assertable in tier-1 (the same discipline as
``mxtpu.resilience.faults``).  Beside the tick every event carries
``t_ns = time.perf_counter_ns()`` and ``parent`` (the begin tick of the
span open on the emitting thread).  ``t_ns`` is NOISE: it and
``parent`` stay out of the deterministic serialization and ride only
under ``include_noise=True``.

Off by default.  Enable with ``MXTPU_TRACE=1`` (ambient, read once at
tracer construction) or the :func:`tracing` context manager.

Boundary spans (:data:`BOUNDARY_TYPES`: one per trainer step, engine
iteration or phase of one — never one per token or request) are kept
ALWAYS, tracer enabled or not, in a bounded ring of finished spans
(:meth:`Tracer.boundary_spans`), and open a
``jax.profiler.TraceAnnotation("mxtpu.<type>")`` whenever a profiler
session runs, however it was started.  XLA compilations land in the
same ring as ``xla.compile`` spans (:func:`attach_jax`), and so does what
the process did before its first step: ``process.start`` and
``mxtpu.import`` (:func:`package_import`, :func:`importing`; they
describe the process, so :meth:`Tracer.reset` keeps them) and
``block.initialize``.  ``perf_counter_ns`` is the clock a
benchmark's own host spans use, so a reader lays the ring on a device
trace by shifting with one span both sides hold.

Event taxonomy (:data:`EVENT_TYPES`): every event carries a registered
type — an unregistered type raises at the emit site, and the
``obs_check`` analysis pass (O001, docs/analysis.md) cross-checks that
every declared fault site in ``resilience.faults.SITES`` has its
``fault.<site>`` type registered here, so observability coverage is
lost loudly, never silently.

Correlation ids: events carry an optional ``rid`` string threaded along
the existing rid <-> tag maps — engines emit ``"<tag>:<rid>"`` (tag =
``ledger_tag`` or ``"eng"``; replica pools stamp the replica id), the
gateway emits ``"gw:<rid>"``, and the transport registers an ALIAS from
the engine id to the gateway id at submit, so one request's events from
every layer assemble into one :meth:`Tracer.timeline`.

Determinism contract: with the tracer reset at the start of a run, the
same seeds + the same ``MXTPU_FAULT_PLAN`` produce a byte-identical
:meth:`Tracer.to_json` (asserted in tests/test_observability.py), and
tracing compiles ZERO additional programs — every emit is host-side
bookkeeping (asserted via the compile ledger).

This module must stay import-light (no jax at import time): the serving
and resilience hot paths import it unconditionally.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
from collections import deque
from time import perf_counter_ns
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

__all__ = ["TraceEvent", "Span", "Tracer", "get_tracer", "tracing",
           "gateway_rid", "EVENT_TYPES", "BOUNDARY_TYPES",
           "export_chrome_trace", "attach_jax", "package_import",
           "importing"]


#: alias entries (engine-rid -> gateway-rid) kept for at most this many
#: child ids; the oldest-registered is evicted past it.  One alias lands
#: per submitted request, so the always-on serving posture (ambient
#: MXTPU_FLIGHT_BUFFER, tracer never reset) would otherwise grow the
#: map without bound — the same bounded-bookkeeping discipline as the
#: flight recorder's request rings.
MAX_ALIASES = 8192

#: finished boundary spans kept (oldest evicted past it): at a hundred
#: engine iterations a second and seven spans an iteration, a minute
MAX_BOUNDARY_SPANS = 65536

#: events a tracer keeps in memory (further ones are counted in
#: ``dropped_events``); no knob, as the ring has none
MAX_EVENTS = 200000

#: the registered span/event taxonomy: type -> one-line description
#: (docs/observability.md mirrors this table).  ``fault.<site>`` types
#: are declared EXPLICITLY (not derived from ``faults.SITES``) so the
#: O001 cross-check can catch a site added without its event type.
EVENT_TYPES: Dict[str, str] = {
    # -- gateway (mxtpu.serving.gateway) --------------------------------
    "gateway.admit": "request accepted into the gateway queue (QoS "
                     "class, tenant); queue wait = dispatch tick delta",
    "gateway.shed": "request shed (QoS overflow / quota / engine shed)",
    "gateway.dispatch": "request dispatched to a replica (gen, replica, "
                        "wait_ticks)",
    "gateway.hedge": "hedged duplicate dispatch fired",
    "gateway.requeue": "dispatch lost (replica death/stall) — stream "
                       "reset, request requeued at class front",
    "gateway.expired": "tick deadline passed; finished with partial "
                       "stream",
    "gateway.finish": "terminal gateway status (ok/failed)",
    "gateway.pump": "one gateway service iteration (boundary span)",
    # -- router / transport ---------------------------------------------
    "router.dispatch": "replica selected (locality score, chosen "
                       "replica, load)",
    "transport.submit": "spec handed to a replica engine (aliases the "
                        "engine rid to the gateway rid)",
    "transport.worker_spawn": "subprocess replica worker started and "
                              "completed its init handshake (pid is "
                              "noise — see docs/serving.md)",
    "transport.worker_exit": "subprocess replica worker left the pool "
                             "(graceful shutdown, kill, or reaped "
                             "death; exit code when reapable)",
    "transport.rpc_timeout": "a replica RPC exhausted its tick budget "
                             "(method, ticks) — counted toward "
                             "replica death as a transport failure",
    "replica.death": "supervisor declared a replica dead "
                     "(drain-and-requeue)",
    "replica.revive": "probation over — replica re-admitted (a "
                      "subprocess replica respawned a fresh worker "
                      "first)",
    # -- elastic serving (mxtpu.serving.autoscale) ----------------------
    "autoscale.decision": "one autoscaler policy evaluation that acted "
                          "(direction, shed delta, queue depth, pool "
                          "size)",
    "autoscale.spawn": "autoscaler grew the pool by one replica (or "
                       "failed to — error field; capacity unchanged)",
    "autoscale.retire": "graceful scale-down lifecycle (stage: begin/"
                        "released/reopened) — the victim drains at "
                        "stream completion, never the death path",
    "serving.adopt": "live weight hot-swap lifecycle (stage: staged/"
                     "installed/failed) — new param generation adopted "
                     "at an iteration boundary",
    "serving.rollback": "previous param generation re-staged "
                        "(hot-swap rollback)",
    # -- engines (mxtpu.parallel.serving) -------------------------------
    "engine.iteration": "one engine scheduler iteration (boundary "
                        "span; the end carries the iteration's counts: "
                        "decoding, prefilling, waiting, tokens, "
                        "prefill_tokens)",
    "engine.schedule": "eviction, adoption and the admission loop of "
                       "one iteration (boundary span)",
    "engine.prefill": "the chunked-prefill loop of one iteration, or "
                      "an admission's first chunk inside its "
                      "engine.schedule (boundary span)",
    "engine.decode_step": "the pooled decode / verify step of one "
                          "iteration (boundary span)",
    "engine.host_read": "one blocking read of a device value by the "
                        "engine's host loop, or (site=replica.poll) the "
                        "in-process replica's drain of the new tokens, "
                        "which waits for the step just dispatched "
                        "(boundary span)",
    "engine.admit": "admission started (prompt tokens)",
    "engine.prefix_hit": "radix/host-tier prefix hit (tokens, pages "
                         "shared — prefill skipped)",
    "engine.cow": "copy-on-write page clone at the divergence point",
    "engine.swap_in": "host-tier chain restored at admission (pages)",
    "engine.swap_out": "pinned chain spilled to the host tier (pages; "
                       "dropped=True when the copy was abandoned)",
    "engine.defer": "admission deferred on transient page exhaustion",
    "engine.prefill_chunk": "one chunked-prefill program ran for a "
                            "prefilling slot",
    "engine.decode": "slot emitted one token in the pooled decode step",
    "engine.draft": "speculative proposal drafted for a slot",
    "engine.verify": "slot scored in the pooled batched-verify call "
                     "(drafted, accepted)",
    "engine.finish": "request terminal in the engine "
                     "(ok/failed/expired/cancelled)",
    "engine.quarantine": "per-slot failure contained (site, error)",
    "engine.requeue": "quarantined request re-queued (retries left)",
    "engine.shed": "submission shed (typed LoadShedError)",
    "engine.cancel": "request cancelled through the idempotent release "
                     "path",
    # -- guardian (mxtpu.resilience.guardian) ---------------------------
    "guardian.skip": "non-finite step contained (update gated off)",
    "guardian.spike": "finite loss spike detected -> rollback",
    "guardian.rollback": "restored the last verified checkpoint",
    "guardian.checkpoint": "verified checkpoint written",
    "guardian.window": "one fused N-step window dispatched (the "
                       "once-per-N host sync)",
    # -- start-up (mxtpu/__init__.py, mxtpu.gluon) ----------------------
    "process.start": "from the kernel's record of the process's start "
                     "to the first line of mxtpu/__init__.py: the "
                     "interpreter and whatever the caller did before it "
                     "imported the package (ring only, once, kept by a "
                     "reset: jax_imported, backend_up)",
    "mxtpu.import": "the import of the package, or of a lazy subpackage "
                    "through mxtpu.__getattr__ (ring only, kept by a "
                    "reset: module; an import inside an import is its "
                    "child)",
    "block.initialize": "the outermost Block / ParameterDict / "
                        "Parameter.initialize call on a thread "
                        "(boundary span; params and bytes materialised "
                        "on the end)",
    # -- SPMDTrainer (mxtpu.parallel.trainer) ---------------------------
    "trainer.stage": "the eager shape-resolving forward and the staging "
                     "of parameters and optimizer state onto the mesh "
                     "(boundary span, once per trainer; the device's "
                     "bytes_in_use, peak_bytes_in_use on the end)",
    "trainer.step": "one SPMDTrainer.step / step_window call as the "
                    "host sees it: dispatch, not device time (boundary "
                    "span; step, first, tokens on the end, and where "
                    "first the device's bytes_in_use, peak_bytes_in_use)",
    # -- XLA (jax.monitoring, attach_jax) -------------------------------
    "xla.compile": "one trace / lower / compile / cache_fetch of a "
                   "program, eager ones included (ring only: seconds, "
                   "kind, name, on a compile fetched; parent = the span "
                   "open on that thread)",
    # -- profiler parity API (mxtpu.profiler) ---------------------------
    "profiler.counter": "profiler.Counter value change",
    "profiler.marker": "profiler.Marker instant",
    # -- automatic fault events (every resilience.faults site) ----------
    # one type per DECLARED site; a plan firing at an undeclared
    # (test-private) site emits fault.unregistered with a site field
    "fault.serving.step": "injected fault fired at serving.step",
    "fault.serving.admit": "injected fault fired at serving.admit",
    "fault.serving.prefix_lookup":
        "injected fault fired at serving.prefix_lookup",
    "fault.serving.block_alloc":
        "injected fault fired at serving.block_alloc",
    "fault.serving.swap_out": "injected fault fired at serving.swap_out",
    "fault.serving.swap_in": "injected fault fired at serving.swap_in",
    "fault.serving.draft": "injected fault fired at serving.draft",
    "fault.serving.verify": "injected fault fired at serving.verify",
    "fault.gateway.admit": "injected fault fired at gateway.admit",
    "fault.router.dispatch": "injected fault fired at router.dispatch",
    "fault.replica.health": "injected fault fired at replica.health",
    "fault.replica.stream": "injected fault fired at replica.stream",
    "fault.transport.rpc": "injected fault fired at transport.rpc",
    "fault.transport.encode":
        "injected fault fired at transport.encode",
    "fault.transport.worker_death":
        "injected fault fired at transport.worker_death",
    "fault.kvstore.reduce": "injected fault fired at kvstore.reduce",
    "fault.checkpoint.save": "injected fault fired at checkpoint.save",
    "fault.engine.flush": "injected fault fired at engine.flush",
    "fault.guardian.check": "injected fault fired at guardian.check",
    "fault.ckpt.write": "injected fault fired at ckpt.write",
    "fault.ckpt.verify": "injected fault fired at ckpt.verify",
    "fault.autoscale.spawn":
        "injected fault fired at autoscale.spawn",
    "fault.autoscale.retire":
        "injected fault fired at autoscale.retire",
    "fault.serving.adopt": "injected fault fired at serving.adopt",
    "fault.unregistered": "injected fault fired at a site with no "
                          "declared event type (site in fields)",
}


#: span types kept whether the tracer is enabled or not (module
#: docstring).  Few per second by construction; anything per token or
#: per request stays an instant behind ``Tracer.active``.
BOUNDARY_TYPES = frozenset((
    "block.initialize",
    "trainer.stage", "trainer.step",
    "engine.iteration", "engine.schedule", "engine.prefill",
    "engine.decode_step", "engine.host_read",
    "gateway.pump",
))

#: ring spans that describe the process, not a run: a reset keeps them
PROCESS_TYPES = frozenset(("process.start", "mxtpu.import"))

_COMPILE_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_fetch",
}


class TraceEvent(NamedTuple):
    """One recorded event.  ``tick`` is the deterministic counter clock
    (one tick per recorded event); ``phase`` is ``"I"`` (instant),
    ``"B"``/``"E"`` (span begin/end); ``noise`` holds non-deterministic
    annotations.  ``t_ns`` is ``time.perf_counter_ns()`` at the emit and
    ``parent`` the begin tick of the span open on the emitting thread;
    both, like ``noise``, are excluded from the deterministic
    serialization."""

    tick: int
    etype: str
    rid: Optional[str]
    phase: str
    fields: Dict[str, Any]
    noise: Dict[str, Any]
    t_ns: int = 0
    parent: Optional[int] = None

    def to_dict(self, include_noise: bool = False) -> Dict[str, Any]:
        d: Dict[str, Any] = {"tick": self.tick, "type": self.etype,
                             "phase": self.phase}
        if self.rid is not None:
            d["rid"] = self.rid
        if self.fields:
            d["fields"] = self.fields
        if include_noise:
            d["t_ns"] = self.t_ns
            if self.parent is not None:
                d["parent"] = self.parent
            if self.noise:
                d["noise"] = self.noise
        return d


class Span(NamedTuple):
    """One finished span of the boundary ring.  ``tick`` is its begin
    tick, the id its children name as ``parent`` (None for
    ``xla.compile``, which has no children and takes no tick);
    ``start_ns``/``end_ns`` are ``time.perf_counter_ns()``; ``fields``
    holds the begin fields and what :meth:`_Span.set` added before the
    end."""

    etype: str
    tick: Optional[int]
    parent: Optional[int]
    start_ns: int
    end_ns: int
    rid: Optional[str]
    fields: Dict[str, Any]

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


def gateway_rid(tag) -> str:
    """Correlation id of a gateway request from its dispatch tag: the
    gateway tags replica submissions ``(rid, dispatch_gen)`` — every
    generation of one request shares ONE timeline."""
    if isinstance(tag, tuple) and tag:
        return "gw:%s" % (tag[0],)
    return "gw:%s" % (tag,)


class _Span:
    """Begin/end event pair.  A boundary span (:data:`BOUNDARY_TYPES`)
    is recorded always — ring, tick and ``TraceAnnotation`` — and as
    events too while the tracer is active; any other span exists only
    while the tracer is active."""

    __slots__ = ("_tr", "_etype", "_rid", "_fields", "_end", "_noise",
                 "_ann", "_tick", "_parent", "_t0")

    def __init__(self, tracer, etype, rid, fields):
        self._tr = tracer
        self._etype = etype
        self._rid = rid
        self._fields = fields
        self._end = None
        self._noise = None
        self._ann = None
        self._tick = None       # set when the span is recorded at all

    def set(self, **fields):
        """Fields known only inside the span (an iteration's counts, the
        step number): they ride on the end event and in the ring."""
        if self._end is None:
            self._end = fields
        else:
            self._end.update(fields)
        return self

    def set_noise(self, **fields):
        """Fields that two runs of one seed do not share (what the
        device's allocator counts): in the ring like any other, on the
        end event under ``noise``, out of the deterministic bytes."""
        self._noise = dict(self._noise or {}, **fields)
        return self

    def __enter__(self):
        tr = self._tr
        active = tr._enabled or tr._sinks
        if not (active or self._etype in BOUNDARY_TYPES):
            return self
        stack = tr._stack()
        self._parent = parent = stack[-1] if stack else None
        self._t0 = t0 = perf_counter_ns()
        if active:
            tick = tr._record(self._etype, self._rid, "B", self._fields,
                              None, t0, parent).tick
        else:       # tracer off: the begin still takes a tick, the
            tr._tick = tick = tr._next_tick()   # span's id in the ring
        self._tick = tick
        stack.append(tick)
        cls = _ANNOTATION
        if cls is not None and cls.is_enabled():    # TraceMe's own test,
            # asked first: 20 ns against the 0.6 us of an idle annotation
            self._ann = cls("mxtpu." + self._etype)
            self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        tick = self._tick
        if tick is None:
            return False
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        end = perf_counter_ns()
        tr = self._tr
        stack = tr._stack()
        if stack and stack[-1] == tick:
            stack.pop()
        etype, rid, fields = self._etype, self._rid, self._fields
        if tr._enabled or tr._sinks:
            tr._record(etype, rid, "E", self._end or {}, self._noise, end,
                       self._parent)
        if etype in BOUNDARY_TYPES:
            ended = self._end
            if self._noise:
                ended = dict(ended or {}, **self._noise)
            if ended:
                fields = dict(fields, **ended) if fields else ended
            if rid is not None:
                rid = tr._alias.get(rid, rid)
            tr._ring.append((etype, tick, self._parent, self._t0, end, rid,
                             fields))
        return False


#: ``jax.profiler.TraceAnnotation`` once :func:`attach_jax` has run (this
#: module imports no jax itself).  Whichever way a profiler session was
#: started, its ``is_enabled()`` says whether one runs.
_ANNOTATION: Any = None


def _env_truthy(name: str) -> bool:
    return os.environ.get(name, "").strip().lower() not in (
        "", "0", "false", "off", "no")


class Tracer:
    """Process-wide structured tracer (module docstring).

    ``max_events`` bounds the in-memory trace (further events are
    counted in ``dropped_events``, never silently lost from the
    counters); flight-recorder sinks observe every event regardless, so
    their bounded ring buffers stay current past the cap.
    """

    def __init__(self, max_events: Optional[int] = None,
                 enabled: Optional[bool] = None):
        self._lock = threading.RLock()
        self._enabled = (_env_truthy("MXTPU_TRACE") if enabled is None
                         else bool(enabled))
        self._max_events = int(MAX_EVENTS if max_events is None
                               else max_events)
        self._events: List[TraceEvent] = []
        self._ring: deque = deque(maxlen=MAX_BOUNDARY_SPANS)
        self._local = threading.local()
        # (tick, kind, name, wall_s, t_ns)
        self._profiler_events: List[Tuple[int, str, str, float, int]] = []
        self._alias: Dict[str, str] = {}
        self._tick = 0      # the last tick handed out
        self._next_tick = itertools.count(1).__next__   # atomic, lock-free
        self._dropped = 0
        self._sinks: List[Any] = []   # flight recorders

    # -- lifecycle -------------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self._enabled

    @property
    def active(self) -> bool:
        """Whether emit() records anywhere (the tracer proper OR an
        attached flight-recorder sink) — the cheap guard every
        instrumented hot path checks first."""
        return self._enabled or bool(self._sinks)

    def enable(self, reset: bool = True) -> None:
        with self._lock:
            if reset:
                self.reset()
            self._enabled = True

    def disable(self) -> None:
        with self._lock:
            self._enabled = False

    def reset(self) -> None:
        """Clear events, the boundary ring, the tick clock, aliases, and
        the profiler channel — the start-of-run point the determinism
        contract is relative to.  The ring keeps its
        :data:`PROCESS_TYPES` spans: they tell of the process, which a
        new run does not start again."""
        with self._lock:
            self._events = []
            kept = [s for s in self._ring if s[0] in PROCESS_TYPES]
            self._ring.clear()
            self._ring.extend(kept)
            self._profiler_events = []
            self._alias = {}
            self._tick = 0
            self._next_tick = itertools.count(1).__next__
            self._dropped = 0

    # -- sinks (flight recorder) -----------------------------------------
    def add_sink(self, sink) -> None:
        with self._lock:
            if sink not in self._sinks:
                self._sinks.append(sink)

    def remove_sink(self, sink) -> None:
        with self._lock:
            if sink in self._sinks:
                self._sinks.remove(sink)

    # -- correlation -----------------------------------------------------
    def alias(self, child: str, parent: str) -> None:
        """Register ``child`` as another name of ``parent``'s timeline
        (the transport's engine-rid -> gateway-rid mapping): events
        emitted under ``child`` resolve to ``parent`` at record time."""
        with self._lock:
            if (child not in self._alias
                    and len(self._alias) >= MAX_ALIASES):
                self._alias.pop(next(iter(self._alias)))
            self._alias[child] = parent

    def resolve(self, rid: Optional[str]) -> Optional[str]:
        if rid is None:
            return None
        return self._alias.get(rid, rid)

    # -- recording -------------------------------------------------------
    def emit(self, etype: str, rid: Optional[str] = None,
             phase: str = "I", noise: Optional[dict] = None,
             **fields) -> Optional[TraceEvent]:
        """Record one typed event (no-op unless :attr:`active`).
        ``etype`` must be registered in :data:`EVENT_TYPES` — a typo
        here is a taxonomy bug and raises."""
        if not (self._enabled or self._sinks):
            return None
        stack = self._stack()
        return self._record(etype, rid, phase, fields, noise,
                            perf_counter_ns(),
                            stack[-1] if stack else None)

    def _record(self, etype, rid, phase, fields, noise, t_ns, parent):
        if etype not in EVENT_TYPES:
            raise ValueError(
                "unregistered trace event type %r — add it to "
                "mxtpu.observability.trace.EVENT_TYPES (the obs_check "
                "pass cross-checks the taxonomy)" % (etype,))
        with self._lock:
            rid = self._alias.get(rid, rid) if rid is not None else None
            self._tick = tick = self._next_tick()
            ev = TraceEvent(tick, etype, rid, phase,
                            fields, noise or {}, t_ns, parent)
            if self._enabled:
                if len(self._events) < self._max_events:
                    self._events.append(ev)
                else:
                    self._dropped += 1
            for sink in self._sinks:
                sink.observe(ev)
            return ev

    def span(self, etype: str, rid: Optional[str] = None,
             **fields) -> _Span:
        """Context manager recording a begin/end event pair inside a
        ``jax.profiler.TraceAnnotation``; ``.set(**fields)`` inside it
        adds fields to the end.  A :data:`BOUNDARY_TYPES` span is kept
        in the ring (:meth:`boundary_spans`) whether the tracer is
        enabled or not."""
        return _Span(self, etype, rid, fields)

    def _stack(self) -> List[int]:
        """Begin ticks of the spans open on the calling thread."""
        try:
            return self._local.stack
        except AttributeError:
            stack = self._local.stack = []
            return stack

    def compile_seen(self, event: str, seconds: float, fun_name=None,
                     **_) -> None:
        """The ``jax.monitoring`` duration listener: a trace, lowering,
        back-end compilation or cache fetch that just ended on this
        thread becomes an ``xla.compile`` span of the ring, its parent
        the span open here and its ``name`` jax's name of the program (a
        trace's is the function's qualified name, a lowering's and a
        compilation's the module's: ``jit(step)``).  A cache fetch lies
        inside the back-end compilation that asked for it and jax hands
        it no name: the ``compile`` span that ends next on its thread
        names it, and says of itself ``fetched`` — true where a fetch
        ended inside it, so it compiled nothing.  A reader takes the
        union of these spans, not the sum of their seconds."""
        kind = _COMPILE_KINDS.get(event)
        if kind is None:
            return
        end = perf_counter_ns()
        start = end - int(seconds * 1e9)
        ring = self._ring
        if kind in ("trace", "lower"):
            # every jit met while tracing or lowering reports its own
            # trace, by the thousand and inside this span: the outermost
            # stands for them
            while ring:
                last = ring[-1]
                if not (last[0] == "xla.compile" and last[3] >= start
                        and last[6]["kind"] == "trace"):
                    break
                if ring.pop() is not last:      # another thread's append
                    break                       # slipped in: leave it be
        fields = {"kind": kind, "seconds": seconds, "name": fun_name}
        local = self._local
        if kind == "cache_fetch":
            local.fetch = (end, fields)
        elif kind == "compile":
            fetch = getattr(local, "fetch", None)
            local.fetch = None      # a fetch is one compilation's
            fields["fetched"] = fetch is not None and fetch[0] >= start
            if fields["fetched"]:
                fetch[1]["name"] = fun_name
        stack = self._stack()
        ring.append(("xla.compile", None, stack[-1] if stack else None,
                     start, end, None, fields))

    def boundary_spans(self, types=None) -> List[Span]:
        """The finished boundary spans still in the ring, oldest first
        (by END time: a child comes before the span that holds it)."""
        tset = None if types is None else (
            {types} if isinstance(types, str) else set(types))
        return [Span._make(s) for s in list(self._ring)
                if tset is None or s[0] in tset]

    # -- the profiler parity channel -------------------------------------
    def profiler_event(self, name: str, wall_s: float = 0.0,
                       kind: str = "scope") -> None:
        """Record one explicit profiler-API event (Task/Frame/Event
        scopes, Markers).  Unlike trace events this channel is ALWAYS
        recorded — the user called the profiler API explicitly — but
        its wall durations are NOISE by nature and excluded from the
        deterministic trace serialization."""
        with self._lock:
            self._tick = tick = self._next_tick()
            if len(self._profiler_events) < self._max_events:
                self._profiler_events.append(
                    (tick, kind, name, float(wall_s),
                     perf_counter_ns()))

    def profiler_events(self) -> List[Tuple[int, str, str, float]]:
        """``(tick, kind, name, wall_s)`` of every profiler-API event."""
        with self._lock:
            return [e[:4] for e in self._profiler_events]

    def clear_profiler_events(self) -> None:
        with self._lock:
            self._profiler_events = []

    # -- querying --------------------------------------------------------
    def events(self, rid: Optional[str] = None,
               types=None) -> List[TraceEvent]:
        with self._lock:
            out = list(self._events)
        if rid is not None:
            out = [e for e in out if e.rid == self.resolve(rid)]
        if types is not None:
            tset = {types} if isinstance(types, str) else set(types)
            out = [e for e in out if e.etype in tset]
        return out

    def timeline(self, rid: str) -> List[TraceEvent]:
        """Every recorded event of one request, tick order."""
        return self.events(rid=rid)

    def span_count(self) -> int:
        """Completed spans (end events) recorded so far."""
        with self._lock:
            return sum(1 for e in self._events if e.phase == "E")

    @property
    def ticks(self) -> int:
        """The current tick — cheap; ``stats()`` scans the whole event
        list, which failure-path callers must not pay per postmortem."""
        with self._lock:
            return self._tick

    @property
    def dropped_events(self) -> int:
        return self._dropped

    def stats(self) -> Dict[str, int]:
        """Numeric summary (a MetricsRegistry source)."""
        with self._lock:
            return {
                "enabled": int(self._enabled),
                "events": len(self._events),
                "spans": sum(1 for e in self._events
                             if e.phase == "E"),
                "dropped_events": self._dropped,
                "boundary_spans": len(self._ring),
                "profiler_events": len(self._profiler_events),
                "ticks": self._tick,
                "aliases": len(self._alias),
            }

    # -- serialization ---------------------------------------------------
    def to_json(self, include_noise: bool = False,
                indent: Optional[int] = None) -> str:
        """Deterministic JSON of the recorded trace: same seeds + same
        fault plan (+ a reset at the start of the run) => byte-identical
        output.  ``include_noise=True`` adds ``t_ns``, ``parent`` and
        the NOISE-labeled annotations (then equality is no longer
        promised)."""
        with self._lock:
            events = [e.to_dict(include_noise=include_noise)
                      for e in self._events]
            dropped = self._dropped
        return json.dumps({"version": 1, "clock": "tick",
                           "dropped": dropped, "events": events},
                          sort_keys=True, separators=(",", ":"),
                          indent=indent)


class _TracingContext:
    """``with tracing():`` — enable (resetting by default), restore the
    prior enabled state on exit."""

    def __init__(self, reset: bool = True):
        self._reset = reset
        self._prev = None

    def __enter__(self) -> Tracer:
        tr = get_tracer()
        self._prev = tr.enabled
        tr.enable(reset=self._reset)
        return tr

    def __exit__(self, *exc):
        if not self._prev:
            get_tracer().disable()
        return False


def tracing(reset: bool = True) -> _TracingContext:
    """Scoped tracing: ``with tracing() as tr: ... tr.to_json()``."""
    return _TracingContext(reset=reset)


_TRACER = Tracer()


def get_tracer() -> Tracer:
    """The process-wide tracer instance."""
    return _TRACER


# -- the process's own spans: before the first step -----------------------

_IMPORT_IDS = itertools.count(-1, -1).__next__  # never reset: spans stay


def _since_process_start_ns() -> Optional[int]:
    """Nanoseconds since the kernel's record of this process's start
    (``/proc/self/stat`` field 22 against ``/proc/uptime``: the
    arithmetic of chipbench's ``seconds_since_process_start``, so both
    ends agree); None where ``/proc`` is not there."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return int((uptime - ticks / os.sysconf("SC_CLK_TCK")) * 1e9)
    except (OSError, ValueError, IndexError):
        return None


class importing:
    """``with importing("mxtpu.gluon"):`` — one ``mxtpu.import`` span of
    the process-wide ring, from ``start_ns`` (now, if not given) to the
    block's end.  Its id is below zero and not a tick; spans and
    compilations inside it name it as ``parent``."""

    __slots__ = ("_module", "_t0", "_id", "_parent")

    def __init__(self, module: str, start_ns: Optional[int] = None):
        self._module = module
        self._t0 = start_ns

    def __enter__(self):
        stack = _TRACER._stack()
        self._parent = stack[-1] if stack else None
        if self._t0 is None:
            self._t0 = perf_counter_ns()
        self._id = _IMPORT_IDS()
        stack.append(self._id)
        return self

    def __exit__(self, *exc):
        end = perf_counter_ns()
        stack = _TRACER._stack()
        if stack and stack[-1] == self._id:
            stack.pop()
        _TRACER._ring.append(("mxtpu.import", self._id, self._parent,
                              self._t0, end, None,
                              {"module": self._module}))
        return False


def package_import(t0_ns: int, jax_imported: bool) -> importing:
    """What ``mxtpu/__init__.py`` calls once it has this module:
    ``t0_ns`` is ``perf_counter_ns()`` at its first line, ``jax_imported``
    whether jax was in ``sys.modules`` then.  Records ``process.start``
    (the kernel's record of the process's start laid on
    ``perf_counter_ns``, to ``t0_ns``; ``backend_up``: a jax backend
    existed already, i.e. the caller opened the device's client before it
    imported the program) and returns the package's own ``mxtpu.import``
    span, open since ``t0_ns``, for the last line to close."""
    since = _since_process_start_ns()
    if since is not None:
        bridge = sys.modules.get("jax._src.xla_bridge")
        up = getattr(bridge, "backends_are_initialized", None)
        _TRACER._ring.append((
            "process.start", None, None, perf_counter_ns() - since, t0_ns,
            None, {"jax_imported": jax_imported,
                   "backend_up": bool(up is not None and up())}))
    return importing("mxtpu", t0_ns).__enter__()


def attach_jax() -> None:
    """What this module takes from jax, taken once: ``mxtpu/__init__.py``
    calls this right after it has imported jax.  Spans open
    ``jax.profiler.TraceAnnotation`` from here on, and
    :meth:`Tracer.compile_seen` of the process-wide tracer listens to
    ``jax.monitoring``: XLA compilations, eager programs' too, become
    ``xla.compile`` spans of the boundary ring."""
    global _ANNOTATION
    if _ANNOTATION is not None:
        return
    import jax
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(
        _TRACER.compile_seen)
    _ANNOTATION = jax.profiler.TraceAnnotation


# -- chrome trace-event export (one writer for both APIs) ----------------

def export_chrome_trace(file=None, include_noise: bool = True,
                        tracer: Optional[Tracer] = None) -> Optional[str]:
    """Chrome trace-event JSON (chrome://tracing / Perfetto) serving
    BOTH the structured trace and the legacy ``mxtpu.profiler``
    Counter/Marker/scope events through one writer (the reference
    profiler's output format).  With ``include_noise`` the timeline is
    wall time (``ts`` = ``t_ns`` in us, on ``time.perf_counter``'s
    clock); without, the deterministic clock (``ts`` = tick, scopes one
    tick long).  ``file`` may be a path or a writable file object; with
    neither, the JSON string is returned."""
    tr = tracer if tracer is not None else get_tracer()
    tid_map: Dict[str, int] = {}

    def _tid(rid):
        if rid is None:
            return 0
        return tid_map.setdefault(rid, len(tid_map) + 1)

    def _ts(tick, t_ns):
        return t_ns / 1e3 if include_noise else tick

    trace_events: List[dict] = []
    for ev in tr.events():
        ph = {"I": "i", "B": "B", "E": "E"}[ev.phase]
        rec = {"name": ev.etype, "ph": ph, "ts": _ts(ev.tick, ev.t_ns),
               "pid": 0, "tid": _tid(ev.rid), "cat": "mxtpu"}
        if ph == "i":
            rec["s"] = "t"
        args = dict(ev.fields)
        if ev.rid is not None:
            args["rid"] = ev.rid
        if include_noise and ev.noise:
            args["NOISE"] = dict(ev.noise)
        rec["args"] = args
        trace_events.append(rec)
    with tr._lock:
        scopes = list(tr._profiler_events)
    for (tick, kind, name, wall_s, t_ns) in scopes:
        # a scope is recorded when it ends: it began wall_s earlier
        dur = wall_s * 1e6 if include_noise else 1
        trace_events.append({
            "name": name, "ph": "X", "ts": _ts(tick, t_ns) - dur,
            "dur": dur, "pid": 0, "tid": 0,
            "cat": "profiler,NOISE-wall-duration",
            "args": {"kind": kind, "wall_s": wall_s},
        })
    # the profiler parity API's counters, as chrome counter samples
    try:
        from .. import profiler as _prof
        now = _ts(tr.ticks, perf_counter_ns())
        for name, val in sorted(_prof.counter_values().items()):
            if isinstance(val, (int, float)):
                trace_events.append({
                    "name": name, "ph": "C", "ts": now,
                    "pid": 0, "tid": 0, "cat": "profiler",
                    "args": {"value": val}})
    except Exception:  # noqa: BLE001 — export must not die on a
        pass           # profiler import problem

    doc = {"traceEvents": trace_events, "displayTimeUnit": "ms",
           "otherData": {"clock": "time.perf_counter (us)" if include_noise
                         else "mxtpu deterministic tick"}}
    text = json.dumps(doc, sort_keys=True, separators=(",", ":"))
    if file is None:
        return text
    if hasattr(file, "write"):
        file.write(text)
        return None
    with open(file, "w") as f:
        f.write(text)
    return None
