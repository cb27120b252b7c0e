"""mxtpu.serving — fault-tolerant multi-replica serving: supervised
replica pool, prefix-locality router, streaming QoS gateway.

Everything below this package serves from ONE engine process; this is
the service layer that turns N engine replicas into one front for
heavy traffic (ROADMAP item 1).  Topology::

      client ──► Gateway ──► Router ──► ReplicaSupervisor
                 (QoS,        (prefix     │   health checks, stall
                  quotas,      locality    │   detection, drain-and-
                  streaming,   + load,     │   requeue, revive
                  deadlines,   hedging,    ▼
                  hedging)     reroute)   [ReplicaTransport × N]
                                           InProcessReplica(engine)

Layers (each module's docstring has the full story):

- :mod:`~mxtpu.serving.transport` — :class:`ReplicaTransport`, the
  process/ICI seam: today's :class:`InProcessReplica` adapts one
  ``ContinuousBatchingEngine``/``PagedContinuousBatchingEngine``; a
  process-per-replica or DCN transport slots in here (PAPER.md layer-3
  KVStore blueprint) without the layers above changing.
- :mod:`~mxtpu.serving.supervisor` — :class:`ReplicaSupervisor`:
  counter-clock health checks (consecutive ``replica.health`` /
  ``replica.stream`` failures, stall detection on ``stats()`` deltas),
  deterministic drain-and-requeue on declared death (zero pages
  survive on a dead replica), probation revival.
- :mod:`~mxtpu.serving.router` — :class:`Router`: places requests by
  the paged engines' exact radix/host-tier locality signal
  (``prefix_probe``) blended with load; typed
  :class:`ReplicaDownError` reroutes ride a ``RetryPolicy``.
- :mod:`~mxtpu.serving.gateway` — :class:`Gateway`: per-iteration
  token streaming, QoS classes + per-tenant quotas over bounded
  admission (shed lowest class first, structured
  :class:`~mxtpu.resilience.QosShedError` /
  :class:`~mxtpu.resilience.EngineShedError` with retry-after hints),
  tick-counted deadlines, hedged re-dispatch.

Every failure path is a counter-driven fault site (``gateway.admit``,
``router.dispatch``, ``replica.health``, ``replica.stream`` — see
docs/resilience.md), so the whole service replays bit-for-bit: any
stream that completes — routed, hedged, requeued after a mid-decode
replica death — is bit-identical to an isolated
``ShardedDecoder.generate`` with the same seed
(tests/test_serving_router.py).
"""

from __future__ import annotations

import os
from typing import Callable, Optional

from .autoscale import Autoscaler
from .gateway import Gateway
from .router import Router
from .supervisor import ReplicaSupervisor
from .transport import (ChipHeldError, InProcessReplica, ReplicaDownError,
                        ReplicaTransport, SubprocessReplica,
                        request_spec)

__all__ = ["Autoscaler", "Gateway", "Router", "ReplicaSupervisor",
           "ReplicaTransport", "InProcessReplica", "SubprocessReplica",
           "ReplicaDownError", "ChipHeldError", "request_spec",
           "replica_pool"]


def replica_pool(factory, n: Optional[int] = None,
                 transport: Optional[str] = None,
                 kwargs=None, **spawn_kw):
    """Build N replicas from an engine factory.

    ``transport`` selects the boundary (default from
    ``MXTPU_REPLICA_TRANSPORT``, itself defaulting to ``inprocess``):

    - ``"inprocess"`` — ``factory(i)`` is a CALLABLE returning a fresh
      engine for replica i; pass ``ledger_tag="r%d" % i`` through so
      each replica's compiled-program family stays separable in the
      compile ledger.
    - ``"subprocess"`` — ``factory`` is a ``"module:callable"`` SPEC
      string resolved inside each spawned worker process
      (:class:`SubprocessReplica`); ``kwargs`` is the factory's kwargs
      dict, or a callable ``i -> dict`` for per-replica values (ledger
      tags, ports).  Extra keyword arguments pass through to
      :class:`SubprocessReplica` (``rpc_timeout_ticks``, ``codec``,
      ``env``, ...); ``env`` may likewise be a callable ``i -> dict`` —
      ``env=chip_pin_env`` gives worker i the i-th chip of a host and
      nothing else (a chip belongs to one process; docs/serving.md).

    ``n`` defaults to ``MXTPU_REPLICAS`` (itself defaulting to 1: one
    replica is a plain engine behind the gateway's QoS front).

    >>> pool = replica_pool(
    ...     lambda i: PagedContinuousBatchingEngine(
    ...         block, mesh, rules, ledger_tag="r%d" % i), n=2)
    >>> gw = Gateway(pool)

    >>> pool = replica_pool(
    ...     "mxtpu.serving.worker:demo_paged_engine", n=2,
    ...     transport="subprocess",
    ...     kwargs=lambda i: {"ledger_tag": "r%d" % i})
    """
    if n is None:
        try:
            n = int(os.environ.get("MXTPU_REPLICAS", 1))
        except ValueError:
            n = 1
    if n < 1:
        raise ValueError("replica_pool needs n >= 1, got %d" % n)
    if transport is None:
        transport = os.environ.get("MXTPU_REPLICA_TRANSPORT",
                                   "inprocess").strip() or "inprocess"
    if transport == "inprocess":
        if not callable(factory):
            raise ValueError(
                "inprocess replica_pool needs a callable factory(i) "
                "returning an engine, got %r" % (factory,))
        return [InProcessReplica(factory(i), "r%d" % i)
                for i in range(n)]
    if transport == "subprocess":
        if not isinstance(factory, str):
            raise ValueError(
                "subprocess replica_pool needs a 'module:callable' "
                "factory spec string (resolved in the worker process), "
                "got %r" % (factory,))
        env = spawn_kw.pop("env", None)
        return [SubprocessReplica(
            factory,
            kwargs=(kwargs(i) if callable(kwargs)
                    else dict(kwargs or {})),
            replica_id="r%d" % i,
            env=env(i) if callable(env) else env,
            **spawn_kw) for i in range(n)]
    raise ValueError(
        "unknown replica transport %r (MXTPU_REPLICA_TRANSPORT: "
        "'inprocess' or 'subprocess')" % (transport,))
