"""What a unit of recomputation keeps beside its input.

Under ``remat_scope`` (gluon/block.py) the backward pass keeps a unit's
input and forms everything inside it again — except the values an op
marked with :func:`keep`: outputs that are dear to form a second time and
cheap to hold (a kernel's result that only the next product's weight
gradient reads).  Every unit's ``jax.checkpoint`` runs under the one
:func:`policy`, which keeps the marked values and nothing else.

Outside a checkpoint a mark is an identity that XLA erases.  A mark
inside an inner ``jax.checkpoint`` or a loop's body is not seen by the
unit's policy: mark the value where it leaves them.

The policy counts what it keeps, per traced program: ``remat_scope``
zeroes the counts when it opens, so after a step program has been traced
``remat.kept_outputs`` / ``remat.kept_bytes`` in the MetricsRegistry
(observability/metrics.py) are that program's.  The sizes are those of
the equations the policy ruled on while the backward pass was traced, a
shard's where the mark lies under a ``shard_map``; a marked value that
nothing in the backward pass reads is counted and then dropped by JAX,
so an op marks only what its own backward, or the next op's, reads.
Host-side Python ints mutated at trace time, as in
ops/pallas/counters.py, but the tracing thread's own, as ``remat_scope``
is: two trainers traced on two threads do not touch each other's
counts, and the registry reads those of the thread that asks.
"""

from __future__ import annotations

import threading

import jax
from jax.ad_checkpoint import checkpoint_name

__all__ = ["KEPT", "keep", "policy", "counts", "reset"]

#: the one name an op gives a value that a unit's checkpoint keeps
KEPT = "mxtpu_kept"

_save = jax.checkpoint_policies.save_only_these_names(KEPT)
_COUNTS = threading.local()


def keep(x):
    """Mark ``x`` as kept by the unit of recomputation it is formed in."""
    return checkpoint_name(x, KEPT)


def policy(prim, *avals, **params):
    """The ``jax.checkpoint`` policy of every unit: keep what
    :func:`keep` marked, form everything else again."""
    kept = _save(prim, *avals, **params)
    if kept:
        now = counts()
        _COUNTS.kept_outputs = now["kept_outputs"] + len(avals)
        _COUNTS.kept_bytes = now["kept_bytes"] + sum(
            a.size * a.dtype.itemsize for a in avals)
    return kept


def counts():
    """Values and bytes kept in the newest program this thread traced
    under ``remat_scope`` — the MetricsRegistry source payload."""
    return {"kept_outputs": getattr(_COUNTS, "kept_outputs", 0),
            "kept_bytes": getattr(_COUNTS, "kept_bytes", 0)}


def reset():
    _COUNTS.kept_outputs = _COUNTS.kept_bytes = 0
