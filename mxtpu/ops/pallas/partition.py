"""GSPMD partitioning scope for the Pallas attention kernels.

A Mosaic kernel cannot be partitioned by GSPMD ("Mosaic kernels cannot
be automatically partitioned. Please wrap the call in a shard_map"): a
pallas_call traced inside a jit whose operands are sharded has to say
itself how it splits.  The code that builds the jitted program knows the
mesh and the layout; the kernels sit deep below it.  So the program
builder opens a scope around its traced body and the kernels read it at
trace time.

The paged-attention / paged-prefill pallas_calls are traced deep inside
the mixer's ``step_pages``-family cache forms, but the information needed
to partition them — the device mesh and which mesh axes shard the
KV-heads axis of the paged cache (``cache_spec[1]``, ``"tp"`` by
default) — lives on the ``ShardedDecoder`` that builds the jitted
programs.  Rather than thread a mesh argument through every leaf-form
helper, the decoder opens :func:`head_sharding_scope` around its traced
bodies and the kernels read :func:`current_head_sharding` at trace time.

When the scope reports more than one shard, the kernels wrap their
pallas_call in ``shard_map`` over the heads axis: q/out (B, H, W, D) and
the page pools (N, KV, bs, D) split on their head axis, block tables /
positions replicate, and each device runs the identical kernel on its
per-device KV heads — the per-shard geometry ``kernel_check`` verdicts
via ``KernelSpec.mesh_axis``.  The GQA fold keeps q heads kv-major
(h = kv*rep + r), so an H-axis split lands every query head on the same
device as its KV head and the kernel body needs no cross-device
communication at all.

The training step (``SPMDTrainer``) opens the same scope with the batch
axes of its ``batch_spec`` as well: ``flash_attention`` then splits its
(B, H, T, D) operands over batch and heads (:func:`shard_attention`),
each device running the kernel on its own rows and heads.

Trace-time host state (a plain stack), same discipline as the
invocation counters: never read inside traced code, only while the
trace runs.
"""

from __future__ import annotations

import contextlib
from typing import NamedTuple

from jax import shard_map
from jax.sharding import PartitionSpec as P

__all__ = ["head_sharding_scope", "current_head_sharding",
           "head_shard_map", "shard_attention"]

_SCOPE = []


class HeadSharding(NamedTuple):
    mesh: object            # the jax Mesh
    axes: tuple             # mesh axes over the heads axis
    shards: int
    batch_axes: tuple       # mesh axes over the batch axis (training)
    batch_shards: int


def _axes(mesh, axes):
    axes = (axes,) if isinstance(axes, str) else tuple(axes or ())
    sizes = getattr(mesh, "axis_sizes", None) or {}
    shards = 1
    for a in axes:
        shards *= int(sizes.get(a, 1))
    return axes, shards


@contextlib.contextmanager
def head_sharding_scope(mesh, axes, batch_axes=()):
    """Declare, for the duration of a traced body, that attention heads
    (the paged cache's KV-heads axis — the engine's ``cache_spec[1]``,
    e.g. ``"tp"``) are sharded over mesh ``axes`` and, for a training
    step, the batch over ``batch_axes``.  ``mesh`` is the DeviceMesh (or
    anything with ``jax_mesh``/``axis_sizes``); a scope that resolves to
    one shard either way is recorded as inactive."""
    axes, shards = _axes(mesh, axes)
    batch_axes, batch_shards = _axes(mesh, batch_axes)
    entry = None
    if shards > 1 or batch_shards > 1:
        entry = HeadSharding(getattr(mesh, "jax_mesh", mesh), axes, shards,
                             batch_axes, batch_shards)
    _SCOPE.append(entry)
    try:
        yield entry
    finally:
        _SCOPE.pop()


def current_head_sharding():
    """The :class:`HeadSharding` of the innermost active scope, or None
    when unscoped / single-shard — kernels then make the unpartitioned
    call."""
    return _SCOPE[-1] if _SCOPE else None


def head_shard_map(fn, mesh, in_specs, out_specs):
    """shard_map with replication checking off: the kernels' outputs
    are genuinely sharded and the block tables genuinely replicated."""
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def shard_attention(fn, batch, heads):
    """``fn(q, k, v) -> out`` over (B, H, T, D) operands, wrapped in
    shard_map over the live scope's batch and heads axes — each only
    where its shard count divides the extent (an axis that does not
    divide stays whole on every device).  ``fn`` itself outside a
    scope."""
    scope = current_head_sharding()
    if scope is None:
        return fn

    def over(axes, shards, extent):
        if shards > 1 and extent % shards == 0:
            return axes[0] if len(axes) == 1 else axes
        return None

    spec = P(over(scope.batch_axes, scope.batch_shards, batch),
             over(scope.axes, scope.shards, heads), None, None)
    return head_shard_map(fn, scope.mesh, (spec, spec, spec), spec)
