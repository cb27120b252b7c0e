"""Pipeline parallelism (SURVEY §2.3 row: ABSENT in the reference —
MXNet 1.x has no PP; the closest artifact is coarse `group2ctx` device
placement.  This is the TPU-native capability the north star adds).

Design — GPipe over a `shard_map` "pp" mesh axis, fully differentiable:

- Stages are HOMOGENEOUS: one `stage_fn(params, x) -> x` applied P times
  with per-stage params stacked on a leading axis sharded over "pp"
  (each device holds exactly its stage's slice).  This is the idiomatic
  JAX formulation — every rank compiles the SAME program (SPMD), and a
  transformer body (N identical blocks) maps onto it directly.
- The microbatch schedule is a `lax.scan` over M + P - 1 ticks: each
  tick every rank applies its stage to what it holds, then `ppermute`
  shifts activations one rank forward.  Rank 0 feeds microbatch t at
  tick t; rank P-1 banks its output at tick t into slot t-(P-1).
  The (P-1)-tick bubble is the standard GPipe cost.
- **Backward is free**: scan and ppermute are differentiable, so
  `jax.grad` through `pipeline()` yields the reverse schedule (grads
  ppermute backwards through the ring) with no hand-written logic —
  the functional-transform payoff that the reference's imperative
  engine could never express.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import DeviceMesh

__all__ = ["pipeline", "stack_stage_params", "stage_sharding"]


def stack_stage_params(per_stage_params):
    """Stack a list of per-stage pytrees along a new leading 'stage'
    axis (shard it over "pp" with `stage_sharding`)."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *per_stage_params)


def stage_sharding(mesh: DeviceMesh, tree):
    """NamedShardings placing each stage's params slice on its pp rank."""
    jm = mesh.jax_mesh
    return jax.tree_util.tree_map(
        lambda x: NamedSharding(jm, P("pp", *([None] * (x.ndim - 1)))),
        tree)


def pipeline(stage_fn, stacked_params, x, mesh: DeviceMesh,
             num_microbatches: int):
    """Run `stage_fn` as a P-stage GPipe pipeline over the mesh's "pp"
    axis.

    stage_fn : (params_slice, act) -> act, same act shape in/out.
    stacked_params : pytree with leading stage axis of size P (use
        `stack_stage_params`); sharded or not — `shard_map` partitions it.
    x : (batch, ...) global input; batch must divide num_microbatches.
    Returns (batch, ...) output = stage_{P-1}(... stage_0(x)).
    Differentiable; jit-compatible (call under jit for real use).
    """
    pp = mesh.size("pp")
    if pp <= 1:
        def body(carry, p):
            return stage_fn(p, carry), None
        out, _ = lax.scan(body, x, stacked_params)
        return out
    b = x.shape[0]
    if b % num_microbatches:
        raise ValueError("batch %d must divide num_microbatches %d"
                         % (b, num_microbatches))
    mb = b // num_microbatches
    xs = x.reshape((num_microbatches, mb) + x.shape[1:])
    fwd = [(i, (i + 1) % pp) for i in range(pp)]  # ring, one step forward

    def per_rank(params_slice, xs_full):
        # params_slice: (1, ...) this rank's stage; xs_full: all
        # microbatches (replicated — rank 0 is the only consumer)
        params_local = jax.tree_util.tree_map(lambda p: p[0], params_slice)
        rank = lax.axis_index("pp")
        n_ticks = num_microbatches + pp - 1
        act0 = jnp.zeros_like(xs_full[0])
        ys0 = jnp.zeros_like(xs_full)

        def tick(carry, t):
            act, ys = carry
            # rank 0 injects microbatch t (clamped; masked past the end)
            inject = lax.dynamic_index_in_dim(
                xs_full, jnp.minimum(t, num_microbatches - 1), axis=0,
                keepdims=False)
            act = jnp.where(rank == 0, inject, act)
            out = stage_fn(params_local, act)
            # last rank banks its finished microbatch t-(P-1)
            slot = jnp.clip(t - (pp - 1), 0, num_microbatches - 1)
            bank = jnp.logical_and(rank == pp - 1, t >= pp - 1)
            cur = lax.dynamic_index_in_dim(ys, slot, 0, keepdims=False)
            ys = lax.dynamic_update_index_in_dim(
                ys, jnp.where(bank, out, cur), slot, 0)
            act = lax.ppermute(out, "pp", fwd)
            return (act, ys), None

        (act, ys), _ = lax.scan(tick, (act0, ys0), jnp.arange(n_ticks))
        # broadcast the last rank's banked outputs to every rank so the
        # shard_map output is replicated (out_specs=P())
        ys = lax.psum(jnp.where(rank == pp - 1, ys, jnp.zeros_like(ys)),
                      "pp")
        return ys

    ys = shard_map(
        per_rank, mesh=mesh.jax_mesh,
        in_specs=(P("pp"), P()), out_specs=P(),
        check_vma=False)(stacked_params, xs)
    return ys.reshape((b,) + x.shape[1:])
