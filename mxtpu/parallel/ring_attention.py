"""Ring attention: sequence-parallel exact attention over the "sp" mesh axis.

Absent in the reference (MXNet 1.x predates it — SURVEY §2.3); required
here because long-context is first-class on TPU. Design: Q/K/V are sharded
along the sequence dimension across the "sp" axis; each device computes
blockwise attention of its local queries against the K/V block it currently
holds while the K/V blocks rotate around the ring via `lax.ppermute` (ICI
neighbor exchange — bandwidth-optimal, no all-gather materialization).
Softmax is computed in streaming (flash) form with a running max and
denominator, so memory stays O(T_local²) regardless of ring size.

Public entry points:
- ring_attention_inner: runs INSIDE shard_map/pmap (axis_name visible)
- ring_self_attention: host-level wrapper that shard_maps over a DeviceMesh
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

__all__ = ["ring_attention_inner", "ring_self_attention"]


def _block_attn(q, k, v, mask, m, l, o, scale):
    """One streaming-softmax accumulation step.

    q: (B, H, Tq, D), k/v: (B, H, Tk, D), mask: (Tq, Tk) additive or None.
    m: running max (B, H, Tq), l: running denom, o: running numerator.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = s + mask
    m_blk = s.max(axis=-1)
    m_new = jnp.maximum(m, m_blk)
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * corr + p.sum(axis=-1)
    o_new = o * corr[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(p.dtype))
    return m_new, l_new, o_new


def ring_attention_inner(q, k, v, axis_name: str = "sp",
                         causal: bool = False, scale: Optional[float] = None):
    """Exact attention with K/V ring rotation. Call inside shard_map.

    q, k, v: (B, H, T_local, D) — the local sequence shard.
    Returns (B, H, T_local, D).
    """
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    t_local = q.shape[2]
    d = q.shape[3]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    perm = [(i, (i + 1) % n) for i in range(n)]

    qf = q.astype(jnp.float32)
    m0 = jnp.full(q.shape[:3], -jnp.inf, jnp.float32)
    l0 = jnp.zeros(q.shape[:3], jnp.float32)
    o0 = jnp.zeros(qf.shape, jnp.float32)

    q_pos = my_idx * t_local + jnp.arange(t_local)  # global query positions

    def body(carry, step):
        k_blk, v_blk, m, l, o = carry
        src = (my_idx - step) % n  # ring provenance of the current kv block
        if causal:
            k_pos = src * t_local + jnp.arange(t_local)
            mask = jnp.where(q_pos[:, None] >= k_pos[None, :], 0.0,
                             -jnp.inf).astype(jnp.float32)
        else:
            mask = None
        m, l, o = _block_attn(qf, k_blk.astype(jnp.float32),
                              v_blk.astype(jnp.float32), mask, m, l, o,
                              scale)
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return (k_blk, v_blk, m, l, o), None

    (k_f, v_f, m, l, o), _ = lax.scan(
        body, (k, v, m0, l0, o0), jnp.arange(n))
    out = o / l[..., None]
    return out.astype(q.dtype)


def ring_self_attention(q, k, v, mesh, causal: bool = False,
                        scale: Optional[float] = None,
                        batch_axis: str = "dp", seq_axis: str = "sp"):
    """shard_map wrapper: q/k/v (B, H, T, D) sharded batch→dp, seq→sp."""
    jm = getattr(mesh, "jax_mesh", mesh)
    spec = P(batch_axis, None, seq_axis, None)
    fn = functools.partial(ring_attention_inner, axis_name=seq_axis,
                           causal=causal, scale=scale)
    mapped = shard_map(fn, mesh=jm, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return mapped(q, k, v)
