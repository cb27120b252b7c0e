"""Chunked-prefill (flash-prefill) attention kernel in Pallas (TPU).

The paged engines prefill prompts in pow2 chunks
(the ``prefill_pages`` cache form): the chunk's K/V rows are written into
the block pool, then the XLA path GATHERS every table entry back out —
a full-K/V materialization whose residency the K003 pricer measured at
~2 MiB per (slot, kv-head) row at T=2048.  This kernel walks the slot's
int32 block table with scalar-prefetched indices instead, exactly the
paged_decode_attention discipline: grid (KV, q-tiles, M), each step
DMAs ONE page selected by ``table[j]``, pages past the chunk's valid
extent route to the reserved null page 0 and are skipped by
``pl.when`` — per-grid-step residency is one q tile + one page, not the
prompt's full K/V extent.

The chunk's rep*T query lanes (GQA fold, lane l = r*T + t) are
subdivided into 128-lane q tiles; online softmax (running max /
denominator / fp32 accumulator) carries across the page walk per tile,
and causal masking inside the chunk falls out of the lane arithmetic:
lane l of the tile at offset i attends key positions
<= start_pos + ((i*qb + l) % T).

int8 variant: with ``k_scales`` / ``v_scales`` the page dequantizes
(payload × per-head-per-position scale) inside the kernel — the int8
cache never materializes a float copy on the prefill read either.

Gating, partitioning and verification all mirror the decode kernel:
the same tri-state ``MXTPU_PALLAS_PAGED_ATTN`` resolves the default
(``auto`` = on for real accelerator backends where
:func:`validate_call_geometry` passes, off on interpret-only CPU hosts
per K007), an active ``head_sharding_scope`` shard_maps the call over
the cache's heads axis, :func:`kernel_spec` feeds the static
kernel_check pass (per-shard via ``mesh_axis``), and
tests/test_prefill_attention_pallas.py holds the interpret-mode parity
matrix against :func:`xla_reference` — the bit-exact gather path the
engines run when the gate resolves off.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...base import register_op
from . import counters
from .paged_attention import (_NEG_INF, paged_attention_enabled,
                              path_geometry, resolve_path,
                              validate_call_geometry as
                              _decode_call_geometry)
from .partition import current_head_sharding, head_shard_map

__all__ = ["paged_prefill_attention", "paged_prefill_enabled",
           "kernel_spec", "validate_call_geometry"]

KERNEL_NAME = "paged_prefill"

_QB = 128  # q-tile lane count — one (8*sublane, 128-lane) MXU-sized tile


def _q_tile(lanes):
    """Lanes per q tile: 128 when the chunk's rep*T fold subdivides
    evenly, else the whole fold (small chunks)."""
    return _QB if lanes % _QB == 0 else lanes


def paged_prefill_enabled(D=None, block_size=None, pool_dtype=None,
                          T=None, rep=None, q_dtype="float32") -> bool:
    """Resolve the shared tri-state gate for one prefill call site —
    same rules as ``paged_attention_enabled`` plus this kernel's own
    geometry guard, recorded per chunk length."""
    if D is None:
        return paged_attention_enabled()
    return resolve_path(
        KERNEL_NAME, "%s,T=%s,rep=%s,q=%s" % (
            path_geometry(D, block_size, pool_dtype), T, rep, q_dtype),
        lambda: validate_call_geometry(D, block_size, pool_dtype, T=T,
                                       rep=rep, q_dtype=q_dtype))


def invocation_count() -> int:
    return counters.count(KERNEL_NAME)


def validate_call_geometry(D, block_size, pool_dtype, T=None, rep=None,
                           q_dtype="float32"):
    """Runtime mirror of the static rules for THIS kernel: the decode
    kernel's K001 (lane-aligned D) and K002 (block_size a multiple of
    the cache dtype's sublane tile), plus the q-tile rule — when the
    rep*T lane fold does not subdivide into 128-lane tiles, the whole
    fold is one tile and must itself be a multiple of the QUERY dtype's
    sublane tile."""
    from ...analysis.memory_estimate import sublane_tile

    errs = _decode_call_geometry(D, block_size, pool_dtype)
    if T is not None and rep is not None:
        qb = _q_tile(rep * int(T))
        sub = sublane_tile(q_dtype)
        if qb % sub != 0:
            errs.append(
                "K002: q tile %d (rep=%d x chunk T=%d) is not a "
                "multiple of the %s sublane tile %d"
                % (qb, rep, T, q_dtype, sub))
    return errs


def _kernel(tbl_ref, start_ref, nv_ref, q_ref, k_ref, *rest,
            sm_scale, bs, T, qb, n_pages, quant):
    """One (kv head, q tile) pair walks the slot's block-table chain;
    online-softmax state lives in VMEM scratch across the page walk."""
    if quant:
        ks_ref, v_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        v_ref, o_ref, m_ref, l_ref, acc_ref = rest
    kv = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < nv_ref[0])
    def _page():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale       # (qb, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bs, D)
        v = v_ref[0, 0].astype(jnp.float32)
        if quant:
            k = k * ks_ref[0, kv].astype(jnp.float32)[:, None]
            v = v * vs_ref[0, kv].astype(jnp.float32)[:, None]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        # causal mask within the chunk: tile lane l is fold lane
        # i*qb + l = r*T + t, so its logical query position is
        # start + ((i*qb + l) % T); this page's keys sit at j*bs + col
        k_pos = j * bs + jax.lax.broadcasted_iota(
            jnp.int32, (qb, bs), 1)
        t = (i * qb + jax.lax.broadcasted_iota(
            jnp.int32, (qb, bs), 0)) % T
        s = jnp.where(k_pos <= start_ref[0] + t, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1,
                                                 keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(j == n_pages - 1)
    def _fin():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


def _num_valid_pages(start_pos, T, block_size, M):
    """Pages the chunk's causal extent can touch: logical positions
    0 .. start_pos + T - 1 — shared by the runtime call and the
    kernel_spec model (the decode-kernel discipline)."""
    return jnp.clip((start_pos + (T - 1)) // block_size + 1, 1,
                    M).astype(jnp.int32)


def _page_index(kv, i, j, tbl, start, nv):
    """Valid steps read ``table[j]``; steps past the chunk's extent
    read the reserved null page 0 (one no-op DMA, skipped by
    pl.when)."""
    return (jnp.where(j < nv[0], tbl[j], 0), kv, 0, 0)


def _scale_index(kv, i, j, tbl, start, nv):
    """The page's whole (KV, bs) scale plane (the decode kernel's
    rule: no size-1 second-to-last block dim); the kernel picks its
    head's row."""
    return (jnp.where(j < nv[0], tbl[j], 0), 0, 0)


def _model_table(M, n_pages, nv):
    """Representative table for the static checker: live entries point
    at distinct allocated pages (1-based), padded entries carry the
    null page — the engine's per-slot table row convention."""
    import numpy as np

    table = np.zeros(M, np.int32)
    page = 1
    for j in range(int(nv)):
        table[j] = page
        page = page % (n_pages - 1) + 1
    return table


def kernel_spec(T, KV, rep, D, block_size, max_length, start_pos=0,
                q_dtype="bfloat16", cache_dtype="float32",
                num_blocks=None, table=None, interpret=False,
                mesh_axis=None):
    """KernelSpec descriptor (mxtpu.analysis.kernel_check) for one
    paged_prefill_attention call — the REAL index maps over a model
    scalar-prefetch table, per-shard geometry via
    ``mesh_axis=(axis_name, shards)`` exactly as the decode kernel's
    spec builder."""
    import numpy as np

    from ...analysis.kernel_check import (BlockOperand, KernelSpec,
                                          ScalarPrefetch, ScratchOperand)

    bs = int(block_size)
    T = int(T)
    M = math.ceil(max_length / bs)
    name_sfx = ""
    if mesh_axis is not None:
        axis_name, shards = mesh_axis[0], int(mesh_axis[1])
        mesh_axis = (axis_name, shards, int(KV))
        if shards > 1 and KV % shards == 0:
            KV = KV // shards
        name_sfx = ",%s=%d" % (axis_name, shards)
    N = int(num_blocks) if num_blocks is not None else M + 1
    quant = str(cache_dtype) == "int8"
    pool_dtype = "int8" if quant else cache_dtype
    lanes = rep * T
    qb = _q_tile(lanes)
    n_qt = lanes // qb
    nv = int(np.asarray(_num_valid_pages(
        np.int32(start_pos), T, bs, M)))
    table = _model_table(M, N, nv) if table is None \
        else np.asarray(table).astype(np.int32).reshape(-1)
    start = np.asarray([start_pos], np.int32)
    nv_arr = np.asarray([nv], np.int32)

    q_im = lambda kv, i, j, tbl, start, nv: (0, kv, i, 0)  # noqa: E731
    operands = [
        BlockOperand("q", "in", (1, 1, qb, D), (1, KV, lanes, D),
                     q_dtype, q_im, strict_dims=(-1,)),
        BlockOperand("pool_k", "in", (1, 1, bs, D), (N, KV, bs, D),
                     pool_dtype, _page_index, strict_dims=(-1, -2)),
    ]
    if quant:
        operands.append(BlockOperand(
            "k_scales", "in", (1, KV, bs), (N, KV, bs), "float32",
            _scale_index))
    operands.append(BlockOperand(
        "pool_v", "in", (1, 1, bs, D), (N, KV, bs, D), pool_dtype,
        _page_index, strict_dims=(-1, -2)))
    if quant:
        operands.append(BlockOperand(
            "v_scales", "in", (1, KV, bs), (N, KV, bs), "float32",
            _scale_index))
    operands.append(BlockOperand(
        "o", "out", (1, 1, qb, D), (1, KV, lanes, D), q_dtype, q_im,
        strict_dims=(-1,)))
    return KernelSpec(
        "paged_prefill[%s,T=%d,bs=%d,D=%d%s]" % (pool_dtype, T, bs, D,
                                                 name_sfx),
        grid=(KV, n_qt, M),
        operands=operands,
        scratch=[ScratchOperand("m", (qb, 1), "float32"),
                 ScratchOperand("l", (qb, 1), "float32"),
                 ScratchOperand("acc", (qb, D), "float32")],
        prefetch=[ScalarPrefetch("table", table, valid_range=(0, N)),
                  ScalarPrefetch("start", start,
                                 valid_range=(0, max_length)),
                  ScalarPrefetch("nv", nv_arr, valid_range=(1, M + 1))],
        interpret=interpret,
        mesh_axis=mesh_axis)


def _call_local(qr, pool_k, pool_v, table, start, k_scales=None,
                v_scales=None, *, sm_scale, T, interpret):
    """The unpartitioned pallas_call on (possibly per-shard) operands:
    qr is the kv-major (1, KV, rep*T, D) fold."""
    _, KV, lanes, D = qr.shape
    N, _, bs, _ = pool_k.shape
    M = table.shape[-1]
    quant = k_scales is not None
    qb = _q_tile(lanes)
    start = jnp.asarray(start, jnp.int32).reshape(1)
    nv = _num_valid_pages(start, T, bs, M)

    in_specs = [
        pl.BlockSpec((1, 1, qb, D),
                     lambda kv, i, j, tbl, start, nv: (0, kv, i, 0)),
        pl.BlockSpec((1, 1, bs, D), _page_index),
    ]
    args = [qr, pool_k]
    if quant:
        in_specs.append(pl.BlockSpec((1, KV, bs), _scale_index))
        args.append(k_scales)
    in_specs.append(pl.BlockSpec((1, 1, bs, D), _page_index))
    args.append(pool_v)
    if quant:
        in_specs.append(pl.BlockSpec((1, KV, bs), _scale_index))
        args.append(v_scales)

    kernel = functools.partial(_kernel, sm_scale=sm_scale, bs=bs, T=T,
                               qb=qb, n_pages=M, quant=quant)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(KV, lanes // qb, M),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, qb, D),
            lambda kv, i, j, tbl, start, nv: (0, kv, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, KV, lanes, D), qr.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="paged_prefill_chunk",
    )(table, start, nv, *args)


def paged_prefill_attention(q, pool_k, pool_v, table, start_pos,
                            k_scales=None, v_scales=None, scale=None):
    """Chunked-prefill attention over one slot's block table.

    q : (1, H, T, D) chunk queries (rope already applied) — T is the
        prefill chunk length; the chunk's K/V rows are already written
        into the pool at logical positions start_pos .. start_pos+T-1.
    pool_k / pool_v : (N, KV, bs, D) page pools (float, or int8 payload
        when ``k_scales``/``v_scales`` (N, KV, bs) are given).
    table : (M,) int32 block table of the slot (page 0 = null page).
    start_pos : scalar int32 — the chunk's first logical position.

    Returns (1, H, T, D) in q's dtype; H = KV * rep kv-major.  Inside
    an active ``head_sharding_scope`` the call is shard_map-partitioned
    over the heads axis.
    """
    _, H, T, D = q.shape
    N, KV, bs, _ = pool_k.shape
    rep = H // KV
    sm_scale = float(scale if scale is not None else 1.0 / math.sqrt(D))
    quant = k_scales is not None

    qr = q.reshape(1, KV, rep * T, D)
    table = table.astype(jnp.int32).reshape(-1)
    start = jnp.asarray(start_pos, jnp.int32).reshape(1)

    interpret = jax.default_backend() == "cpu"
    if not interpret:
        errs = validate_call_geometry(
            D, bs, "int8" if quant else str(pool_k.dtype), T=T,
            rep=rep, q_dtype=str(q.dtype))
        if errs:
            raise ValueError(
                "paged_prefill_attention: TPU-illegal call geometry — "
                + "; ".join(errs)
                + ". Fix the engine's block_size/head_dim/prefill_chunk"
                " (or run `python -m mxtpu.analysis kernel` for the "
                "full static verdict); interpret-mode CPU tests accept "
                "this geometry, hardware does not.")
    counters.bump(KERNEL_NAME)
    call = functools.partial(_call_local, sm_scale=sm_scale, T=T,
                             interpret=interpret)

    shard = current_head_sharding()
    if shard is not None and shard.shards > 1 \
            and KV % shard.shards == 0:
        from jax.sharding import PartitionSpec as P

        jm, axes = shard.mesh, shard.axes
        ax = axes[0] if len(axes) == 1 else tuple(axes)
        heads4 = P(None, ax, None, None)
        heads3 = P(None, ax, None)
        repl = P()
        if quant:
            fn = lambda a, b_, c, d, e, f, g: call(  # noqa: E731
                a, b_, c, d, e, f, g)
            in_specs = (heads4, heads4, heads4, repl, repl,
                        heads3, heads3)
            mapped = head_shard_map(fn, jm, in_specs, heads4)
            out = mapped(qr, pool_k, pool_v, table, start,
                         k_scales, v_scales)
        else:
            fn = lambda a, b_, c, d, e: call(a, b_, c, d, e)  # noqa: E731
            in_specs = (heads4, heads4, heads4, repl, repl)
            mapped = head_shard_map(fn, jm, in_specs, heads4)
            out = mapped(qr, pool_k, pool_v, table, start)
    else:
        out = call(qr, pool_k, pool_v, table, start, k_scales, v_scales)
    return out.reshape(1, KV, rep, T, D).reshape(1, H, T, D)


def xla_reference(q, pool_k, pool_v, table, start_pos, k_scales=None,
                  v_scales=None, scale=None):
    """The XLA gather path on raw arrays — the same math
    ``prefill_pages`` runs when the gate resolves off, and the parity
    reference for the kernel."""
    _, H, T, D = q.shape
    N, KV, bs, _ = pool_k.shape
    M = table.shape[-1]
    rep = H // KV
    sm_scale = float(scale if scale is not None else 1.0 / math.sqrt(D))
    t = table.astype(jnp.int32).reshape(-1)
    start = jnp.asarray(start_pos, jnp.int32).reshape(())

    def gather(pool, scales):
        g = pool[t].astype(jnp.float32)            # (M, KV, bs, D)
        if scales is not None:
            g = g * scales[t].astype(jnp.float32)[..., None]
        return g.transpose(1, 0, 2, 3).reshape(KV, M * bs, D)

    keys = gather(pool_k, k_scales)
    values = gather(pool_v, v_scales)
    qr = q.reshape(KV, rep * T, D).astype(jnp.float32) * sm_scale
    s = jnp.einsum("kld,ktd->klt", qr, keys,
                   preferred_element_type=jnp.float32)
    k_pos = jnp.arange(M * bs, dtype=jnp.int32)
    q_pos = start + (jnp.arange(rep * T, dtype=jnp.int32) % T)
    s = jnp.where(k_pos[None, None, :] <= q_pos[None, :, None], s,
                  _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("klt,ktd->kld", p, values)
    return o.reshape(1, KV, rep, T, D).reshape(1, H, T, D).astype(
        q.dtype)


@register_op("paged_prefill_attention", differentiable=False)
def paged_prefill_attention_op(q, pool_k, pool_v, table, start_pos,
                               k_scales=None, v_scales=None, scale=None):
    return paged_prefill_attention(q, pool_k, pool_v, table, start_pos,
                                   k_scales=k_scales, v_scales=v_scales,
                                   scale=scale)
