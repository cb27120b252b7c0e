"""Ragged paged decode-attention kernel in Pallas (TPU).

Serving's first Pallas kernel (ROADMAP item 3): the paged engines'
decode/verify read is a gather of EVERY table entry — M pages per slot,
padded entries included — followed by a masked softmax over the full
padded extent.  This kernel walks each slot's int32 block table with
scalar-prefetched indices instead: grid (B, KV, M), each step DMAs ONE
page of one kv head selected by ``tables[b, j]``, pages past the slot's
valid extent are routed to the reserved null page 0 (a single-page
no-op read) and skipped by ``pl.when`` — so HBM traffic is
O(valid pages), not O(table width), which is the one-cache-read claim
of speculative verify at kernel granularity.

Softmax runs in online (max/denominator-carrying) form across the page
walk, fp32 accumulation, exactly the flash_attention discipline.  The
verify window rides the same kernel: q carries W lanes per query head
and lane w of slot b attends logical positions <= pos[b] + w.

int8 variant: with ``k_scales`` / ``v_scales`` the pools are int8
payloads and the per-head-per-position scales dequantize INSIDE the
kernel — the cache crosses HBM at one byte per element and never
materializes a float copy.

Gating is tri-state (``MXTPU_PALLAS_PAGED_ATTN`` = ``auto``/``1``/``0``,
default ``auto``): on a real accelerator backend the kernel IS the
default execution path wherever :func:`validate_call_geometry` accepts
the call geometry; on interpret-only CPU hosts ``auto`` resolves off
(the K007 rule — interpret mode accepts geometry hardware wouldn't) and
the XLA gather path runs, which stays the bit-exact parity reference
everywhere.  ``1`` forces the kernel (CPU tests run it in interpret
mode), ``0`` forces the XLA path.  The resolved decision is baked into
the serving jit keys so ledger program families stay pinned.

Under a tp-sharded cache (``cache_spec`` heads axis, shard count > 1)
the pallas_call is wrapped in ``shard_map`` over that axis — q/out and
the page pools split on their heads axis, tables/pos replicate, and
each device runs the kernel on its per-device KV heads (see
ops/pallas/partition.py; the decoder opens the scope around its traced
bodies).  Verified against the XLA path in
tests/test_paged_attention_pallas.py.

Geometry contract: ``mxtpu.analysis.kernel_check`` is the source of
truth (docs/analysis.md K0xx) — :func:`kernel_spec` describes this
call's grid/blocks/index-maps/scratch/prefetch for the static pass,
which enforces lane-aligned D (K001), block_size a multiple of the
cache dtype's sublane tile (K002: 8 fp32 / 16 bf16 / 32 int8), the
VMEM budget (K003) and in-pool tables (K004) pre-compile.  On a
non-interpret backend :func:`validate_call_geometry` mirrors the rules
at call time and raises naming the violated K-rule; the engines'
CPU-test geometries are interpret-mode-only (K007).
"""

from __future__ import annotations

import contextlib
import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...base import register_op
from . import counters
from .partition import current_head_sharding, head_shard_map

__all__ = ["paged_decode_attention", "paged_attention_enabled",
           "paged_attention_mode", "recording_paths", "kernel_spec",
           "validate_call_geometry"]

_NEG_INF = -1e30

KERNEL_NAME = "paged_attention"


def paged_attention_mode() -> str:
    """The raw tri-state gate: ``"auto"`` (default), ``"1"`` (force the
    kernel, interpret mode on CPU) or ``"0"`` (force the XLA gather
    path).  Unrecognized values read as ``auto``."""
    v = os.environ.get("MXTPU_PALLAS_PAGED_ATTN", "auto").strip().lower()
    if v in ("0", "false", "off"):
        return "0"
    if v in ("1", "true", "on"):
        return "1"
    return "auto"


#: trace-time stack of ``{"kernel[geometry]": "pallas|xla: why"}``
#: sinks — the program builder (``ShardedDecoder``) opens
#: :func:`recording_paths` around the body it traces, so every gate
#: verdict lands on the decoder whose program it was baked into.  Host
#: state read only while a trace runs, like ``partition._SCOPE``.
_RECORD: list = []


@contextlib.contextmanager
def recording_paths(sink: dict):
    """Collect the gate verdicts resolved inside this block in ``sink``
    — the paged engine's ``stats["attention_paths"]``."""
    _RECORD.append(sink)
    try:
        yield sink
    finally:
        _RECORD.pop()


def resolve_path(kernel, geometry=None, violations=None) -> bool:
    """Resolve the tri-state gate for one call site and make the choice
    visible: ``auto`` = the kernel where the backend is a real
    accelerator AND ``violations()`` (the kernel's
    ``validate_call_geometry``) is empty; the XLA gather path on
    interpret-only CPU hosts (K007: interpret mode accepts geometry
    hardware would reject) unless forced with ``1``.  The verdict and
    its reason go under ``kernel[geometry]`` into the innermost
    :func:`recording_paths` sink; declining on an accelerator warns,
    once per sink, naming the violated K-rule — a served model must not
    land on the gather path in silence.  Without a geometry only the
    mode and the backend decide (the jit-key read), and nothing is
    recorded."""
    mode = paged_attention_mode()
    backend = jax.default_backend()
    declined = False
    if mode != "auto":
        on, why = mode == "1", "MXTPU_PALLAS_PAGED_ATTN=%s" % mode
    elif backend == "cpu":
        on, why = False, "K007: the cpu backend is interpret-only"
    else:
        errs = violations() if violations else []
        on, declined = not errs, bool(errs)
        why = "; ".join(errs) if errs else "geometry legal on %s" % backend
    if geometry is None:
        return on
    key = "%s[%s]" % (kernel, geometry)
    verdict = "%s: %s" % ("pallas" if on else "xla", why)
    sink = _RECORD[-1] if _RECORD else {}
    if declined and sink.get(key) != verdict:
        import warnings

        warnings.warn(
            "%s takes the XLA gather path on %s — %s"
            % (key, backend, why), RuntimeWarning, stacklevel=3)
    sink[key] = verdict
    return on


def path_geometry(D, block_size, pool_dtype) -> str:
    """The geometry every paged gate's record starts with."""
    return "D=%d,bs=%d,%s" % (D, block_size, pool_dtype)


def paged_attention_enabled(D=None, block_size=None,
                            pool_dtype=None) -> bool:
    """Resolve the tri-state gate for one decode/verify call site
    (docs/inference.md "Serving Pallas kernels") — see
    :func:`resolve_path`."""
    if D is None:
        return resolve_path(KERNEL_NAME)
    return resolve_path(
        KERNEL_NAME, path_geometry(D, block_size, pool_dtype),
        lambda: validate_call_geometry(D, block_size, pool_dtype))


def invocation_count(name=KERNEL_NAME) -> int:
    """Traced-call count (ops/pallas/counters; one bump per traced
    pallas_call, not per execution)."""
    return counters.count(name)


def _kernel(tbl_ref, pos_ref, nv_ref, *rest,
            sm_scale, bs, W, n_pages, quant, tree):
    """One (slot b, kv head) pair walks its block-table chain; carries
    online-softmax state in VMEM scratch across the page walk.  With
    ``quant`` the pools are int8 payloads and ``rest`` carries their
    scale refs — the page dequantizes (payload × per-head-per-position
    scale) inside the kernel, then the identical online softmax.  With
    ``tree`` a fourth scalar-prefetch operand carries the (B, W) int32
    ancestor bitmask and the triangular W-window mask is swapped for
    the per-lane tree mask (see paged_decode_attention)."""
    if tree:
        anc_ref, q_ref, k_ref, *rest = rest
    else:
        anc_ref, (q_ref, k_ref, *rest) = None, rest
    if quant:
        ks_ref, v_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        v_ref, o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    kv = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < nv_ref[b])
    def _page():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale      # (rep*W, D)
        lanes, d = q.shape
        k = k_ref[0, 0].astype(jnp.float32)                 # (bs, D)
        v = v_ref[0, 0].astype(jnp.float32)
        if quant:
            k = k * ks_ref[0, kv].astype(jnp.float32)[:, None]
            v = v * vs_ref[0, kv].astype(jnp.float32)[:, None]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        # logical key positions of this page vs each lane's extent:
        # lane l = r*W + w attends positions <= pos[b] + (l % W)
        k_pos = j * bs + jax.lax.broadcasted_iota(
            jnp.int32, (lanes, bs), 1)
        w = jax.lax.broadcasted_iota(jnp.int32, (lanes, bs), 0) % W
        if tree:
            # tree verify: cache rows pos[b]..pos[b]+W-1 hold the
            # window tokens in LANE order; lane w attends committed
            # history (rel < 0), itself (rel == w), and exactly its
            # strict tree ancestors (bit rel of anc[b, w])
            rel = k_pos - pos_ref[b]
            bits = jnp.stack([anc_ref[b, i] for i in range(W)])
            bits = jnp.tile(bits, lanes // W)[:, None]   # (lanes, 1)
            bit = (bits >> jnp.clip(rel, 0, 31)) & 1
            ok = (rel < 0) | (rel == w) | ((rel >= 0) & (rel < W)
                                           & (bit == 1))
            s = jnp.where(ok, s, _NEG_INF)
        else:
            s = jnp.where(k_pos <= pos_ref[b] + w, s, _NEG_INF)
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


def _num_valid_pages(pos, W, block_size, M):
    """Pages a slot's W-wide window can touch: logical positions
    0 .. pos + W - 1.  ONE definition shared by the runtime call and
    the kernel_spec model, so the static pass always verdicts the same
    table walk the kernel performs."""
    return jnp.clip((pos + (W - 1)) // block_size + 1, 1, M).astype(
        jnp.int32)


def _model_tables(B, M, n_pages, block_size, W, max_length):
    """Representative ragged (tables, pos) for the static checker:
    each slot holds a different valid extent, its live entries point at
    distinct allocated pages (1-based — page 0 is the reserved null
    page) and every padded entry carries the null page, exactly the
    engine's table convention."""
    import numpy as np

    pos = np.asarray([(7 + 13 * b) % max(max_length - W, 1)
                      for b in range(B)], np.int32)
    nv = np.asarray(_num_valid_pages(pos, W, block_size, M))
    tables = np.zeros((B, M), np.int32)
    page = 1
    for b in range(B):
        for j in range(int(nv[b])):
            tables[b, j] = page
            page = page % (n_pages - 1) + 1  # stay inside the pool
    return tables, pos


def _model_anc(B, W, branch=2):
    """Representative (B, W) ancestor bitmask for the static checker: a
    ``branch``-ary draft tree in window-lane order (lane 0 = root, lane
    w's parent = (w-1)//branch — topological, so every ancestor bit is
    < w), the same strict-ancestors-only convention the engines emit."""
    import numpy as np

    anc = np.zeros((W,), np.int32)
    for w in range(1, W):
        p = (w - 1) // max(int(branch), 1)
        anc[w] = anc[p] | np.int32(1 << p)
    return np.broadcast_to(anc, (B, W)).copy()


def _check_anc_model(anc, W):
    """Semantic validation of a model ancestor table — evaluated by the
    kernel_check index-map sweep (NUMPY values; the traced runtime maps
    never see concrete bits), so a malformed table surfaces as a
    located K004 ERROR on the tree spec instead of silently modeling a
    mask the kernel would never run."""
    import numpy as np

    a = np.asarray(anc)
    if a.ndim != 2 or a.shape[-1] != W:
        raise ValueError(
            "malformed ancestor table: shape %r, expected (B, W=%d)"
            % (a.shape, W))
    if W > 32:
        raise ValueError(
            "malformed ancestor table: W=%d exceeds the 32-lane int32 "
            "bitmask" % W)
    a = a.astype(np.int64)
    if (a[:, 0] != 0).any():
        raise ValueError(
            "malformed ancestor table: lane 0 is the shared root and "
            "has no ancestors (anc[:, 0] must be 0)")
    for w in range(1, W):
        col = a[:, w]
        if ((col < 0) | (col >= (1 << w))).any():
            raise ValueError(
                "malformed ancestor table: lane %d carries an ancestor "
                "bit >= its own lane — parents must precede children "
                "in window-lane order" % w)
        if (col & 1 == 0).any():
            raise ValueError(
                "malformed ancestor table: lane %d does not descend "
                "from the root (bit 0 unset)" % w)
        for j in range(1, w):
            on = (col >> j) & 1 == 1
            if (on & ((a[:, j] & ~col) != 0)).any():
                raise ValueError(
                    "malformed ancestor table: lane %d lists lane %d "
                    "as an ancestor but not lane %d's own ancestors — "
                    "ancestor sets must be transitively closed"
                    % (w, j, j))


def _page_index_tree_model(b, kv, j, tbl, pos, nv, anc):
    """kernel_check-side tree table walk: identical page selection,
    plus semantic validation of the ancestor table (concrete values are
    only available here — see _check_anc_model)."""
    _check_anc_model(anc, anc.shape[-1])
    return _page_index_tree(b, kv, j, tbl, pos, nv, anc)


def _scale_index_tree_model(b, kv, j, tbl, pos, nv, anc):
    _check_anc_model(anc, anc.shape[-1])
    return _scale_index_tree(b, kv, j, tbl, pos, nv, anc)


def kernel_spec(B, KV, rep, W, D, block_size, max_length,
                q_dtype="bfloat16", cache_dtype="float32",
                num_blocks=None, tables=None, pos=None, interpret=False,
                mesh_axis=None, tree=False, anc=None):
    """KernelSpec descriptor (mxtpu.analysis.kernel_check) for one
    paged_decode_attention call — the REAL index maps (_page_index /
    _scale_index, block-table walk and null-page-0 routing included)
    over model scalar-prefetch tables, so the static pass evaluates the
    same functions the pallas_call traces.

    ``mesh_axis=(axis_name, shards)`` describes the shard_map-partitioned
    call: ``KV`` stays the GLOBAL kv-head count and the spec's operand
    geometry becomes PER-SHARD (KV//shards heads per device), so K003
    prices the per-device VMEM the partitioned kernel actually uses.  A
    shard count that does not divide KV is recorded as-is — the static
    pass locates it as a K009 mesh-axis mismatch ERROR instead of this
    builder raising.

    ``tree=True`` (or an explicit ``anc`` table) describes the
    tree-verify variant: a fourth scalar-prefetch operand carries the
    (B, W) int32 ancestor bitmask and the spec's index maps validate
    its semantics (strict ancestors < w, rooted, transitively closed —
    _check_anc_model) during the K004 sweep, so a malformed table a
    caller audits is a located ERROR, recorded as-is rather than this
    builder raising."""
    import numpy as np

    from ...analysis.kernel_check import (BlockOperand, KernelSpec,
                                          ScalarPrefetch, ScratchOperand)

    bs = int(block_size)
    M = math.ceil(max_length / bs)
    name_sfx = ""
    if mesh_axis is not None:
        axis_name, shards = mesh_axis[0], int(mesh_axis[1])
        mesh_axis = (axis_name, shards, int(KV))
        if shards > 1 and KV % shards == 0:
            KV = KV // shards
        name_sfx = ",%s=%d" % (axis_name, shards)
    N = int(num_blocks) if num_blocks is not None else B * M + 1
    quant = str(cache_dtype) == "int8"
    # caller overrides apply INDEPENDENTLY (auditing a real engine's
    # table must never silently fall back to clean model tables just
    # because pos was omitted); the int32 cast mirrors the runtime's,
    # so the spec describes the call as traced, not the caller's
    # pre-cast dtype
    model_tables, model_pos = _model_tables(B, M, N, bs, W, max_length)
    tables = model_tables if tables is None \
        else np.asarray(tables).astype(np.int32)
    pos = model_pos if pos is None \
        else np.asarray(pos).astype(np.int32)
    nv = np.asarray(_num_valid_pages(pos, W, bs, M))
    tree = tree or anc is not None
    if tree:
        anc = _model_anc(B, W) if anc is None \
            else np.asarray(anc).astype(np.int32)
    lanes = rep * W
    if tree:
        q_im = lambda b, kv, j, tbl, pos, nv, anc: (  # noqa: E731
            b, kv, 0, 0)
        page_im, scale_im = _page_index_tree_model, _scale_index_tree_model
    else:
        q_im = lambda b, kv, j, tbl, pos, nv: (b, kv, 0, 0)  # noqa: E731
        page_im, scale_im = _page_index, _scale_index
    pool_dtype = "int8" if quant else cache_dtype
    # strict_dims: D (head_dim) and bs (block_size) are engine-chosen
    # tile parameters — the full-axis exemption must not absolve a
    # sub-tile choice there (bs IS the pool's full sublane axis); the
    # rep*W lane count and the scale rows are workload-determined and
    # pad legally
    operands = [
        BlockOperand("q", "in", (1, 1, lanes, D), (B, KV, lanes, D),
                     q_dtype, q_im, strict_dims=(-1,)),
        BlockOperand("pool_k", "in", (1, 1, bs, D), (N, KV, bs, D),
                     pool_dtype, page_im, strict_dims=(-1, -2)),
    ]
    if quant:
        operands.append(BlockOperand(
            "k_scales", "in", (1, KV, bs), (N, KV, bs), "float32",
            scale_im))
    operands.append(BlockOperand(
        "pool_v", "in", (1, 1, bs, D), (N, KV, bs, D), pool_dtype,
        page_im, strict_dims=(-1, -2)))
    if quant:
        operands.append(BlockOperand(
            "v_scales", "in", (1, KV, bs), (N, KV, bs), "float32",
            scale_im))
    operands.append(BlockOperand(
        "o", "out", (1, 1, lanes, D), (B, KV, lanes, D), q_dtype, q_im,
        strict_dims=(-1,)))
    prefetch = [ScalarPrefetch("tables", tables, valid_range=(0, N)),
                ScalarPrefetch("pos", pos, valid_range=(0, max_length)),
                ScalarPrefetch("nv", nv, valid_range=(1, M + 1))]
    if tree:
        # strict-ancestor bits are all < w <= W-1, so a well-formed
        # table stays below 2**(W-1)
        prefetch.append(ScalarPrefetch(
            "anc", anc, valid_range=(0, 1 << max(W - 1, 1))))
    return KernelSpec(
        "paged_attention[%s,W=%d,bs=%d,D=%d%s%s]"
        % (pool_dtype, W, bs, D, ",tree" if tree else "", name_sfx),
        grid=(B, KV, M),
        operands=operands,
        scratch=[ScratchOperand("m", (lanes, 1), "float32"),
                 ScratchOperand("l", (lanes, 1), "float32"),
                 ScratchOperand("acc", (lanes, D), "float32")],
        prefetch=prefetch,
        interpret=interpret,
        mesh_axis=mesh_axis)


def validate_call_geometry(D, block_size, pool_dtype, W=None):
    """The runtime mirror of the kernel_check static rules for THIS
    kernel: returns the list of violated-rule messages (empty = TPU
    legal).  K001 — head_dim must be lane-aligned (multiple of 128);
    K002 — block_size must be a multiple of the cache dtype's sublane
    tile (8 fp32 / 16 bf16 / 32 int8).  ``W`` (tree-verify calls only)
    adds the tree-mask table rule: the per-lane ancestor set rides an
    int32 bitmask whose bits are strict-ancestor lanes < w, so the
    window must fit W <= 32 lanes (31 draft nodes + root — the engine
    cap on ``spec_tree`` nodes)."""
    from ...analysis.memory_estimate import LANE, sublane_tile

    errs = []
    if D % LANE != 0:
        errs.append("K001: head_dim D=%d is not a multiple of the "
                    "%d-lane tile" % (D, LANE))
    sub = sublane_tile(pool_dtype)
    if block_size % sub != 0:
        errs.append("K002: block_size=%d is not a multiple of the %s "
                    "sublane tile %d (8 fp32 / 16 bf16 / 32 int8)"
                    % (block_size, pool_dtype, sub))
    if W is not None and W > 32:
        errs.append("K004: tree verify window W=%d exceeds the 32-lane "
                    "int32 ancestor bitmask — cap spec_tree at 31 "
                    "draft nodes (+ root)" % W)
    return errs


def _page_index(b, kv, j, tbl, pos, nv):
    """Block-table page selection for the pool BlockSpecs: valid steps
    read ``tables[b, j]``; steps past the slot's valid extent read the
    reserved null page 0 (one small no-op DMA, skipped by pl.when)."""
    return (jnp.where(j < nv[b], tbl[b, j], 0), kv, 0, 0)


def _scale_index(b, kv, j, tbl, pos, nv):
    """Scale-plane selection: the block is the page's WHOLE (KV, bs)
    plane — Mosaic refuses a size-1 second-to-last block dim on a KV-wide
    array — and the kernel picks its own head's row."""
    return (jnp.where(j < nv[b], tbl[b, j], 0), 0, 0)


def _page_index_tree(b, kv, j, tbl, pos, nv, anc):
    """Tree-verify variant: identical table walk, but the grid spec
    carries a fourth scalar-prefetch operand (the ancestor bitmask),
    so every index map takes it — the walk itself never reads it."""
    return (jnp.where(j < nv[b], tbl[b, j], 0), kv, 0, 0)


def _scale_index_tree(b, kv, j, tbl, pos, nv, anc):
    return (jnp.where(j < nv[b], tbl[b, j], 0), 0, 0)


def _call_local(qr, pool_k, pool_v, tables, pos, k_scales=None,
                v_scales=None, anc=None, *, sm_scale, W, interpret):
    """The unpartitioned pallas_call on (possibly per-shard) operands:
    qr is the kv-major (B, KV, rep*W, D) fold — under shard_map KV here
    is the PER-DEVICE kv-head count.  ``anc`` (B, W) int32 selects the
    tree-mask kernel variant (fourth scalar-prefetch operand)."""
    B, KV, lanes, D = qr.shape
    N, _, bs, _ = pool_k.shape
    M = tables.shape[-1]
    quant = k_scales is not None
    tree = anc is not None
    nv = _num_valid_pages(pos, W, bs, M)

    page_index = _page_index_tree if tree else _page_index
    scale_index = _scale_index_tree if tree else _scale_index
    if tree:
        q_im = lambda b, kv, j, tbl, pos, nv, anc: (  # noqa: E731
            b, kv, 0, 0)
    else:
        q_im = lambda b, kv, j, tbl, pos, nv: (b, kv, 0, 0)  # noqa: E731

    in_specs = [
        pl.BlockSpec((1, 1, lanes, D), q_im),
        pl.BlockSpec((1, 1, bs, D), page_index),
    ]
    args = [qr, pool_k]
    if quant:
        in_specs.append(pl.BlockSpec((1, KV, bs), scale_index))
        args.append(k_scales)
    in_specs.append(pl.BlockSpec((1, 1, bs, D), page_index))
    args.append(pool_v)
    if quant:
        in_specs.append(pl.BlockSpec((1, KV, bs), scale_index))
        args.append(v_scales)

    kernel = functools.partial(_kernel, sm_scale=sm_scale, bs=bs,
                               W=W, n_pages=M, quant=quant, tree=tree)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 if tree else 3,
        grid=(B, KV, M),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, lanes, D), q_im),
        scratch_shapes=[
            pltpu.VMEM((lanes, 1), jnp.float32),
            pltpu.VMEM((lanes, 1), jnp.float32),
            pltpu.VMEM((lanes, D), jnp.float32),
        ],
    )
    prefetch = (tables, pos, nv, anc) if tree else (tables, pos, nv)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, KV, lanes, D), qr.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="paged_attention_decode",
    )(*prefetch, *args)


def paged_decode_attention(q, pool_k, pool_v, tables, pos,
                           k_scales=None, v_scales=None, scale=None,
                           anc=None):
    """Ragged paged attention over block tables.

    q : (B, H, W, D) queries — W = 1 for the plain decode step, > 1 for
        a speculative verify window (lane w attends <= pos[b] + w).
    pool_k / pool_v : (N, KV, bs, D) page pools (float, or int8 payload
        when ``k_scales``/``v_scales`` (N, KV, bs) are given).
    tables : (B, M) int32 block tables (page 0 = reserved null page).
    pos : (B,) int32 per-slot positions (the last written position of
        window lane 0).
    anc : optional (B, W) int32 ancestor bitmask — tree-speculative
        verify.  The cache rows pos[b]..pos[b]+W-1 hold the window
        tokens in LANE order; bit j of ``anc[b, w]`` marks window lane
        j a STRICT tree ancestor of lane w (so bit 0, the shared root,
        is set for every lane w >= 1 and ``anc[b, 0] == 0``; bits are
        always < w, keeping the mask inside 31 bits for any W <= 32).
        Lane w then attends committed history (< pos[b]), itself, and
        exactly its ancestors — a degenerate chain
        ``anc[b, w] = (1 << w) - 1`` reproduces the triangular
        <= pos[b] + w window mask bit for bit.  The page walk is
        UNCHANGED: HBM traffic stays O(valid pages) for the whole tree.

    Returns (B, H, W, D) in q's dtype.  H = KV * rep, kv-major (head
    h = kv*rep + r — the models' GQA fold).  Inside an active
    ``head_sharding_scope`` (the decoder's tp-sharded cache) the call is
    shard_map-partitioned over the heads axis (``anc`` replicates like
    tables/pos).
    """
    B, H, W, D = q.shape
    N, KV, bs, _ = pool_k.shape
    rep = H // KV
    sm_scale = float(scale if scale is not None else 1.0 / math.sqrt(D))
    quant = k_scales is not None
    tree = anc is not None

    qr = q.reshape(B, KV, rep * W, D)
    tables = tables.astype(jnp.int32)
    pos = jnp.asarray(pos, jnp.int32).reshape(-1)
    if tree:
        anc = jnp.asarray(anc, jnp.int32).reshape(B, W)

    interpret = jax.default_backend() == "cpu"
    errs = validate_call_geometry(
        D, bs, "int8" if quant else str(pool_k.dtype),
        W=W if tree else None)
    if tree and any("K004" in e for e in errs):
        # the tree-mask width rule is a correctness bound, not a TPU
        # lowering rule — it holds in interpret mode too
        raise ValueError(
            "paged_decode_attention: "
            + "; ".join(e for e in errs if "K004" in e))
    if not interpret and errs:
        # runtime mirror of the static kernel_check pass: TPU-illegal
        # geometry fails HERE with the violated K-rule named instead of
        # deferring to an opaque Mosaic lowering error mid-compile
        raise ValueError(
            "paged_decode_attention: TPU-illegal call geometry — "
            + "; ".join(errs)
            + ". Fix the engine's block_size/head_dim (or run "
            "`python -m mxtpu.analysis kernel` for the full static "
            "verdict); interpret-mode CPU tests accept this "
            "geometry, hardware does not.")
    counters.bump(KERNEL_NAME)
    call = functools.partial(_call_local, sm_scale=sm_scale, W=W,
                             interpret=interpret)

    shard = current_head_sharding()
    if shard is not None and shard.shards > 1 \
            and KV % shard.shards == 0:
        from jax.sharding import PartitionSpec as P

        jm, axes = shard.mesh, shard.axes
        ax = axes[0] if len(axes) == 1 else tuple(axes)
        heads4 = P(None, ax, None, None)   # qr/out and page pools
        heads3 = P(None, ax, None)         # int8 scale planes
        repl = P()                         # tables / pos / anc
        if quant and tree:
            fn = lambda a, b_, c, d, e, f, g, h: call(  # noqa: E731
                a, b_, c, d, e, f, g, h)
            in_specs = (heads4, heads4, heads4, repl, repl,
                        heads3, heads3, repl)
            mapped = head_shard_map(fn, jm, in_specs, heads4)
            out = mapped(qr, pool_k, pool_v, tables, pos,
                         k_scales, v_scales, anc)
        elif quant:
            fn = lambda a, b_, c, d, e, f, g: call(  # noqa: E731
                a, b_, c, d, e, f, g)
            in_specs = (heads4, heads4, heads4, repl, repl,
                        heads3, heads3)
            mapped = head_shard_map(fn, jm, in_specs, heads4)
            out = mapped(qr, pool_k, pool_v, tables, pos,
                         k_scales, v_scales)
        elif tree:
            fn = lambda a, b_, c, d, e, h: call(  # noqa: E731
                a, b_, c, d, e, None, None, h)
            in_specs = (heads4, heads4, heads4, repl, repl, repl)
            mapped = head_shard_map(fn, jm, in_specs, heads4)
            out = mapped(qr, pool_k, pool_v, tables, pos, anc)
        else:
            fn = lambda a, b_, c, d, e: call(a, b_, c, d, e)  # noqa: E731
            in_specs = (heads4, heads4, heads4, repl, repl)
            mapped = head_shard_map(fn, jm, in_specs, heads4)
            out = mapped(qr, pool_k, pool_v, tables, pos)
    else:
        out = call(qr, pool_k, pool_v, tables, pos, k_scales, v_scales,
                   anc)
    return out.reshape(B, KV, rep, W, D).reshape(B, H, W, D)


def xla_reference(q, pool_k, pool_v, tables, pos, k_scales=None,
                  v_scales=None, scale=None, anc=None):
    """The XLA gather path on raw arrays — the reference the kernel is
    verified against (the same math the models' step_pages/verify_pages
    run when the gate is off).  ``anc`` (B, W) int32 applies the tree
    ancestor mask (see paged_decode_attention)."""
    B, H, W, D = q.shape
    N, KV, bs, _ = pool_k.shape
    M = tables.shape[-1]
    rep = H // KV
    sm_scale = float(scale if scale is not None else 1.0 / math.sqrt(D))
    t = tables.astype(jnp.int32)
    pos = jnp.asarray(pos, jnp.int32).reshape(-1)

    def gather(pool, scales):
        g = pool[t].astype(jnp.float32)          # (B, M, KV, bs, D)
        if scales is not None:
            g = g * scales[t].astype(jnp.float32)[..., None]
        return g.transpose(0, 2, 1, 3, 4).reshape(B, KV, M * bs, D)

    keys = gather(pool_k, k_scales)
    values = gather(pool_v, v_scales)
    qr = q.reshape(B, KV, rep * W, D).astype(jnp.float32) * sm_scale
    s = jnp.einsum("bkld,bktd->bklt", qr, keys,
                   preferred_element_type=jnp.float32)
    k_pos = jnp.arange(M * bs, dtype=jnp.int32)
    w = jnp.arange(rep * W, dtype=jnp.int32) % W
    if anc is not None:
        bits = jnp.asarray(anc, jnp.int32).reshape(B, W)[:, w]
        rel = k_pos[None, None, :] - pos[:, None, None]    # (B, 1, t)
        bit = (bits[:, :, None] >> jnp.clip(rel, 0, 31)) & 1
        valid = ((rel < 0) | (rel == w[None, :, None])
                 | ((rel >= 0) & (rel < W) & (bit == 1)))  # (B, l, t)
    else:
        valid = (k_pos[None, None, :]
                 <= pos[:, None, None] + w[None, :, None])  # (B, l, t)
    s = jnp.where(valid[:, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bklt,bktd->bkld", p, values)
    return o.reshape(B, KV, rep, W, D).reshape(B, H, W, D).astype(
        q.dtype)


@register_op("paged_decode_attention", differentiable=False)
def paged_decode_attention_op(q, pool_k, pool_v, tables, pos,
                              k_scales=None, v_scales=None, scale=None,
                              anc=None):
    return paged_decode_attention(q, pool_k, pool_v, tables, pos,
                                  k_scales=k_scales, v_scales=v_scales,
                                  scale=scale, anc=anc)
