"""Contrib ops (parity: src/operator/contrib/ — most importantly the
interleaved multi-head-attention fused kernels in transformer.cc used by
GluonNLP BERT: _contrib_interleaved_matmul_selfatt_qk / _valatt and the
encdec variants, plus arange_like, index ops, roi_align).

The interleaved layout the reference fuses by hand — projections stored as
(seq, batch, 3*heads*dim) with q/k/v interleaved per head — is kept at the
API boundary; XLA fuses the reshape+matmul chain, and the full-attention
hot path additionally has a Pallas flash-attention kernel
(mxtpu/ops/pallas_attention.py) selected by gluon.nn.MultiHeadAttention.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..base import register_op


def _split_qkv_interleaved(qkv, heads):
    """(S, B, 3*H*D) interleaved per-head -> q, k, v each (B*H, S, D)."""
    S, B, P = qkv.shape
    D = P // (3 * heads)
    x = qkv.reshape(S, B, heads, 3, D)
    q = x[:, :, :, 0]  # (S, B, H, D)
    k = x[:, :, :, 1]
    v = x[:, :, :, 2]
    def to_bhsd(t):
        return t.transpose(1, 2, 0, 3).reshape(B * heads, S, D)
    return to_bhsd(q), to_bhsd(k), to_bhsd(v)


@register_op("interleaved_matmul_selfatt_qk",
             aliases=("_contrib_interleaved_matmul_selfatt_qk",))
def interleaved_matmul_selfatt_qk(queries_keys_values, heads=1):
    q, k, _ = _split_qkv_interleaved(queries_keys_values, heads)
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
    return jnp.matmul(q * scale, jnp.swapaxes(k, -1, -2))  # (B*H, S, S)


@register_op("interleaved_matmul_selfatt_valatt",
             aliases=("_contrib_interleaved_matmul_selfatt_valatt",))
def interleaved_matmul_selfatt_valatt(queries_keys_values, attention, heads=1):
    S, B, P = queries_keys_values.shape
    _, _, v = _split_qkv_interleaved(queries_keys_values, heads)
    out = jnp.matmul(attention, v)  # (B*H, S, D)
    D = P // (3 * heads)
    return out.reshape(B, heads, S, D).transpose(2, 0, 1, 3).reshape(S, B, heads * D)


@register_op("interleaved_matmul_encdec_qk",
             aliases=("_contrib_interleaved_matmul_encdec_qk",))
def interleaved_matmul_encdec_qk(queries, keys_values, heads=1):
    Sq, B, HD = queries.shape
    D = HD // heads
    q = queries.reshape(Sq, B, heads, D).transpose(1, 2, 0, 3).reshape(B * heads, Sq, D)
    Sk = keys_values.shape[0]
    kv = keys_values.reshape(Sk, B, heads, 2, D)
    k = kv[:, :, :, 0].transpose(1, 2, 0, 3).reshape(B * heads, Sk, D)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, q.dtype))
    return jnp.matmul(q * scale, jnp.swapaxes(k, -1, -2))


@register_op("interleaved_matmul_encdec_valatt",
             aliases=("_contrib_interleaved_matmul_encdec_valatt",))
def interleaved_matmul_encdec_valatt(keys_values, attention, heads=1):
    Sk, B, P = keys_values.shape
    D = P // (2 * heads)
    kv = keys_values.reshape(Sk, B, heads, 2, D)
    v = kv[:, :, :, 1].transpose(1, 2, 0, 3).reshape(B * heads, Sk, D)
    out = jnp.matmul(attention, v)  # (B*H, Sq, D)
    Sq = attention.shape[1]
    return out.reshape(B, heads, Sq, D).transpose(2, 0, 1, 3).reshape(Sq, B, heads * D)


@register_op("arange_like", aliases=("_contrib_arange_like",),
             differentiable=False)
def arange_like(data, start=0.0, step=1.0, repeat=1, axis=None):
    if axis is None:
        n = -(-data.size // repeat)
        out = jnp.arange(start, start + step * n, step, dtype=data.dtype)
        if repeat > 1:
            out = jnp.repeat(out, repeat)[:data.size]
        return out.reshape(data.shape)
    n = -(-data.shape[axis] // repeat)
    out = jnp.arange(start, start + step * n, step, dtype=data.dtype)
    if repeat > 1:
        out = jnp.repeat(out, repeat)[:data.shape[axis]]
    return out


@register_op("div_sqrt_dim", aliases=("_contrib_div_sqrt_dim",))
def div_sqrt_dim(data):
    return data / jnp.sqrt(jnp.asarray(data.shape[-1], data.dtype))


@register_op("index_copy", aliases=("_contrib_index_copy",))
def index_copy(old_tensor, index_vector, new_tensor):
    return old_tensor.at[index_vector.astype(jnp.int32)].set(new_tensor)


@register_op("index_array", aliases=("_contrib_index_array",),
             differentiable=False)
def index_array(data, axes=None):
    shape = data.shape
    if axes is None:
        axes = tuple(range(len(shape)))
    grids = jnp.meshgrid(*[jnp.arange(shape[a]) for a in axes], indexing="ij")
    return jnp.stack(grids, axis=-1).astype(jnp.int64)


@register_op("ROIAlign", aliases=("_contrib_ROIAlign", "roi_align"))
def roi_align(data, rois, pooled_size=(7, 7), spatial_scale=1.0,
              sample_ratio=2, position_sensitive=False, aligned=False):
    """ROIAlign (Mask-RCNN style), vmapped bilinear sampling over rois."""
    B, C, H, W = data.shape
    ph, pw = pooled_size
    sr = max(int(sample_ratio), 1)

    def one_roi(roi):
        bidx = roi[0].astype(jnp.int32)
        offset = 0.5 if aligned else 0.0
        x1 = roi[1] * spatial_scale - offset
        y1 = roi[2] * spatial_scale - offset
        x2 = roi[3] * spatial_scale - offset
        y2 = roi[4] * spatial_scale - offset
        rw = jnp.maximum(x2 - x1, 1.0 if not aligned else 1e-6)
        rh = jnp.maximum(y2 - y1, 1.0 if not aligned else 1e-6)
        bin_h = rh / ph
        bin_w = rw / pw
        iy = (jnp.arange(ph)[:, None, None, None]
              * bin_h + y1 + (jnp.arange(sr)[None, None, :, None] + 0.5) * bin_h / sr)
        ix = (jnp.arange(pw)[None, :, None, None]
              * bin_w + x1 + (jnp.arange(sr)[None, None, None, :] + 0.5) * bin_w / sr)
        iy = jnp.broadcast_to(iy, (ph, pw, sr, sr)).reshape(-1)
        ix = jnp.broadcast_to(ix, (ph, pw, sr, sr)).reshape(-1)
        img = data[bidx]  # (C, H, W)
        y0 = jnp.clip(jnp.floor(iy).astype(jnp.int32), 0, H - 1)
        x0 = jnp.clip(jnp.floor(ix).astype(jnp.int32), 0, W - 1)
        y1i = jnp.clip(y0 + 1, 0, H - 1)
        x1i = jnp.clip(x0 + 1, 0, W - 1)
        wy = jnp.clip(iy, 0, H - 1) - y0
        wx = jnp.clip(ix, 0, W - 1) - x0
        v = (img[:, y0, x0] * (1 - wy) * (1 - wx)
             + img[:, y0, x1i] * (1 - wy) * wx
             + img[:, y1i, x0] * wy * (1 - wx)
             + img[:, y1i, x1i] * wy * wx)  # (C, ph*pw*sr*sr)
        v = v.reshape(C, ph, pw, sr * sr).mean(axis=-1)
        return v

    return jax.vmap(one_roi)(rois)


@register_op("quantize", aliases=("_contrib_quantize",), differentiable=False,
             num_outputs=3)
def quantize(data, min_range, max_range, out_type="uint8"):
    scale = 255.0 / (max_range - min_range)
    q = jnp.clip(jnp.round((data - min_range) * scale), 0, 255)
    return q.astype(jnp.uint8), min_range, max_range


@register_op("dequantize", aliases=("_contrib_dequantize",),
             differentiable=False)
def dequantize(data, min_range, max_range, out_type="float32"):
    scale = (max_range - min_range) / 255.0
    return data.astype(jnp.float32) * scale + min_range


@register_op("rms_norm", aliases=("_contrib_rms_norm",))
def rms_norm(data, gamma, eps=1e-6):
    """RMSNorm (no reference analogue — LayerNorm sans mean; the Llama-era
    norm). Computed in fp32 for bf16 stability, cast back."""
    dt = data.dtype
    x = data.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * gamma.astype(jnp.float32)).astype(dt)


@register_op("rope", aliases=("_contrib_rope",))
def rope(data, base=10000.0, offset=0, scale=1.0):
    """Rotary position embedding over the last dim of (B, H, T, D) or
    (B, T, D). Pairs are (x[..., :D/2], x[..., D/2:]) — the Llama layout.

    ``offset`` may be a scalar (python int or traced — every row sits at
    the same position), a (B,) vector: row b's positions start at
    offset[b] (continuous-batching decode, where each cache slot is at
    its own depth), or a (B, T) matrix of ABSOLUTE positions: element
    (b, t) is rotated at offset[b, t] (tree-speculative verify, where
    window lane t sits at its own tree depth rather than at t)."""
    dt = data.dtype
    x = data.astype(jnp.float32)
    D = x.shape[-1]
    T = x.shape[-2]
    half = D // 2
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if getattr(offset, "ndim", 0) >= 2:
        # int->fp32 is exact below 2^24, so wherever
        # offset[b, t] == offset[b] + t this path is bit-identical to
        # the (B,) branch (and hence to the sequential decode step)
        pos = jnp.asarray(offset, jnp.float32) * scale       # (B, T)
        ang = pos[..., None] * freqs                         # (B, T, D/2)
        shape = (x.shape[0],) + (1,) * (x.ndim - 3) + (T, half)
    elif getattr(offset, "ndim", 0) >= 1:
        off = jnp.asarray(offset, jnp.float32).reshape(-1)   # (B,)
        pos = (jnp.arange(T, dtype=jnp.float32)[None, :]
               + off[:, None]) * scale                       # (B, T)
        ang = pos[..., None] * freqs                         # (B, T, D/2)
        shape = (x.shape[0],) + (1,) * (x.ndim - 3) + (T, half)
    else:
        pos = (jnp.arange(T, dtype=jnp.float32) + offset) * scale
        ang = pos[:, None] * freqs[None, :]                  # (T, D/2)
        shape = (1,) * (x.ndim - 2) + (T, half)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    sin = sin.reshape(shape)
    cos = cos.reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(dt)


@register_op("mrope", aliases=("_contrib_mrope",))
def mrope(data, positions, sections=(16, 24, 24), base=10000.0):
    """Rotary position embedding with several position streams
    (Qwen2-VL's multimodal form) over the last dim of (B, H, T, D) or
    (B, T, D), pairs as ``rope`` has them.  ``positions`` is
    (streams, T) or (streams, B, T), absolute; of the D/2 frequencies the
    first ``sections[0]`` turn by stream 0, the next ``sections[1]`` by
    stream 1, and so on (``sum(sections) == D/2``).  With every stream
    equal to 0..T-1 it is ``rope`` bit for bit."""
    import numpy as onp

    dt = data.dtype
    x = data.astype(jnp.float32)
    half = x.shape[-1] // 2
    if sum(sections) != half:
        raise ValueError("sections %r do not add up to %d frequencies"
                         % (tuple(sections), half))
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    stream = onp.repeat(onp.arange(len(sections)), sections)     # (D/2,)
    pos = jnp.asarray(positions, jnp.float32)
    ang = jnp.moveaxis(pos[stream], 0, -1) * freqs     # (.., T, D/2)
    lead = (1,) if pos.ndim == 2 else (x.shape[0],)
    shape = lead + (1,) * (x.ndim - 3) + ang.shape[-2:]
    sin, cos = jnp.sin(ang).reshape(shape), jnp.cos(ang).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(dt)


@register_op("masked_softmax", aliases=("_contrib_masked_softmax",))
def masked_softmax(data, mask=None, axis=-1, temperature=1.0):
    """Softmax with additive/boolean mask (parity: masked_softmax in later
    reference lines; fp32 accumulation)."""
    dt = data.dtype
    x = data.astype(jnp.float32)
    if temperature != 1.0:
        x = x / temperature
    if mask is not None:
        if mask.dtype == jnp.bool_:
            x = jnp.where(mask, x, -jnp.inf)
        else:
            x = x + mask.astype(jnp.float32)
    out = jax.nn.softmax(x, axis=axis)
    return out.astype(dt)


@register_op("batch_dot_attn")
def batch_dot_attn(q, k):
    """Attention scores q·kᵀ over (B, H, T, D) (parity: the qk half of
    _contrib_interleaved_matmul_selfatt_qk, batch-major layout). fp32
    accumulation on the MXU via preferred_element_type; true-fp32 dot for
    fp32 inputs (jax>=0.9 defaults fp32 matmuls to the bf16 MXU path)."""
    from .tensor import matmul_precision
    return jnp.einsum("bhqd,bhkd->bhqk", q, k,
                      preferred_element_type=jnp.float32,
                      precision=matmul_precision(q, k)).astype(q.dtype)


@register_op("attn_value")
def attn_value(attn, v):
    """Attention-weighted values (parity: the valatt half of the fused
    interleaved kernels, batch-major)."""
    from .tensor import matmul_precision
    return jnp.einsum("bhqk,bhkd->bhqd", attn, v,
                      preferred_element_type=jnp.float32,
                      precision=matmul_precision(attn, v)).astype(v.dtype)


@register_op("causal_mask_fill")
def causal_mask_fill(scores, value=-1e9):
    """Add a causal mask to (..., Tq, Tk) scores."""
    Tq, Tk = scores.shape[-2], scores.shape[-1]
    mask = jnp.tril(jnp.ones((Tq, Tk), jnp.bool_), Tk - Tq)
    return jnp.where(mask, scores, jnp.asarray(value, scores.dtype))


@register_op("ring_attention")
def ring_attention_op(q, k, v, causal=False, scale=None, _mesh=None,
                      seq_axis="sp", batch_axis="dp"):
    """Sequence-parallel exact attention (shard_map + ppermute over the
    mesh's sp axis). Registered as an op so the imperative autograd tape
    records it like any other (no reference analogue — SURVEY §2.3 lists
    SP as absent upstream)."""
    from ..parallel.ring_attention import ring_self_attention
    if _mesh is None:
        raise ValueError("ring_attention requires _mesh=DeviceMesh")
    return ring_self_attention(q, k, v, _mesh, causal=causal, scale=scale,
                               batch_axis=batch_axis, seq_axis=seq_axis)


# ------------------------------------------------------------ bounding boxes
# (parity: src/operator/contrib/bounding_box.cc — _contrib_box_iou /
# _contrib_box_nms.  The reference implements greedy NMS as a CUDA kernel
# over sorted candidates; here the candidate order and O(N^2) IoU matrix
# are static-shaped so XLA can compile them, and the sequential greedy
# suppression is a lax.fori_loop over the sorted list.)

def _boxes_to_corner(b, fmt):
    if fmt == "corner":
        return b
    if fmt == "center":  # (x, y, w, h) -> (xmin, ymin, xmax, ymax)
        x, y, w, h = b[..., 0], b[..., 1], b[..., 2], b[..., 3]
        return jnp.stack([x - w / 2, y - h / 2, x + w / 2, y + h / 2],
                         axis=-1)
    raise ValueError("box format must be 'corner' or 'center', got %r"
                     % (fmt,))


def _boxes_from_corner(b, fmt):
    if fmt == "corner":
        return b
    x0, y0, x1, y1 = b[..., 0], b[..., 1], b[..., 2], b[..., 3]
    return jnp.stack([(x0 + x1) / 2, (y0 + y1) / 2, x1 - x0, y1 - y0],
                     axis=-1)


def _pairwise_iou(a, b):
    """a (N, 4), b (M, 4) corner boxes -> (N, M) IoU."""
    tl = jnp.maximum(a[:, None, :2], b[None, :, :2])
    br = jnp.minimum(a[:, None, 2:], b[None, :, 2:])
    wh = jnp.maximum(br - tl, 0.0)
    inter = wh[..., 0] * wh[..., 1]
    area_a = jnp.maximum((a[:, 2] - a[:, 0]) * (a[:, 3] - a[:, 1]), 0.0)
    area_b = jnp.maximum((b[:, 2] - b[:, 0]) * (b[:, 3] - b[:, 1]), 0.0)
    union = area_a[:, None] + area_b[None, :] - inter
    return jnp.where(union > 0, inter / union, 0.0)


@register_op("box_iou", aliases=("_contrib_box_iou",),
             differentiable=False)
def box_iou(lhs, rhs, format="corner"):
    """IoU between every box in lhs (..., 4) and every box in rhs
    (..., 4); output shape lhs.shape[:-1] + rhs.shape[:-1] (parity:
    _contrib_box_iou, bounding_box.cc)."""
    l = _boxes_to_corner(lhs, format).reshape(-1, 4)
    r = _boxes_to_corner(rhs, format).reshape(-1, 4)
    out = _pairwise_iou(l, r)
    return out.reshape(tuple(lhs.shape[:-1]) + tuple(rhs.shape[:-1]))


@register_op("box_nms", aliases=("_contrib_box_nms",),
             differentiable=False)
def box_nms(data, overlap_thresh=0.5, valid_thresh=0.0, topk=-1,
            coord_start=2, score_index=1, id_index=-1, background_id=-1,
            force_suppress=False, in_format="corner",
            out_format="corner"):
    """Greedy non-maximum suppression (parity: _contrib_box_nms).

    data: (..., N, K) rows [.., id?, score, x1, y1, x2, y2, ..]; output
    has the same shape with rows sorted by score and suppressed/invalid
    rows overwritten with -1.
    """
    shape = data.shape
    N, K = shape[-2], shape[-1]
    flat = data.reshape((-1, N, K))

    def one(batch):
        scores = batch[:, score_index]
        boxes = _boxes_to_corner(
            batch[:, coord_start:coord_start + 4], in_format)
        valid = scores > valid_thresh
        if id_index >= 0:
            ids = batch[:, id_index]
            if background_id >= 0:
                valid = valid & (ids != background_id)
        # sort by score desc, invalid entries last
        order = jnp.argsort(jnp.where(valid, -scores, jnp.inf))
        sbatch = batch[order]
        svalid = valid[order]
        if topk > 0:
            svalid = svalid & (jnp.arange(N) < topk)
        sboxes = boxes[order]
        iou = _pairwise_iou(sboxes, sboxes)
        sup = (iou > overlap_thresh) & jnp.triu(
            jnp.ones((N, N), jnp.bool_), k=1)
        if id_index >= 0 and not force_suppress:
            sids = sbatch[:, id_index]
            sup = sup & (sids[:, None] == sids[None, :])

        def body(i, keep):
            # row i suppresses lower-scored overlaps only if itself kept
            return keep & ~(sup[i] & keep[i])

        keep = jax.lax.fori_loop(0, N, body, svalid)
        out = sbatch
        if out_format != in_format:
            coords = _boxes_from_corner(sboxes, out_format)
            out = jnp.concatenate(
                [out[:, :coord_start], coords,
                 out[:, coord_start + 4:]], axis=1)
        return jnp.where(keep[:, None], out,
                         jnp.full_like(out, -1.0))

    return jax.vmap(one)(flat).reshape(shape)


# ------------------------------------------------------------ SSD multibox
# (parity: src/operator/contrib/multibox_prior.cc / multibox_target.cc /
# multibox_detection.cc — the reference's SSD training + inference ops)

@register_op("multibox_prior", aliases=("_contrib_MultiBoxPrior",),
             differentiable=False)
def multibox_prior(data, sizes=(1.0,), ratios=(1.0,), clip=False,
                   steps=(-1.0, -1.0), offsets=(0.5, 0.5)):
    """Anchor generation from a (B, C, H, W) feature map: per cell,
    len(sizes) + len(ratios) - 1 normalized corner boxes
    ((size_i, ratio_0) for all i, then (size_0, ratio_j) for j>0)."""
    H, W = data.shape[2], data.shape[3]
    sizes = [float(s) for s in (sizes if hasattr(sizes, "__len__")
                                else [sizes])]
    ratios = [float(r) for r in (ratios if hasattr(ratios, "__len__")
                                 else [ratios])]
    step_y = float(steps[0]) if steps[0] > 0 else 1.0 / H
    step_x = float(steps[1]) if steps[1] > 0 else 1.0 / W
    cy = (jnp.arange(H, dtype=jnp.float32) + float(offsets[0])) * step_y
    cx = (jnp.arange(W, dtype=jnp.float32) + float(offsets[1])) * step_x
    gy, gx = jnp.meshgrid(cy, cx, indexing="ij")  # (H, W)

    halves = []  # (half_w, half_h) per anchor kind
    for s in sizes:
        r = ratios[0]
        halves.append((s * math.sqrt(r) / 2.0, s / math.sqrt(r) / 2.0))
    for r in ratios[1:]:
        s = sizes[0]
        halves.append((s * math.sqrt(r) / 2.0, s / math.sqrt(r) / 2.0))

    boxes = []
    for hw, hh in halves:
        boxes.append(jnp.stack([gx - hw, gy - hh, gx + hw, gy + hh],
                               axis=-1))  # (H, W, 4)
    out = jnp.stack(boxes, axis=2).reshape(1, H * W * len(halves), 4)
    if clip:
        out = jnp.clip(out, 0.0, 1.0)
    return out.astype(jnp.float32)


def _mb_center(b):
    """corner (x1,y1,x2,y2) -> (cx, cy, w, h)"""
    return ((b[..., 0] + b[..., 2]) / 2, (b[..., 1] + b[..., 3]) / 2,
            b[..., 2] - b[..., 0], b[..., 3] - b[..., 1])


@register_op("multibox_target", aliases=("_contrib_MultiBoxTarget",),
             differentiable=False, num_outputs=3)
def multibox_target(anchor, label, cls_pred, overlap_threshold=0.5,
                    ignore_label=-1.0, negative_mining_ratio=-1.0,
                    negative_mining_thresh=0.5, minimum_negative_samples=0,
                    variances=(0.1, 0.1, 0.2, 0.2)):
    """SSD target assignment.  anchor (1, N, 4) corners; label
    (B, M, 5) rows [cls, x1, y1, x2, y2] padded with -1; cls_pred
    (B, num_cls+1, N) (used for online hard negative mining).

    Returns (box_target (B, N*4), box_mask (B, N*4), cls_target (B, N))
    — cls_target is shifted by +1 (0 = background), matching the
    reference."""
    A = anchor.reshape(-1, 4)
    N = A.shape[0]
    v = jnp.asarray(variances, jnp.float32)

    def one(lab, cp):
        gt_valid = lab[:, 0] >= 0  # (M,)
        gt = lab[:, 1:5]
        iou = _pairwise_iou(A, gt)  # (N, M)
        iou = jnp.where(gt_valid[None, :], iou, -1.0)
        # (a) each valid GT claims its best anchor (bipartite pass)
        best_anchor = jnp.argmax(iou, axis=0)  # (M,)
        forced = jnp.zeros((N,), jnp.int32) - 1
        # later GTs overwrite earlier on conflict, like the sequential ref
        for m in range(gt.shape[0]):
            forced = jnp.where(
                (jnp.arange(N) == best_anchor[m]) & gt_valid[m],
                m, forced)
        # (b) threshold pass on the rest
        best_gt = jnp.argmax(iou, axis=1)           # (N,)
        best_iou = jnp.max(iou, axis=1)
        match = jnp.where(forced >= 0, forced,
                          jnp.where(best_iou >= overlap_threshold,
                                    best_gt, -1))
        matched = match >= 0
        mg = jnp.clip(match, 0, gt.shape[0] - 1)
        g = gt[mg]                                   # (N, 4)
        acx, acy, aw, ah = _mb_center(A)
        gcx, gcy, gw, gh = _mb_center(g)
        eps = 1e-8
        tx = (gcx - acx) / jnp.maximum(aw, eps) / v[0]
        ty = (gcy - acy) / jnp.maximum(ah, eps) / v[1]
        tw = jnp.log(jnp.maximum(gw, eps) / jnp.maximum(aw, eps)) / v[2]
        th = jnp.log(jnp.maximum(gh, eps) / jnp.maximum(ah, eps)) / v[3]
        bt = jnp.stack([tx, ty, tw, th], axis=-1)    # (N, 4)
        bt = jnp.where(matched[:, None], bt, 0.0)
        bm = jnp.where(matched[:, None],
                       jnp.ones((N, 4), jnp.float32), 0.0)
        ct = jnp.where(matched, lab[mg, 0] + 1.0, 0.0)
        if negative_mining_ratio > 0:
            # hard negatives: unmatched anchors ranked by max non-bg conf
            max_conf = jnp.max(cp[1:, :], axis=0)    # (N,)
            neg_order = jnp.argsort(
                jnp.where(matched, -jnp.inf, max_conf))[::-1]
            n_pos = jnp.sum(matched)
            quota = jnp.maximum(
                (negative_mining_ratio * n_pos).astype(jnp.int32),
                minimum_negative_samples)
            rank = jnp.zeros((N,), jnp.int32).at[neg_order].set(
                jnp.arange(N, dtype=jnp.int32))
            keep_neg = (~matched) & (rank < quota)
            ct = jnp.where(matched, ct,
                           jnp.where(keep_neg, 0.0, float(ignore_label)))
        return bt.reshape(-1), bm.reshape(-1), ct

    bt, bm, ct = jax.vmap(one)(label.astype(jnp.float32),
                               cls_pred.astype(jnp.float32))
    return bt, bm, ct


@register_op("multibox_detection", aliases=("_contrib_MultiBoxDetection",),
             differentiable=False)
def multibox_detection(cls_prob, loc_pred, anchor, clip=True,
                       threshold=0.01, background_id=0,
                       nms_threshold=0.5, force_suppress=False,
                       variances=(0.1, 0.1, 0.2, 0.2), nms_topk=-1):
    """SSD inference: decode loc_pred against anchors, pick each
    anchor's best non-background class, then box_nms.  cls_prob
    (B, num_cls+1, N), loc_pred (B, N*4), anchor (1, N, 4).
    Output (B, N, 6) rows [cls_id, score, x1, y1, x2, y2], -1-filled."""
    A = anchor.reshape(-1, 4)
    N = A.shape[0]
    v = jnp.asarray(variances, jnp.float32)
    acx, acy, aw, ah = _mb_center(A)

    def one(cp, lp):
        p = lp.reshape(N, 4)
        cx = p[:, 0] * v[0] * aw + acx
        cy = p[:, 1] * v[1] * ah + acy
        w_ = jnp.exp(p[:, 2] * v[2]) * aw
        h_ = jnp.exp(p[:, 3] * v[3]) * ah
        boxes = jnp.stack([cx - w_ / 2, cy - h_ / 2,
                           cx + w_ / 2, cy + h_ / 2], axis=-1)
        if clip:
            boxes = jnp.clip(boxes, 0.0, 1.0)
        if background_id != 0:
            raise ValueError("multibox_detection: background_id must "
                             "be 0 (reference default)")
        fg = cp[1:]                                  # (num_cls, N)
        cls_id = jnp.argmax(fg, axis=0).astype(jnp.float32)
        score = jnp.max(fg, axis=0)
        keep = score > threshold
        rows = jnp.concatenate(
            [jnp.where(keep, cls_id, -1.0)[:, None],
             jnp.where(keep, score, -1.0)[:, None], boxes], axis=-1)
        return rows

    det = jax.vmap(one)(cls_prob.astype(jnp.float32),
                        loc_pred.astype(jnp.float32))  # (B, N, 6)
    return box_nms(det, overlap_thresh=nms_threshold, valid_thresh=0.0,
                   topk=nms_topk, coord_start=2, score_index=1,
                   id_index=0, force_suppress=force_suppress)


# ------------------------------------------------------------ fft / ifft

@register_op("fft", aliases=("_contrib_fft",), differentiable=False)
def fft_op(data, compute_size=128):
    """(parity: src/operator/contrib/fft.cc): real input (..., d) ->
    interleaved re/im (..., 2d)."""
    f = jnp.fft.fft(data.astype(jnp.float32), axis=-1)
    out = jnp.stack([f.real, f.imag], axis=-1)
    return out.reshape(*data.shape[:-1], 2 * data.shape[-1]).astype(
        jnp.float32)


@register_op("ifft", aliases=("_contrib_ifft",), differentiable=False)
def ifft_op(data, compute_size=128):
    """Inverse of fft's interleaved layout: (..., 2d) -> real (..., d).
    NOTE (reference parity): upstream ifft does NOT normalize by d — it
    returns d * ifft(x); we match numpy semantics * d for parity."""
    d = data.shape[-1] // 2
    c = data.reshape(*data.shape[:-1], d, 2)
    z = c[..., 0] + 1j * c[..., 1]
    return (jnp.fft.ifft(z, axis=-1).real * d).astype(jnp.float32)


# ---------------------------------------------------------------------------
# round-5 tail (VERDICT r4 item 2)

@register_op("AdaptiveAvgPooling2D",
             aliases=("_contrib_AdaptiveAvgPooling2D",))
def adaptive_avg_pooling2d(data, output_size=(1, 1)):
    """Adaptive average pooling to a fixed output grid
    (src/operator/contrib/adaptive_avg_pooling.cc).  Bin boundaries use
    the floor/ceil split of the reference kernel; implemented as a
    masked mean over static output cells, so it stays jit-static for any
    input size."""
    if isinstance(output_size, int):
        output_size = (output_size, output_size)
    B, C, H, W = data.shape
    oh, ow = output_size
    import numpy as _onp
    hstart = _onp.floor(_onp.arange(oh) * H / oh).astype(int)
    hend = _onp.ceil((_onp.arange(oh) + 1) * H / oh).astype(int)
    wstart = _onp.floor(_onp.arange(ow) * W / ow).astype(int)
    wend = _onp.ceil((_onp.arange(ow) + 1) * W / ow).astype(int)
    mh = (_onp.arange(H)[None, :] >= hstart[:, None]) \
        & (_onp.arange(H)[None, :] < hend[:, None])       # (oh, H)
    mw = (_onp.arange(W)[None, :] >= wstart[:, None]) \
        & (_onp.arange(W)[None, :] < wend[:, None])       # (ow, W)
    mh = jnp.asarray(mh, data.dtype) / jnp.asarray(
        (hend - hstart)[:, None], data.dtype)
    mw = jnp.asarray(mw, data.dtype) / jnp.asarray(
        (wend - wstart)[:, None], data.dtype)
    # mean over each bin: two contractions ride the MXU
    return jnp.einsum("bchw,oh,pw->bcop", data, mh, mw)


@register_op("bipartite_matching", differentiable=False, num_outputs=2,
             aliases=("_contrib_bipartite_matching",))
def bipartite_matching(data, is_ascend=False, threshold=0.0, topk=-1):
    """Greedy bipartite matching over a score matrix (bounding_box.cc
    BipartiteMatching; the SSD target-assignment primitive).  Returns
    (row_match, col_match): for each row the matched col (or -1), and
    for each col the matched row (or -1).  Supports a leading batch dim
    like the reference."""
    batched = data.ndim == 3
    scores = data if batched else data[None]
    B, N, M = scores.shape
    k = N if topk <= 0 else min(topk, N)
    big = jnp.asarray(_np_inf_like(scores.dtype), scores.dtype)

    def one(s):
        s0 = -s if is_ascend else s

        def body(carry, _):
            s_cur, row_m, col_m = carry
            flat = jnp.argmax(s_cur)
            i, j = flat // M, flat % M
            # the threshold comparison is unconditional (the reference
            # always applies it — an explicit 0.0 is a real cutoff);
            # exhausted cells sit at -big and always fail it
            ok = s_cur[i, j] > (-threshold if is_ascend else threshold)
            row_m = jnp.where(ok, row_m.at[i].set(j.astype(row_m.dtype)),
                              row_m)
            col_m = jnp.where(ok, col_m.at[j].set(i.astype(col_m.dtype)),
                              col_m)
            s_cur = s_cur.at[i, :].set(-big).at[:, j].set(-big)
            return (s_cur, row_m, col_m), None

        init = (s0, jnp.full((N,), -1, jnp.float32),
                jnp.full((M,), -1, jnp.float32))
        (_, row_m, col_m), _ = jax.lax.scan(body, init, None, length=k)
        return row_m, col_m

    row, col = jax.vmap(one)(scores)
    if not batched:
        row, col = row[0], col[0]
    return row, col


def _np_inf_like(dtype):
    import numpy as _onp
    return _onp.finfo(_onp.dtype(dtype)).max / 2


@register_op("gradientmultiplier", aliases=("_contrib_gradientmultiplier",))
def gradientmultiplier(data, scalar=1.0):
    """Identity forward, gradient scaled by ``scalar``
    (contrib/gradient_multiplier_op.cc — the GRL building block)."""

    @jax.custom_vjp
    def op(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, g):
        return (g * scalar,)

    op.defvjp(fwd, bwd)
    return op(data)


@register_op("allclose", differentiable=False,
             aliases=("_contrib_allclose",))
def allclose(a, b, rtol=1e-5, atol=1e-8, equal_nan=False):
    """1.0 iff allclose (contrib/allclose_op.cc)."""
    return jnp.allclose(a, b, rtol=rtol, atol=atol,
                        equal_nan=equal_nan).astype(jnp.float32)


@register_op("quadratic", aliases=("_contrib_quadratic",))
def quadratic(data, a=0.0, b=0.0, c=0.0):
    """a*x^2 + b*x + c — the reference's operator-tutorial op
    (contrib/quadratic_op.cc), kept so tutorial code ports verbatim."""
    return a * jnp.square(data) + b * data + c
