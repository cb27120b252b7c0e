"""Attention whose keys a learned indexer selects (DeepSeek Sparse
Attention, DeepSeek-V3.2-Exp report): every query scores the keys before
it with a small indexer of its own,

    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]),

keeps the ``top_k`` best (all of them while it has no more; ties at the
threshold are all kept) and the main attention's heads attend those keys
only.  The indexer learns from a loss of its own, the divergence of the
heads' mean probabilities over the kept keys from the softmax of the
index scores there,

    L_I = mean_t KL(pbar[t, S_t] || softmax_{S_t} I[t, .]),

and from nothing else: the selection passes no gradient, and ``pbar`` is
a constant to the loss.  Callers feed the indexer detached inputs, so the
model's other parameters move by the model's loss alone.

Nothing of shape (heads, T, T) is formed: the index scores and the mean
probabilities come a tile at a time from Pallas kernels
(ops/pallas/indexer.py) and the flash kernels mask by comparing a tile
of scores with its queries' thresholds (``flash_attention(keep=...)``).
What is whole is (T, T) float32 per sequence, keys first: the scores,
the mean probabilities and what the loss forms of them.

A row's threshold is its ``top_k``-th largest score, found without a
sort by 32 counts (``kth_largest``).  Where the indexer's kernels tile,
one of them counts, ``indexer_threshold``: a query tile's whole column
of keys in VMEM, so the scores are read once; ``kth_largest`` itself,
32 passes of XLA over the whole array, serves the shapes too small to
tile or too long for a column to fit, and is what the kernel is held to,
bit for bit.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ..base import register_op
from .pallas.flash_attention import flash_attention
from .pallas.indexer import (MASKED, indexer_probs, indexer_scores,
                             indexer_threshold, threshold_width)
from .remat import keep

__all__ = ["kth_largest", "indexed_attention"]


def kth_largest(x, k, axis=-1):
    """The ``k``-th largest of ``x`` along ``axis`` (float32; the axis
    must hold at least ``k`` values), exactly, without a sort: the bits of
    a float32 order as the floats do once the negatives' are turned, so
    the answer's 32 bits are found from the highest down, each by one
    count of the values at or above a candidate — 32 passes that a
    reduction fuses, where a sort of 8,192 takes some 90.  Each pass
    reads all of ``x``: the plain form, which ``indexed_attention`` takes
    only where ``indexer_threshold`` does not tile."""
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    turned = jnp.where(bits < 0, bits ^ jnp.int32(0x7fffffff), bits)
    ordered = lax.bitcast_convert_type(turned, jnp.uint32) \
        ^ jnp.uint32(0x80000000)

    def refine(n, found):
        candidate = found | (jnp.uint32(0x80000000) >> n.astype(jnp.uint32))
        count = jnp.sum(ordered >= jnp.expand_dims(candidate, axis),
                        axis=axis, dtype=jnp.int32)
        return jnp.where(count >= k, candidate, found)

    shape = list(x.shape)
    del shape[axis]
    found = lax.fori_loop(0, 32, refine, jnp.zeros(shape, jnp.uint32))
    turned = lax.bitcast_convert_type(found ^ jnp.uint32(0x80000000),
                                      jnp.int32)
    bits = jnp.where(turned < 0, turned ^ jnp.int32(0x7fffffff), turned)
    return lax.bitcast_convert_type(bits, jnp.float32)


def _threshold(scores, top_k):
    """(B, T): the ``top_k``-th largest index score of each query's
    causal keys, -inf for a query that has no more than ``top_k`` of them
    (it keeps them all).  scores (B, T, T) keys first, ``MASKED`` above
    the diagonal."""
    T = scores.shape[1]
    if T <= top_k:
        return jnp.full(scores.shape[::2], -jnp.inf, jnp.float32)
    if threshold_width(T) is not None:
        return indexer_threshold(scores, top_k)
    least = kth_largest(scores, top_k, axis=1)
    return jnp.where(jnp.arange(T) >= top_k, least, -jnp.inf)


@register_op("indexed_attention", num_outputs=3)
def indexed_attention(q, k, v, q_idx, k_idx, w_idx, top_k=2048, scale=None):
    """Causal attention over the keys an indexer selects, with the
    indexer's loss.

    q (B, H, T, D); k, v (B, Hkv, T, D) with H a multiple of Hkv (head a
    reads key head a // (H / Hkv)); q_idx (B, Hi, T, d), k_idx (B, T, d)
    and w_idx (B, Hi, T) the indexer's queries, its one key head and its
    heads' weights.  Returns

    - o (B, H, T, Dv): query t attends ``S_t``, the keys s <= t whose
      index score is at least the ``top_k``-th largest of its row;
    - kl (B,): the indexer's loss of each sequence, a mean over its
      positions;
    - kept (B,) float32: the pairs kept, ``sum_t |S_t|``.

    Gradients: o's reach q, k and v only; kl's reach q_idx, k_idx and
    w_idx only.
    """
    B, H, T, D = q.shape
    group = H // k.shape[1]
    scale = float(scale if scale is not None else 1.0 / math.sqrt(D))
    sg = lax.stop_gradient
    # the kept set compares the scores with thresholds that a unit of
    # recomputation keeps, so the scores it forms again must be the
    # first's to the bit: it keeps the kernel's three inputs too (an
    # eighth of the scores' bytes at 16 heads of 64).  Formed again by
    # XLA they came a few 1e-6 off on the chip, and half the rows lost
    # the key at their threshold in the backward pass.
    q_idx, k_idx, w_idx = keep(q_idx), keep(k_idx), keep(w_idx)
    scores = indexer_scores(q_idx, k_idx, w_idx)         # (B, Tk, Tq)
    fixed = sg(scores)
    # the thresholds are 4 T bytes a sequence and 32 counts over the
    # scores to find: a unit of recomputation keeps them
    least = keep(_threshold(fixed, top_k))
    o, lse = flash_attention(q, jnp.repeat(k, group, axis=1),
                             jnp.repeat(v, group, axis=1), causal=True,
                             scale=scale, keep=(fixed, least))
    lse = sg(lse)
    if T < 16 or D % 8 != 0:         # where flash attention itself is dense
        pbar = _dense_probs(sg(q), sg(k), lse, fixed, least, scale)
    else:
        pbar = indexer_probs(sg(q), sg(k), lse, fixed, least, scale)
    at = jnp.arange(T)
    kept = (at[:, None] <= at[None, :]) & (fixed >= least[:, None, :])
    logit = jnp.where(kept, scores, MASKED)
    log_index = logit - jax.nn.logsumexp(logit, axis=1, keepdims=True)
    some = pbar > 0.0
    kl = jnp.sum(jnp.where(
        some, pbar * (jnp.log(jnp.where(some, pbar, 1.0)) - log_index),
        0.0), axis=(1, 2)) / T
    return o, kl, jnp.sum(kept, axis=(1, 2), dtype=jnp.float32)


def _dense_probs(q, k, lse, scores, least, scale):
    """``indexer_probs`` in plain XLA, for shapes too small to tile."""
    T = q.shape[2]
    group = q.shape[1] // k.shape[1]
    s = jnp.einsum("bhsd,bhtd->bhst", jnp.repeat(k, group, axis=1), q,
                   precision=lax.Precision.HIGHEST) * scale
    at = jnp.arange(T)
    kept = (at[:, None] <= at[None, :]) & (scores >= least[:, None, :])
    return jnp.mean(jnp.where(kept[:, None], jnp.exp(s - lse[:, :, None, :]),
                              0.0), axis=1)
