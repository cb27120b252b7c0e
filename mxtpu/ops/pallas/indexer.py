"""The lightning indexer's tiles in Pallas (DeepSeek Sparse Attention,
DeepSeek-V3.2-Exp report): nothing of shape (heads, T, T) is ever in HBM.

    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])        for s <= t

Every (T, T) array here lies KEYS FIRST, ``(B, Tk, Tq)`` — a tile is
(keys, queries) as the flash kernels' transposed tiles are, a query's
weight, threshold or logsumexp a row of lanes — and holds ``MASKED``
where s > t.

A grid step of every kernel is one (query tile, key tile) pair, the key
tiles innermost, and walks that tile's heads itself: the tile's mask,
its cotangent, its scores and thresholds are read once a step and the
one key block once for all the heads that share it.

- ``indexer_scores_fwd`` writes I: a step sums its heads' terms in the
  heads' order and masks the tile once;
- ``indexer_scores_bwd_q_k`` turns I's cotangent into those of qI, w and
  kI in one pass that forms each head's product once: a query tile's
  dqI and dw are carried over the key tiles, and kI's whole gradient —
  one key head, (T, d) — stays in VMEM for the call.  dqI and dkI are
  formed turned, ``(d, queries)`` and ``(d, keys)``: d rows stream
  through the matrix unit where the row form streams a tile's;
- ``indexer_probs`` re-forms the main attention's probabilities from q,
  k and the logsumexp its kernel kept and writes their mean over the
  heads at the kept keys (0 elsewhere): what the indexer's loss is
  measured against.

A step above the diagonal computes nothing and fetches nothing (its
index maps name the blocks the step before it held).

- ``indexer_threshold`` finds each query's ``top_k``-th largest score,
  the selection's threshold, from ONE read of I: its grid is (batch,
  query tile), a step holds its queries' whole column of keys in VMEM
  and counts there, 32 times, what ``ops/dsa.py`` ``kth_largest`` counts
  in 32 passes over HBM.

float32 throughout, products at ``Precision.HIGHEST``; interpret mode on
the CPU.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import counters
from .flash_attention import _padded, _tile, _vmem_limit

__all__ = ["MASKED", "indexer_scores", "indexer_probs", "indexer_threshold",
           "threshold_width", "kernel_specs"]

#: what a (T, T) array of this module holds above the diagonal
MASKED = -1e30

SCORES_FWD_NAME = "indexer_scores_fwd"
#: the name the benchmark's pattern for the queries' pass finds, and the
#: keys' pass's does not
SCORES_BWD_NAME = "indexer_scores_bwd_q_k"
PROBS_NAME = "indexer_probs"
THRESHOLD_NAME = "indexer_threshold"

_HI = jax.lax.Precision.HIGHEST
_dot = functools.partial(jax.lax.dot_general, precision=_HI,
                         preferred_element_type=jnp.float32)
_A_BT = (((1,), (1,)), ((), ()))               # a @ b.T
_A_B = (((1,), (0,)), ((), ()))                # a @ b


def _geometry(T):
    """(padded length, tile): whole blocks of 128, tiles as the flash
    kernels walk them."""
    padded = math.ceil(T / 128) * 128
    return padded, _tile(128, padded)


def _pad(x, axis, to):
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, to - x.shape[axis])
    return jnp.pad(x, widths) if to != x.shape[axis] else x


def _allowed(j, i, tile, valid):
    """(tile, tile) bool: key j * tile + r may be seen by query
    i * tile + c (causal, and not a padded key)."""
    k_pos = j * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    q_pos = i * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
    return (k_pos <= q_pos) & (k_pos < valid)


# Every grid is (batch, query tile i, key tile j).  An input's key tile
# is min(j, i): a step above the diagonal names the diagonal step's
# blocks, which are there already.

def _keys(b, i, j):                            # a block of (B, Tp, d)
    return b, jnp.minimum(j, i), 0


def _square(b, i, j):                          # a tile of a (B, Tp, Tp) input
    return b, jnp.minimum(j, i), i


def _square_out(b, i, j):
    return b, j, i


def _queries(b, i, j):                         # a tile's heads, (B, H, Tp, d)
    return b, 0, i, 0


def _queries_turned(b, i, j):                  # (B, H, d, Tp), (B, H, 1, Tp)
    return b, 0, 0, i


def _vmem(specs, tile):
    """What a call with these blocks asks the compiler for: the blocks,
    twice buffered, and eight (tile, tile) float32 for what a head's
    trip forms (None while the compiler's own limit holds them)."""
    blocks = sum(math.prod(s.block_shape[:-2])
                 * _padded(*s.block_shape[-2:], "float32") for s in specs)
    return _vmem_limit(2 * blocks + 32 * tile * tile)


def _params(specs, tile, carried):
    """CompilerParams of a call with these blocks whose grid's last
    ``carried`` axes carry an output block."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (3 - carried)
        + ("arbitrary",) * carried,
        vmem_limit_bytes=_vmem(specs, tile))


# ------------------------------------------------------------------ scores

def _scores_fwd_kernel(k_ref, q_ref, w_ref, out_ref, *, tile, valid):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j <= i)
    def _():
        k = k_ref[0]
        out_ref[...] = jnp.zeros_like(out_ref)

        def head(h, _):
            z = _dot(k, q_ref[0, h], _A_BT)                 # (keys, queries)
            out_ref[0] += w_ref[0, h] * jnp.maximum(z, 0.0)

        jax.lax.fori_loop(0, q_ref.shape[1], head, None)
        out_ref[0] = jnp.where(_allowed(j, i, tile, valid), out_ref[0],
                               MASKED)

    @pl.when(j > i)
    def _():
        out_ref[...] = jnp.full_like(out_ref, MASKED)


def _scores_bwd_kernel(k_ref, kt_ref, qt_ref, w_ref, g_ref, dqt_ref, dw_ref,
                       dkt_ref, *, tile, valid):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when((i == 0) & (j == 0))
    def _():
        dkt_ref[...] = jnp.zeros_like(dkt_ref)

    @pl.when(j == 0)
    def _():
        dqt_ref[...] = jnp.zeros_like(dqt_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(j <= i)
    def _():
        k, kt = k_ref[0], kt_ref[0]
        g = jnp.where(_allowed(j, i, tile, valid), g_ref[0], 0.0)

        def head(h, dkt):
            qt = qt_ref[0, h]                               # (d, queries)
            z = _dot(k, qt, _A_B)                           # (keys, queries)
            dw_ref[0, h] += jnp.sum(g * jnp.maximum(z, 0.0), axis=0,
                                    keepdims=True)
            gz = jnp.where(z > 0.0, g * w_ref[0, h], 0.0)
            dqt_ref[0, h] += _dot(kt, gz, _A_B)             # (d, queries)
            return dkt + _dot(qt, gz, _A_BT)                # (d, keys)

        dkt = jax.lax.fori_loop(0, qt_ref.shape[1], head,
                                jnp.zeros(kt.shape, jnp.float32))
        dkt_ref[0, :, pl.ds(pl.multiple_of(j * tile, tile), tile)] += dkt


def _scores_fwd_specs(tile, H, d):
    """BlockSpecs of (kI, qI, w, the scores)."""
    return [pl.BlockSpec((1, tile, d), _keys),
            pl.BlockSpec((1, H, tile, d), _queries),
            pl.BlockSpec((1, H, 1, tile), _queries_turned),
            pl.BlockSpec((1, tile, tile), _square_out)]


def _scores_bwd_specs(tile, H, d, Tp):
    """BlockSpecs of (kI, kI and qI turned, w, the scores' cotangent;
    dqI turned, dw, and dkI turned: whole, whatever the step)."""
    turned = pl.BlockSpec((1, H, d, tile), _queries_turned)
    w = pl.BlockSpec((1, H, 1, tile), _queries_turned)
    return [pl.BlockSpec((1, tile, d), _keys),
            pl.BlockSpec((1, d, tile),
                         lambda b, i, j: (b, 0, jnp.minimum(j, i))),
            turned, w, pl.BlockSpec((1, tile, tile), _square),
            turned, w, pl.BlockSpec((1, d, Tp), lambda b, i, j: (b, 0, 0))]


def _scores_operands(q_idx, k_idx, w):
    """Padded (kI (B, Tp, d), qI (B, H, Tp, d), w (B, H, 1, Tp))."""
    Tp, _ = _geometry(q_idx.shape[2])
    f32 = jnp.float32
    return (_pad(k_idx.astype(f32), 1, Tp), _pad(q_idx.astype(f32), 2, Tp),
            _pad(w.astype(f32), 2, Tp)[:, :, None, :])


def _scores_fwd(q_idx, k_idx, w):
    B, H, T, d = q_idx.shape
    Tp, tile = _geometry(T)
    n = Tp // tile
    counters.bump(SCORES_FWD_NAME)
    specs = _scores_fwd_specs(tile, H, d)
    scores = pl.pallas_call(
        functools.partial(_scores_fwd_kernel, tile=tile, valid=T),
        out_shape=jax.ShapeDtypeStruct((B, Tp, Tp), jnp.float32),
        grid=(B, n, n), in_specs=specs[:3], out_specs=specs[3],
        compiler_params=_params(specs, tile, 0),
        interpret=jax.default_backend() == "cpu",
        name=SCORES_FWD_NAME)(*_scores_operands(q_idx, k_idx, w))
    return scores[:, :T, :T]


def _scores_bwd(q_idx, k_idx, w, g):
    B, H, T, d = q_idx.shape
    Tp, tile = _geometry(T)
    n = Tp // tile
    k, q, w_rows = _scores_operands(q_idx, k_idx, w)
    counters.bump(SCORES_BWD_NAME)
    specs = _scores_bwd_specs(tile, H, d, Tp)
    dqt, dw, dkt = pl.pallas_call(
        functools.partial(_scores_bwd_kernel, tile=tile, valid=T),
        out_shape=[jax.ShapeDtypeStruct((B, H, d, Tp), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, 1, Tp), jnp.float32),
                   jax.ShapeDtypeStruct((B, d, Tp), jnp.float32)],
        grid=(B, n, n), in_specs=specs[:5], out_specs=specs[5:],
        compiler_params=_params(specs, tile, 2),
        interpret=jax.default_backend() == "cpu", name=SCORES_BWD_NAME)(
            k, jnp.swapaxes(k, 1, 2), jnp.swapaxes(q, 2, 3), w_rows,
            _pad(_pad(g.astype(jnp.float32), 1, Tp), 2, Tp))
    return (jnp.swapaxes(dqt, 2, 3)[:, :, :T].astype(q_idx.dtype),
            jnp.swapaxes(dkt, 1, 2)[:, :T].astype(k_idx.dtype),
            dw[:, :, 0, :T].astype(w.dtype))


@jax.custom_vjp
def indexer_scores(q_idx, k_idx, w):
    """The index scores of every causal pair, keys first:
    ``out[b, s, t] = sum_j w[b, j, t] relu(q_idx[b, j, t] . k_idx[b, s])``
    for s <= t and ``MASKED`` above the diagonal.  q_idx (B, H, T, d),
    k_idx (B, T, d) — one key head —, w (B, H, T); (B, T, T) float32.
    Differentiable in all three."""
    return _scores_fwd(q_idx, k_idx, w)


def _indexer_scores_fwd(q_idx, k_idx, w):
    return _scores_fwd(q_idx, k_idx, w), (q_idx, k_idx, w)


def _indexer_scores_bwd(kept, g):
    return _scores_bwd(*kept, g)


indexer_scores.defvjp(_indexer_scores_fwd, _indexer_scores_bwd)


# ----------------------------------------------------------- probabilities

def _probs_kernel(q_ref, k_ref, lse_ref, score_ref, least_ref, out_ref, *,
                  scale, tile, valid):
    i, j = pl.program_id(1), pl.program_id(2)
    heads = q_ref.shape[1]
    group = heads // k_ref.shape[1]

    @pl.when(j <= i)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

        def head(h, _):
            st = scale * _dot(k_ref[0, h // group], q_ref[0, h], _A_BT)
            out_ref[0] += jnp.exp(st - lse_ref[0, h])

        jax.lax.fori_loop(0, heads, head, None)
        # a key that was not kept may have overflowed the sum: it is
        # selected away, not multiplied
        kept = _allowed(j, i, tile, valid) & (score_ref[0] >= least_ref[0])
        out_ref[0] = jnp.where(kept, out_ref[0] * (1.0 / heads), 0.0)

    @pl.when(j > i)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


def _probs_specs(tile, H, G, D):
    """BlockSpecs of (q, k, lse, scores, least, the mean probabilities)."""
    return [
        pl.BlockSpec((1, H, tile, D), _queries),
        pl.BlockSpec((1, G, tile, D),
                     lambda b, i, j: (b, 0, jnp.minimum(j, i), 0)),
        pl.BlockSpec((1, H, 1, tile), _queries_turned),
        pl.BlockSpec((1, tile, tile), _square),
        pl.BlockSpec((1, 1, tile), lambda b, i, j: (b, 0, i)),
        pl.BlockSpec((1, tile, tile), _square_out)]


def indexer_probs(q, k, lse, scores, least, scale):
    """The mean over the heads of attention's probabilities at the kept
    keys, keys first: ``out[b, s, t] = mean_a exp(scale q[b, a, t] .
    k[b, a // group, s] - lse[b, a, t])`` where s <= t and
    ``scores[b, s, t] >= least[b, t]``, 0 elsewhere.  q (B, H, T, D),
    k (B, Hkv, T, D) with H a multiple of Hkv, lse (B, H, T) the
    logsumexp over the kept keys (``flash_attention(keep=...)``'s),
    scores (B, T, T), least (B, T).  Not differentiable: the indexer's
    loss takes it as a constant."""
    B, H, T, D = q.shape
    Tp, tile = _geometry(T)
    n = Tp // tile
    f32 = jnp.float32
    counters.bump(PROBS_NAME)
    specs = _probs_specs(tile, H, k.shape[1], D)
    probs = pl.pallas_call(
        functools.partial(_probs_kernel, scale=float(scale), tile=tile,
                          valid=T),
        out_shape=jax.ShapeDtypeStruct((B, Tp, Tp), f32),
        grid=(B, n, n), in_specs=specs[:5], out_specs=specs[5],
        compiler_params=_params(specs, tile, 0),
        interpret=jax.default_backend() == "cpu", name=PROBS_NAME,
    )(_pad(q.astype(f32), 2, Tp), _pad(k.astype(f32), 2, Tp),
      _pad(lse.astype(f32), 2, Tp)[:, :, None, :],
      _pad(_pad(scores.astype(f32), 1, Tp), 2, Tp),
      _pad(least.astype(f32), 1, Tp)[:, None, :])
    return probs[:, :T, :T]


# --------------------------------------------------------------- threshold

#: the queries a grid step of ``indexer_threshold`` takes, widest first,
#: and the keys one trip of its loops walks at most.  On a v5e, 8,192 x
#: 8,192 and the 2,048th largest, ms a call at trips of 32 / 64 / 128 /
#: 256 keys (PERF.md, PR 38): width 512 0.96 / 0.80 / 0.76 / 0.64, 256
#: 1.37 / 1.02 / 0.80 / 0.76, 128 2.11 / 1.44 / 1.05; the 32 passes of
#: ``kth_largest`` in XLA 14.39
THRESHOLD_WIDTHS, THRESHOLD_SLAB = (512, 256, 128), 256
#: a v5e core's VMEM: what a call may ask the compiler for at most
#: (``analysis.kernel_check.PHYSICAL_VMEM`` holds the specs to the same)
_PHYSICAL_VMEM = 128 * (1 << 20)
_LOWEST = -2 ** 31                   # under every candidate: never counted
#: ``MASKED`` as ``_turned`` turns it (it is negative)
_MASKED_TURNED = int(np.float32(MASKED).view(np.int32)) ^ 0x7fffffff


def _turned(bits):
    """The int32 that order, signed, as the float32 of these bits do
    (``kth_largest``'s ``ordered`` before its sign is flipped for an
    unsigned compare), and the bits again of such an int32."""
    return jnp.where(bits < 0, bits ^ 0x7fffffff, bits)


def _threshold_kernel(score_ref, out_ref, turned_ref, *, width, slab, top_k,
                      valid):
    """A query tile's thresholds from its whole column of scores (all
    the keys, ``width`` queries on the lanes): ``kth_largest``'s 32
    refinements, from the highest bit down, each a count down the rows
    of the column's values at or above a candidate.  Only the keys up to
    the tile's last query are walked — the others hold ``MASKED`` and
    are counted by their number — and a tile none of whose queries has
    more than ``top_k`` keys counts nothing."""
    i = pl.program_id(1)
    reach = (i + 1) * width

    @pl.when(reach <= top_k)
    def _():
        out_ref[...] = jnp.full_like(out_ref, -jnp.inf)

    @pl.when(reach > top_k)
    def _():
        each = slab // 8
        trips = reach // slab

        def turn(s, _):
            rows = pl.ds(pl.multiple_of(s * slab, slab), slab)
            turned = _turned(jax.lax.bitcast_convert_type(
                score_ref[0, rows, :], jnp.int32))
            if valid < score_ref.shape[1]:              # padded keys
                k_pos = s * slab + jax.lax.broadcasted_iota(
                    jnp.int32, turned.shape, 0)
                turned = jnp.where(k_pos < valid, turned, _LOWEST)
            turned_ref[pl.ds(s * each, each)] = turned.reshape(
                each, 8, width)

        jax.lax.fori_loop(0, trips, turn, None)
        beyond = jnp.maximum(valid - reach, 0)

        def refine(_, carry):
            found, bit = carry
            candidate = found ^ bit
            wide = jnp.broadcast_to(candidate, (8, width))

            def count(s, acc):
                at = turned_ref[pl.ds(s * each, each)] >= wide
                return acc + jnp.sum(jnp.where(at, 1, 0), axis=0)

            acc = jax.lax.fori_loop(0, trips, count,
                                    jnp.zeros((8, width), jnp.int32))
            n = jnp.sum(acc, axis=0, keepdims=True) \
                + jnp.where(_MASKED_TURNED >= candidate, beyond, 0)
            return (jnp.where(n >= top_k, candidate, found),
                    jax.lax.shift_right_logical(bit, 1))

        found, _ = jax.lax.fori_loop(
            0, 32, refine, (jnp.full((1, width), _LOWEST, jnp.int32),
                            jnp.int32(_LOWEST)))
        q_pos = i * width + jax.lax.broadcasted_iota(
            jnp.int32, (1, width), 1)
        out_ref[0] = jnp.where(
            q_pos >= top_k,
            jax.lax.bitcast_convert_type(_turned(found), jnp.float32),
            -jnp.inf)


def threshold_width(T):
    """The queries a grid step of ``indexer_threshold`` takes at ``T``
    positions: the widest of ``THRESHOLD_WIDTHS`` that tiles the padded
    length and whose column of all the keys VMEM holds.  None where the
    kernels of this file do not tile (under 16 positions) or no column
    fits."""
    if T < 16:
        return None
    Tp, _ = _geometry(T)
    for width in THRESHOLD_WIDTHS:
        limit = _threshold_vmem(Tp, width)
        if Tp % width == 0 and (limit is None or limit <= _PHYSICAL_VMEM):
            return width
    return None


def _threshold_vmem(Tp, width):
    """What the call asks the compiler for: the column twice buffered,
    its turned copy, the thresholds' rows."""
    return _vmem_limit(3 * _padded(Tp, width, "float32")
                       + 2 * _padded(8, width, "float32"))


def _threshold_specs(Tp, width, top_k):
    """BlockSpecs of (the scores, the thresholds).  Grid (batch, query
    tile i); a tile that counts nothing names the first counting tile's
    column, so nothing is fetched for it."""
    first = min(top_k, Tp - 1) // width
    return [pl.BlockSpec((1, Tp, width),
                         lambda b, i: (b, 0, jnp.maximum(i, first))),
            pl.BlockSpec((1, 1, width), lambda b, i: (b, 0, i))]


def indexer_threshold(scores, top_k):
    """(B, T) float32: the ``top_k``-th largest score of each query's
    column, ``ops/dsa.py`` ``kth_largest``'s to the bit, and -inf for a
    query with no more than ``top_k`` causal keys (it keeps them all).
    scores (B, T, T) float32, keys first, ``MASKED`` above the diagonal;
    ``threshold_width(T)`` must not be None.  Passes no gradient."""
    B, T = scores.shape[:2]
    Tp, _ = _geometry(T)
    width = threshold_width(T)
    counters.bump(THRESHOLD_NAME)
    specs = _threshold_specs(Tp, width, top_k)
    scores = jax.lax.stop_gradient(scores).astype(jnp.float32)
    least = pl.pallas_call(
        functools.partial(_threshold_kernel, width=width,
                          slab=min(THRESHOLD_SLAB, width), top_k=int(top_k),
                          valid=T),
        out_shape=jax.ShapeDtypeStruct((B, 1, Tp), jnp.float32),
        grid=(B, Tp // width), in_specs=specs[:1], out_specs=specs[1],
        scratch_shapes=[pltpu.VMEM((Tp // 8, 8, width), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_threshold_vmem(Tp, width)),
        interpret=jax.default_backend() == "cpu", name=THRESHOLD_NAME,
    )(_pad(_pad(scores, 1, Tp), 2, Tp))
    return least[:, 0, :T]


def kernel_specs(B, Hi, T, d, H, G, D, top_k=2048, interpret=False):
    """KernelSpec descriptors (mxtpu.analysis.kernel_check) of the four
    pallas_calls one forward and backward of ``ops/dsa.py``'s indexed
    attention issues beside the flash kernels, in this order: the
    scores forward, the mean probabilities, the scores' backward, the
    thresholds — built from the BlockSpecs the calls themselves use.
    ``Hi`` heads of ``d`` in the indexer; ``H`` query and ``G`` key
    heads of ``D`` in the main attention; the ``top_k`` best kept."""
    from ...analysis.kernel_check import (BlockOperand, KernelSpec,
                                          ScratchOperand)

    Tp, tile = _geometry(T)
    n = Tp // tile
    width = threshold_width(T)
    k_idx, q_idx, w, square = ((B, Tp, d), (B, Hi, Tp, d), (B, Hi, 1, Tp),
                               (B, Tp, Tp))
    arrays = dict(k_idx=k_idx, q_idx=q_idx, w=w, scores=square, g=square,
                  pbar=square, k_idx_t=(B, d, Tp), q_idx_t=(B, Hi, d, Tp),
                  dq_idx_t=(B, Hi, d, Tp), dw=w, dk_idx_t=(B, d, Tp),
                  q=(B, H, Tp, D), k=(B, G, Tp, D), lse=(B, H, 1, Tp),
                  least=(B, 1, Tp))

    def spec(name, specs, operands, outs, grid=(B, n, n), scratch=(),
             limit=lambda specs: _vmem(specs, tile)):
        kinds = ("in",) * (len(specs) - outs) + ("out",) * outs
        return KernelSpec(
            name, grid=grid, interpret=interpret, scratch=scratch,
            operands=[BlockOperand(operand, kind, s.block_shape,
                                   arrays[operand], "float32", s.index_map,
                                   strict_dims=())
                      for s, operand, kind in zip(specs, operands, kinds)],
            vmem_limit=limit(specs))

    tag = "[float32,T=%d,Hi=%d,d=%d]" % (T, Hi, d)
    return [
        spec(SCORES_FWD_NAME + tag, _scores_fwd_specs(tile, Hi, d),
             ("k_idx", "q_idx", "w", "scores"), 1),
        spec(PROBS_NAME + "[float32,T=%d,H=%d,G=%d,D=%d]" % (T, H, G, D),
             _probs_specs(tile, H, G, D),
             ("q", "k", "lse", "scores", "least", "pbar"), 1),
        spec(SCORES_BWD_NAME + tag, _scores_bwd_specs(tile, Hi, d, Tp),
             ("k_idx", "k_idx_t", "q_idx_t", "w", "g", "dq_idx_t", "dw",
              "dk_idx_t"), 3),
        spec(THRESHOLD_NAME + "[float32,T=%d,top_k=%d]" % (T, top_k),
             _threshold_specs(Tp, width, top_k), ("scores", "least"), 1,
             grid=(B, Tp // width),
             scratch=[ScratchOperand("turned", (Tp // 8, 8, width), "int32")],
             limit=lambda _: _threshold_vmem(Tp, width))]
