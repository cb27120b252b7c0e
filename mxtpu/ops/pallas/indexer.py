"""The lightning indexer's tiles in Pallas (DeepSeek Sparse Attention,
DeepSeek-V3.2-Exp report): nothing of shape (heads, T, T) is ever in HBM.

    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])        for s <= t

Every (T, T) array here lies KEYS FIRST, ``(B, Tk, Tq)`` — a tile is
(keys, queries) as the flash kernels' transposed tiles are, a query's
weight, threshold or logsumexp a row of lanes — and holds ``MASKED``
where s > t.

- ``indexer_scores_fwd`` writes I, a head of the indexer a grid step, the
  sum carried in the output tile;
- ``indexer_scores_bwd_q`` and ``indexer_scores_bwd_k`` turn I's
  cotangent into those of qI and w, and of kI: two passes that each form
  the heads' products again, because what they sum over differs (keys
  for a query's, queries and heads for a key's) and a tile's output
  block can be carried over the grid's innermost steps only;
- ``indexer_probs`` re-forms the main attention's probabilities from q,
  k and the logsumexp its kernel kept, a head a grid step, and writes
  their mean over the heads at the kept keys (0 elsewhere): what the
  indexer's loss is measured against.

Tiles above the diagonal are not computed.  float32 throughout, products
at ``Precision.HIGHEST``; interpret mode on the CPU.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import counters
from .flash_attention import _tile

__all__ = ["MASKED", "indexer_scores", "indexer_probs", "kernel_specs"]

#: what a (T, T) array of this module holds above the diagonal
MASKED = -1e30

SCORES_FWD_NAME = "indexer_scores_fwd"
SCORES_BWD_Q_NAME = "indexer_scores_bwd_q"
SCORES_BWD_K_NAME = "indexer_scores_bwd_k"
PROBS_NAME = "indexer_probs"

_HI = jax.lax.Precision.HIGHEST
_dot = functools.partial(jax.lax.dot_general, precision=_HI,
                         preferred_element_type=jnp.float32)
_A_BT = (((1,), (1,)), ((), ()))               # a @ b.T
_A_B = (((1,), (0,)), ((), ()))                # a @ b
_AT_B = (((0,), (0,)), ((), ()))               # a.T @ b

#: the grid's innermost axis (or two) carries an output tile
_CARRY_1 = pltpu.CompilerParams(dimension_semantics=(
    "parallel", "parallel", "parallel", "arbitrary"))
_CARRY_2 = pltpu.CompilerParams(dimension_semantics=(
    "parallel", "parallel", "arbitrary", "arbitrary"))


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


# ------------------------------------------------------------------ scores

def _scores_fwd_kernel(k_ref, q_ref, w_ref, out_ref, *, tile, valid):
    j, i, h = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(h == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(j <= i)
    def _():
        z = _dot(k_ref[0], q_ref[0, 0], _A_BT)              # (keys, queries)
        out_ref[0] += w_ref[0, 0] * jnp.maximum(z, 0.0)

    @pl.when(h == pl.num_programs(3) - 1)
    def _():
        out_ref[0] = jnp.where(_allowed(j, i, tile, valid), out_ref[0],
                               MASKED)


def _scores_bwd_q_kernel(k_ref, q_ref, w_ref, g_ref, dq_ref, dw_ref, *, tile,
                         valid):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(j <= i)
    def _():
        k = k_ref[0]
        z = _dot(k, q_ref[0, 0], _A_BT)
        g = jnp.where(_allowed(j, i, tile, valid), g_ref[0], 0.0)
        dw_ref[0, 0] += jnp.sum(g * jnp.maximum(z, 0.0), axis=0,
                                keepdims=True)
        gz = jnp.where(z > 0.0, g * w_ref[0, 0], 0.0)
        dq_ref[0, 0] += _dot(gz, k, _AT_B)                  # (queries, d)


def _scores_bwd_k_kernel(k_ref, q_ref, w_ref, g_ref, dk_ref, *, tile, valid):
    j, i, h = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when((i == 0) & (h == 0))
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)

    @pl.when(j <= i)
    def _():
        q = q_ref[0, 0]
        z = _dot(k_ref[0], q, _A_BT)
        g = jnp.where(_allowed(j, i, tile, valid), g_ref[0], 0.0)
        gz = jnp.where(z > 0.0, g * w_ref[0, 0], 0.0)
        dk_ref[0] += _dot(gz, q, _A_B)                      # (keys, d)


def _scores_specs(tile, d, order):
    """BlockSpecs of (kI, qI, w, a (T, T) array) for a grid whose axes
    after the batch are ``order``, a permutation of "jih" (key tile,
    query tile, head)."""
    def at(f):
        return lambda b, *axes: f(b, **dict(zip(order, axes)))

    return [pl.BlockSpec((1, tile, d), at(lambda b, j, i, h: (b, j, 0))),
            pl.BlockSpec((1, 1, tile, d),
                         at(lambda b, j, i, h: (b, h, i, 0))),
            pl.BlockSpec((1, 1, 1, tile),
                         at(lambda b, j, i, h: (b, h, 0, i))),
            pl.BlockSpec((1, tile, tile), at(lambda b, j, i, h: (b, j, i)))]


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
    interpret = jax.default_backend() == "cpu"
    counters.bump(SCORES_FWD_NAME)
    *ins, out = _scores_specs(tile, d, "jih")
    scores = pl.pallas_call(
        functools.partial(_scores_fwd_kernel, tile=tile, valid=T),
        out_shape=jax.ShapeDtypeStruct((B, Tp, Tp), jnp.float32),
        grid=(B, n, n, H), in_specs=ins, out_specs=out,
        compiler_params=_CARRY_1, interpret=interpret,
        name=SCORES_FWD_NAME)(*_scores_operands(q_idx, k_idx, w))
    return scores[:, :T, :T]


def _scores_bwd(q_idx, k_idx, w, g):
    B, H, T, d = q_idx.shape
    Tp, tile = _geometry(T)
    n = Tp // tile
    interpret = jax.default_backend() == "cpu"
    operands = _scores_operands(q_idx, k_idx, w) + (
        _pad(_pad(g.astype(jnp.float32), 1, Tp), 2, Tp),)
    counters.bump(SCORES_BWD_Q_NAME)
    specs = _scores_specs(tile, d, "hij")
    dq, dw = pl.pallas_call(
        functools.partial(_scores_bwd_q_kernel, tile=tile, valid=T),
        out_shape=[jax.ShapeDtypeStruct((B, H, Tp, d), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, 1, Tp), jnp.float32)],
        grid=(B, H, n, n), in_specs=specs, out_specs=[specs[1], specs[2]],
        compiler_params=_CARRY_1, interpret=interpret,
        name=SCORES_BWD_Q_NAME)(*operands)
    counters.bump(SCORES_BWD_K_NAME)
    specs = _scores_specs(tile, d, "jih")
    dk = pl.pallas_call(
        functools.partial(_scores_bwd_k_kernel, tile=tile, valid=T),
        out_shape=jax.ShapeDtypeStruct((B, Tp, d), jnp.float32),
        grid=(B, n, n, H), in_specs=specs, out_specs=specs[0],
        compiler_params=_CARRY_2, interpret=interpret,
        name=SCORES_BWD_K_NAME)(*operands)
    return (dq[:, :, :T].astype(q_idx.dtype), dk[:, :T].astype(k_idx.dtype),
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
                  scale, heads, tile, valid):
    j, i, h = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(h == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(j <= i)
    def _():
        st = scale * _dot(k_ref[0, 0], q_ref[0, 0], _A_BT)
        kept = _allowed(j, i, tile, valid) & (score_ref[0] >= least_ref[0])
        out_ref[0] += jnp.where(kept, jnp.exp(st - lse_ref[0, 0]), 0.0) \
            * (1.0 / heads)


def _probs_specs(tile, D, group):
    """BlockSpecs of (q, k, lse, scores, least, the mean probabilities)
    for the grid (batch, key tile, query tile, head)."""
    square = pl.BlockSpec((1, tile, tile), lambda b, j, i, h: (b, j, i))
    return [
        pl.BlockSpec((1, 1, tile, D), lambda b, j, i, h: (b, h, i, 0)),
        pl.BlockSpec((1, 1, tile, D),
                     lambda b, j, i, h: (b, h // group, j, 0)),
        pl.BlockSpec((1, 1, 1, tile), lambda b, j, i, h: (b, h, 0, i)),
        square,
        pl.BlockSpec((1, 1, tile), lambda b, j, i, h: (b, 0, i)),
        square]


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
    *ins, out = _probs_specs(tile, D, H // k.shape[1])
    probs = pl.pallas_call(
        functools.partial(_probs_kernel, scale=float(scale), heads=H,
                          tile=tile, valid=T),
        out_shape=jax.ShapeDtypeStruct((B, Tp, Tp), f32),
        grid=(B, n, n, H), in_specs=ins, out_specs=out,
        compiler_params=_CARRY_1,
        interpret=jax.default_backend() == "cpu", name=PROBS_NAME,
    )(_pad(q.astype(f32), 2, Tp), _pad(k.astype(f32), 2, Tp),
      _pad(lse.astype(f32), 2, Tp)[:, :, None, :],
      _pad(_pad(scores.astype(f32), 1, Tp), 2, Tp),
      _pad(least.astype(f32), 1, Tp)[:, None, :])
    return probs[:, :T, :T]


def kernel_specs(B, Hi, T, d, H, G, D, interpret=False):
    """KernelSpec descriptors (mxtpu.analysis.kernel_check) of the four
    pallas_calls one forward and backward of ``ops/dsa.py``'s indexed
    attention issues beside the flash kernels, in this order: the
    scores forward, the mean probabilities, the scores' backward for the
    queries and weights, and for the keys — built from the BlockSpecs
    the calls themselves use.  ``Hi`` heads of ``d`` in the indexer;
    ``H`` query and ``G`` key heads of ``D`` in the main attention."""
    from ...analysis.kernel_check import BlockOperand, KernelSpec

    Tp, tile = _geometry(T)
    n = Tp // tile
    k_idx, q_idx, w, square = ((B, Tp, d), (B, Hi, Tp, d), (B, Hi, 1, Tp),
                               (B, Tp, Tp))
    arrays = dict(k_idx=k_idx, q_idx=q_idx, w=w, scores=square, g=square,
                  pbar=square, dq_idx=q_idx, dw=w, dk_idx=k_idx,
                  q=(B, H, Tp, D), k=(B, G, Tp, D), lse=(B, H, 1, Tp),
                  least=(B, 1, Tp))

    def operands(specs, names, kinds):
        return [BlockOperand(name, kind, spec.block_shape, arrays[name],
                             "float32", spec.index_map, strict_dims=())
                for spec, name, kind in zip(specs, names, kinds)]

    tag = "[float32,T=%d,Hi=%d,d=%d]" % (T, Hi, d)
    jih, hij = _scores_specs(tile, d, "jih"), _scores_specs(tile, d, "hij")
    return [
        KernelSpec(SCORES_FWD_NAME + tag, grid=(B, n, n, Hi),
                   operands=operands(jih, ("k_idx", "q_idx", "w", "scores"),
                                     ("in", "in", "in", "out")),
                   interpret=interpret),
        KernelSpec(
            PROBS_NAME + "[float32,T=%d,H=%d,G=%d,D=%d]" % (T, H, G, D),
            grid=(B, n, n, H),
            operands=operands(
                _probs_specs(tile, D, H // G),
                ("q", "k", "lse", "scores", "least", "pbar"),
                ("in",) * 5 + ("out",)),
            interpret=interpret),
        KernelSpec(SCORES_BWD_Q_NAME + tag, grid=(B, Hi, n, n),
                   operands=operands(
                       hij + [hij[1], hij[2]],
                       ("k_idx", "q_idx", "w", "g", "dq_idx", "dw"),
                       ("in",) * 4 + ("out", "out")),
                   interpret=interpret),
        KernelSpec(SCORES_BWD_K_NAME + tag, grid=(B, n, n, Hi),
                   operands=operands(
                       jih + [jih[0]],
                       ("k_idx", "q_idx", "w", "g", "dk_idx"),
                       ("in",) * 4 + ("out",)),
                   interpret=interpret)]
