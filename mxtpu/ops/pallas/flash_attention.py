"""Flash attention forward + backward kernels in Pallas (TPU).

Replaces the reference's fused interleaved-MHA CUDA kernels
(src/operator/contrib/transformer.cc) with the memory-optimal streaming
algorithm: a tile of Q stays resident in VMEM while tiles of K/V stream
through, softmax runs in online (max/denominator-carrying) form, so HBM
traffic is O(T·D) instead of O(T²).  The caller's ``q_block`` /
``kv_block`` are the granule the sequence is padded to; both kernels
walk the widest run of whole blocks up to ``TILE`` rows, on transposed
tiles (S^T = K Q^T); the forward visits of a causal call's diagonal
tile only the squares the mask leaves.

Backward (SURVEY §7 hard-part 7) is the FlashAttention-2 formulation in
one Pallas kernel: the forward additionally emits the per-row
logsumexp; per K/V block the kernel streams the head's Q/dO blocks,
forms each tile's S, P and dS once and accumulates dk and dv for the
block and dq for the head (a float32 VMEM scratch carried across the
kv-block grid axis), with delta = rowsum(dO·O) precomputed in XLA.

On CPU (tests) the kernels run in interpret mode; numerics match the
dense reference implementation to ~1e-5 (fp32) / 1e-2 (bf16).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...base import register_op
from .. import remat
from . import counters
from .partition import shard_attention

__all__ = ["flash_attention", "kernel_specs"]

_NEG_INF = -1e30

KERNEL_NAME = "flash_attention"


def kernel_specs(B, H, T, D, dtype="float32", q_block=128, kv_block=128,
                 backward=True, interpret=False, Dv=None, kept=False):
    """KernelSpec descriptors (mxtpu.analysis.kernel_check) for the
    pallas_calls one flash_attention forward/backward issues at this
    workload geometry — same padding and block construction as
    _flash_fwd/_flash_bwd, so the static pass verdicts exactly the
    calls that would run.  ``Dv`` is the values' width where it is not
    the keys' (``D``); ``kept`` describes the calls of
    ``flash_attention(keep=...)``, with the selection's two inputs."""
    from ...analysis.kernel_check import (BlockOperand, KernelSpec,
                                          ScratchOperand)

    Dv = D if Dv is None else Dv
    qb = min(q_block, T)
    kb = min(kv_block, T)
    Tq = math.ceil(T / qb) * qb
    Tk = math.ceil(T / kb) * kb
    BH = B * H
    qb, kb = _tile(qb, Tq), _tile(kb, Tk)
    nq = Tq // qb

    def blk(name, kind, shape, array, dt, imap):
        # the q/kv block tiles are chosen parameters, strict on the
        # sublane dim.  The head width is the arrays' whole last axis:
        # VMEM pads it to the lanes, and the chip's compiler takes 64
        # (BERT's cell) and 192 (latent attention) as it takes 128
        # (tests/test_chip_compile.py)
        return BlockOperand(name, kind, shape, array, dt, imap,
                            strict_dims=(-2,))

    q_im = lambda b, i: (b, i, 0)      # noqa: E731 — mirrors _flash_fwd
    full_im = lambda b, i: (b, 0, 0)   # noqa: E731
    tag = "[%s,T=%d,D=%d]" % (dtype, T, D) if Dv == D else \
        "[%s,T=%d,D=%d,Dv=%d]" % (dtype, T, D, Dv)
    fwd_more = bwd_more = 0
    if kept:
        tag = tag[:-1] + ",kept]"
        fwd_more = 2 * _padded(Tk, qb, "float32")
        bwd_more = 2 * _padded(kb, Tq, "float32")

    def selection(column):
        """The sequence's scores — a Q tile's column (forward) or a K/V
        block's row (backward) — and its thresholds."""
        if column:
            block, im = (1, Tk, qb), lambda b, i: (b // H, 0, i)
            least = ((1, 1, 1, qb), lambda b, i: (b // H, i, 0, 0))
        else:
            block, im = (1, kb, Tq), lambda b, j: (b // H, j, 0)
            least = ((1, nq, 1, qb), lambda b, j: (b // H, 0, 0, 0))
        return [BlockOperand("scores", "in", block, (B, Tk, Tq), "float32",
                             im),
                BlockOperand("least", "in", least[0], (B, nq, 1, qb),
                             "float32", least[1])]

    # lse lies along lanes, a row a Q tile: its block is the head's,
    # resident over the Q-tile axis (the grid's innermost: K006 holds)
    specs = [KernelSpec(
        "flash_attention.fwd" + tag,
        grid=(BH, nq),
        operands=[
            blk("q", "in", (1, qb, D), (BH, Tq, D), dtype, q_im),
            blk("k", "in", (1, Tk, D), (BH, Tk, D), dtype, full_im),
            blk("v", "in", (1, Tk, Dv), (BH, Tk, Dv), dtype, full_im),
        ] + (selection(True) if kept else []) + [
            blk("o", "out", (1, qb, Dv), (BH, Tq, Dv), dtype, q_im),
            BlockOperand("lse", "out", (1, nq, qb), (BH, nq, qb),
                         "float32", full_im),
        ],
        interpret=interpret,
        vmem_limit=_vmem_limit(_fwd_vmem(qb, kb, Tk, D, Dv, dtype)
                               + fwd_more))]
    if not backward:
        return specs
    kv_im = lambda b, j: (b, j, 0)     # noqa: E731 — mirrors _flash_bwd
    # dq's block is resident over the kv-block axis — the grid's
    # innermost, so K006 holds — beside its float32 accumulator; lse and
    # delta lie along lanes, a row a Q block, whole for the head
    specs.append(KernelSpec(
        "flash_attention.bwd" + tag,
        grid=(BH, Tk // kb),
        operands=[
            blk("q", "in", (1, Tq, D), (BH, Tq, D), dtype, full_im),
            blk("k", "in", (1, kb, D), (BH, Tk, D), dtype, kv_im),
            blk("v", "in", (1, kb, Dv), (BH, Tk, Dv), dtype, kv_im),
            blk("do", "in", (1, Tq, Dv), (BH, Tq, Dv), dtype, full_im),
            BlockOperand("lse", "in", (1, nq, qb), (BH, nq, qb), "float32",
                         full_im),
            BlockOperand("delta", "in", (1, nq, qb), (BH, nq, qb),
                         "float32", full_im),
        ] + (selection(False) if kept else []) + [
            blk("dq", "out", (1, Tq, D), (BH, Tq, D), dtype, full_im),
            blk("dk", "out", (1, kb, D), (BH, Tk, D), dtype, kv_im),
            blk("dv", "out", (1, kb, Dv), (BH, Tk, Dv), dtype, kv_im),
        ],
        scratch=[ScratchOperand("dq_acc", (Tq, D), "float32")],
        interpret=interpret,
        vmem_limit=_vmem_limit(_bwd_vmem(Tq, kb, D, Dv, dtype)
                               + bwd_more)))
    return specs


# The kernels keep one head's K and V (forward) or Q, dO and dQ
# (backward) whole in VMEM.  Up to the compiler's own limit per kernel
# nothing is asked for; a longer head (8,192 x 192 float32 is 8 MiB an
# operand, as VMEM pads its lanes to 128) asks for what its blocks take,
# twice over for the pipeline's second buffer, what the forward's tile
# forms (3.5 MiB at 512 x 512), and some room: 64 MiB forward and 94 MiB
# backward at 8,192 x 256 / 256, of the chip's 128.

_SCOPED_VMEM = 16 * (1 << 20)


def _padded(rows, cols, dtype):
    return rows * (-(-cols // 128) * 128) * jnp.dtype(dtype).itemsize


def _fwd_vmem(q_tile, kv_tile, Tk, D, Dv, dtype):
    """The forward's blocks, twice buffered (lse's: the head's rows of
    lanes, Tq reckoned as Tk), and what a trip forms of its tile:
    scores, probabilities and the probabilities' three bf16 pieces (14 B
    an element), O^T before and after its rescale."""
    blocks = 2 * (_padded(Tk, D, dtype) + _padded(Tk, Dv, dtype)
                  + _padded(q_tile, D, dtype) + _padded(q_tile, Dv, dtype)
                  + _padded(8 + Tk // q_tile, q_tile, "float32"))
    return blocks + 14 * kv_tile * q_tile + 2 * _padded(Dv, q_tile, "float32")


def _bwd_vmem(Tq, kv_block, D, Dv, dtype):
    return (2 * (2 * _padded(Tq, D, dtype) + _padded(Tq, Dv, dtype)
                 + 2 * _padded(kv_block, D, dtype)
                 + 2 * _padded(kv_block, Dv, dtype)
                 + 2 * _padded(Tq // 8, 128, "float32"))
            + _padded(Tq, D, "float32"))


def _vmem_limit(needed):
    """None while the blocks fit the compiler's own limit with half of it
    to spare for the tiles the kernel forms; else what to ask for."""
    if needed <= _SCOPED_VMEM // 2:
        return None
    return int(needed * 1.25) + _SCOPED_VMEM


def _compiler_params(needed):
    limit = _vmem_limit(needed)
    return None if limit is None else pltpu.CompilerParams(
        vmem_limit_bytes=limit)


#: both kernels' tiles grow to this many rows, q side and kv side.  On a
#: v5e, float32, the kernel alone, ms a call.  The backward (PERF.md,
#: PR 28), 384 heads x 512 x 64: 4.05 at 512 x 512 where 128 x 128 took
#: 5.46.  The forward (PERF.md, PR 34), at tiles of 128 in rows (as
#: before PR 34) / 512 in rows / 256 transposed / 512 transposed / 1,024
#: transposed: 20 heads x 8,192 x 256 / 256 causal 31.74 / 24.19 / 24.10
#: / 23.71 / 30.19; 32 x 8,192 x 192 / 128 causal 47.36 / 28.92 / 29.40
#: / 28.50 / 36.09; 384 x 512 x 64 not causal 3.74 / 1.69 / 1.66 / 1.37.
#: 512 x 256 and 256 x 512 lie between 256 and 512.  Masking the tile on
#: the diagonal alone instead of every tile moved nothing (23.71 /
#: 23.66): every trip is masked by what the call is, as the backward's
TILE = 512
#: the key steps in which a causal forward walks its diagonal tile, each
#: against the queries from its own first on: 10 of the tile's 16 squares
#: of 128.  The two causal figures above at steps of 512 (the whole
#: tile) / 256 / 128: 23.71 / 22.88 / 22.68 and 28.50 / 27.55 / 27.27 ms
#: (22.69 and 27.34 as the kernel stands, every trip masked)
FWD_DIAG = 128


def _tile(block, padded):
    """The widest run of whole blocks that is at most ``TILE`` rows (one
    block where a block is wider) and tiles ``padded``."""
    n = padded // block
    return block * max(m for m in range(1, n + 1)
                       if n % m == 0 and (m == 1 or block * m <= TILE))


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, q_block, kv_block,
                seq_len, valid_len, hi_prec):
    """One tile of queries against the head's keys, transposed as the
    backward's tiles are: S^T = K Q^T, so the running maximum, the
    denominator and the rescale are (1, Bq) rows on the lanes, and
    O^T = sum V^T P^T is turned once, when the tile leaves.  A causal
    call with square tiles walks the tiles below the diagonal and then
    the diagonal one in steps of FWD_DIAG keys, each against the queries
    from its own first on.  A call with kept keys has two more inputs,
    the tile's column of the sequence's selection scores (Tk, Bq) and
    the thresholds as rows of lanes: a key is masked where its score
    lies under its query's threshold."""
    *kept, o_ref, lse_ref = rest
    # fp32 inputs keep true-fp32 dots; bf16 inputs use the fast MXU default
    # (jax>=0.9 interpret mode emulates TPU bf16 default precision, so the
    # fp32 contract must be explicit)
    prec = jax.lax.Precision.HIGHEST if hi_prec else None
    dot = functools.partial(jax.lax.dot_general, precision=prec,
                            preferred_element_type=jnp.float32)
    a_bt = (((1,), (1,)), ((), ()))               # a @ b.T
    at_b = (((0,), (0,)), ((), ()))               # a.T @ b
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # (Bq, D)
    bq = q.shape[0]

    def visit(carry, start, size, first=0):
        """Keys [start, start + size) against the tile's queries from
        its ``first``-th on: the online softmax's update of their part
        of (m, l, O^T)."""
        rows = pl.ds(pl.multiple_of(start, size), size)
        k = k_ref[0, rows, :].astype(jnp.float32)
        v = v_ref[0, rows, :].astype(jnp.float32)
        m, l, acc = (a[:, first:] for a in carry)
        st = dot(k, q[first:], a_bt)                  # (size, Bq - first)
        if causal or valid_len != seq_len:
            k_pos = start + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
        if valid_len != seq_len:  # zero-padded keys must not attend
            st = jnp.where(k_pos < valid_len, st, _NEG_INF)
        if causal:
            q_pos = qi * q_block + first + jax.lax.broadcasted_iota(
                jnp.int32, st.shape, 1)
            st = jnp.where(q_pos >= k_pos, st, _NEG_INF)
        if kept:
            score_ref, least_ref = kept
            st = jnp.where(score_ref[0, rows, first:]
                           >= least_ref[0, 0, :, first:], st, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(st, axis=0, keepdims=True))
        pt = jnp.exp(st - m_new)
        corr = jnp.exp(m - m_new)
        new = (m_new, l * corr + jnp.sum(pt, axis=0, keepdims=True),
               acc * corr + dot(v, pt, at_b))         # O^T: (Dv, Bq - first)
        if first:
            new = tuple(jnp.concatenate([a[:, :first], b], axis=1)
                        for a, b in zip(carry, new))
        return new

    def tile(j, carry):
        return visit(carry, j * kv_block, kv_block)

    carry = (jnp.full((1, bq), _NEG_INF, jnp.float32),
             jnp.zeros((1, bq), jnp.float32),
             jnp.zeros((v_ref.shape[-1], bq), jnp.float32))
    if causal and q_block == kv_block:
        # square tiles: qi below the diagonal, then the diagonal one
        carry = jax.lax.fori_loop(0, qi, tile, carry)
        step = FWD_DIAG if bq % FWD_DIAG == 0 else bq
        for first in range(0, bq, step):
            carry = visit(carry, qi * q_block + first, step, first)
    else:
        nkv = seq_len // kv_block
        if causal:  # the walk stops at the tile that holds the last row
            nkv = jnp.minimum(pl.cdiv((qi + 1) * q_block, kv_block), nkv)
        carry = jax.lax.fori_loop(0, nkv, tile, carry)
    m, l, acc = carry
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l).T.astype(o_ref.dtype)
    # logsumexp residual for the Pallas backward (fp32; the softmax is
    # re-derived there as exp(s - lse) without a second online pass): a
    # row of the head's block, which leaves with the head's last tile
    lse_ref[0, pl.ds(qi, 1), :] = m + jnp.log(l)


def _pad_to(x, axis, multiple):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


def _kept_operands(keep, Tq, Tk, q_block):
    """``keep``'s two arrays as the kernels read them: the scores
    (B, Tk, Tq) padded as the keys and queries are, the thresholds as
    (B, nq, 1, q_block): a tile's are a row of lanes."""
    scores, least = keep
    scores = jnp.pad(scores.astype(jnp.float32), (
        (0, 0), (0, Tk - scores.shape[1]), (0, Tq - scores.shape[2])))
    least = jnp.pad(least.astype(jnp.float32),
                    ((0, 0), (0, Tq - least.shape[1])))
    return scores, least.reshape(least.shape[0], Tq // q_block, 1, q_block)


def _flash_fwd(q, k, v, scale, causal, q_block, kv_block, interpret,
               keep=None):
    B, H, T, D = q.shape
    Dv = v.shape[-1]
    qp, t_orig = _pad_to(q, 2, q_block)
    kp, _ = _pad_to(k, 2, kv_block)
    vp, _ = _pad_to(v, 2, kv_block)
    Tq = qp.shape[2]
    Tk = kp.shape[2]
    # the blocks are the granule of the padding; the kernel walks tiles
    q_block = _tile(q_block, Tq)
    kv_block = _tile(kv_block, Tk)
    BH = B * H
    qp = qp.reshape(BH, Tq, D)
    kp = kp.reshape(BH, Tk, D)
    vp = vp.reshape(BH, Tk, Dv)

    nq = Tq // q_block
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               q_block=q_block, kv_block=kv_block,
                               seq_len=Tk, valid_len=T,
                               hi_prec=q.dtype == jnp.float32)
    tile = lambda b, i: (b, i, 0)           # noqa: E731
    head = lambda b, i: (b, 0, 0)           # noqa: E731 — resident over i
    operands = [qp, kp, vp]
    in_specs = [
        pl.BlockSpec((1, q_block, D), tile),
        pl.BlockSpec((1, Tk, D), head),
        pl.BlockSpec((1, Tk, Dv), head),
    ]
    vmem = _fwd_vmem(q_block, kv_block, Tk, D, Dv, q.dtype)
    if keep is not None:
        # the sequence's, shared by its heads: the tile's column of the
        # scores and the thresholds' rows
        operands += _kept_operands(keep, Tq, Tk, q_block)
        in_specs += [
            pl.BlockSpec((1, Tk, q_block), lambda b, i: (b // H, 0, i)),
            pl.BlockSpec((1, 1, 1, q_block),
                         lambda b, i: (b // H, i, 0, 0))]
        vmem += 2 * _padded(Tk, q_block, "float32")
    out, lse = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((BH, Tq, Dv), q.dtype),
                   jax.ShapeDtypeStruct((BH, nq, q_block), jnp.float32)],
        grid=(BH, nq),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, q_block, Dv), tile),
                   pl.BlockSpec((1, nq, q_block), head)],
        compiler_params=_compiler_params(vmem),
        interpret=interpret,
        name="flash_attention_fwd",
    )(*operands)
    return out.reshape(B, H, Tq, Dv)[:, :, :t_orig], lse


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                scale, causal, q_block, kv_block, seq_len, valid_len,
                hi_prec):
    """dq, dk and dv for one K/V block: stream Q/dO blocks (from the
    diagonal on for causal) and form every tile once, transposed —
    S^T = K Q^T, P^T = exp(S^T - lse), dS^T = P^T * (V dO^T - delta) —
    so that dv += P^T dO and dk += dS^T Q consume it as it lies and only
    dq += dS K contracts over its rows.  dq accumulates in ``dq_acc``
    across the kv-block grid axis and leaves at its last step.  A call
    with kept keys has two more inputs, the block's row of the
    sequence's selection scores (Bkv, Tq) and the thresholds, and masks
    as the forward does."""
    *kept, dq_ref, dk_ref, dv_ref, dq_acc = rest
    prec = jax.lax.Precision.HIGHEST if hi_prec else None
    dot = functools.partial(jax.lax.dot_general, precision=prec,
                            preferred_element_type=jnp.float32)
    a_bt = (((1,), (1,)), ((), ()))               # a @ b.T
    a_b = (((1,), (0,)), ((), ()))                # a @ b
    at_b = (((0,), (0,)), ((), ()))               # a.T @ b
    kj = pl.program_id(1)

    @pl.when(kj == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    k = k_ref[0].astype(jnp.float32)              # (Bkv, D)
    v = v_ref[0].astype(jnp.float32)
    bkv, d = k.shape
    # the Q side's own blocks, a row of lse each: with q_block !=
    # kv_block it is padded to another length than the K side's seq_len
    nq_total = lse_ref.shape[1]
    i0 = (kj * kv_block) // q_block if causal else 0

    k_pos = kj * kv_block + jax.lax.broadcasted_iota(
        jnp.int32, (bkv, q_block), 0)

    def body(i, carry):
        dk, dv = carry
        rows = pl.ds(i * q_block, q_block)
        qb = q_ref[0, rows, :].astype(jnp.float32)          # UNscaled
        do = do_ref[0, rows, :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(i, 1), :]                    # (1, Bq)
        delta = delta_ref[0, pl.ds(i, 1), :]
        st = scale * dot(k, qb, a_bt)                       # (Bkv, Bq)
        if valid_len != seq_len:
            st = jnp.where(k_pos < valid_len, st, _NEG_INF)
        if causal:
            q_pos = i * q_block + jax.lax.broadcasted_iota(
                jnp.int32, (bkv, q_block), 1)
            st = jnp.where(q_pos >= k_pos, st, _NEG_INF)
        if kept:
            score_ref, least_ref = kept
            st = jnp.where(score_ref[0, :, pl.ds(pl.multiple_of(
                i * q_block, q_block), q_block)]
                >= least_ref[0, i], st, _NEG_INF)
        pt = jnp.exp(st - lse)                    # masked entries -> ~0
        dv = dv + dot(pt, do, a_b)
        dst = pt * (dot(v, do, a_bt) - delta)
        dk = dk + dot(dst, qb, a_b)
        dq_acc[rows, :] += dot(dst, k, at_b)
        return dk, dv

    dk, dv = jax.lax.fori_loop(
        i0, nq_total, body, (jnp.zeros((bkv, d), jnp.float32),
                             jnp.zeros(v.shape, jnp.float32)))
    dk_ref[0] = (scale * dk).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(kj == pl.num_programs(1) - 1)
    def _():
        dq_ref[0] = (scale * dq_acc[...]).astype(dq_ref.dtype)


def _flash_bwd(q, k, v, o, lse, g, scale, causal, q_block, kv_block,
               interpret, keep=None):
    B, H, T, D = q.shape
    Dv = v.shape[-1]
    qp, t_orig = _pad_to(q, 2, q_block)
    kp, _ = _pad_to(k, 2, kv_block)
    vp, _ = _pad_to(v, 2, kv_block)
    gp, _ = _pad_to(g, 2, q_block)          # zero-padded dO: no gradient
    op, _ = _pad_to(o, 2, q_block)
    Tq, Tk = qp.shape[2], kp.shape[2]
    # same padded lengths and tiles as the forward
    q_block = _tile(q_block, Tq)
    kv_block = _tile(kv_block, Tk)
    BH = B * H
    qp = qp.reshape(BH, Tq, D)
    kp = kp.reshape(BH, Tk, D)
    vp = vp.reshape(BH, Tk, Dv)
    gp = gp.reshape(BH, Tq, Dv)
    op = op.reshape(BH, Tq, Dv)
    # the per-row vectors lie along lanes, one row a Q tile, as lse
    # comes from the forward
    nq = Tq // q_block
    delta = jnp.sum(gp.astype(jnp.float32) * op.astype(jnp.float32),
                    axis=-1).reshape(BH, nq, q_block)

    kernel = functools.partial(
        _bwd_kernel, scale=scale, causal=causal, q_block=q_block,
        kv_block=kv_block, seq_len=Tk, valid_len=T,
        hi_prec=q.dtype == jnp.float32)
    head = lambda b, j: (b, 0, 0)           # noqa: E731 — resident over j
    kv = lambda b, j: (b, j, 0)             # noqa: E731
    operands = [qp, kp, vp, gp, lse, delta]
    in_specs = [
        pl.BlockSpec((1, Tq, D), head),
        pl.BlockSpec((1, kv_block, D), kv),
        pl.BlockSpec((1, kv_block, Dv), kv),
        pl.BlockSpec((1, Tq, Dv), head),
        pl.BlockSpec((1, nq, q_block), head),
        pl.BlockSpec((1, nq, q_block), head),
    ]
    vmem = _bwd_vmem(Tq, kv_block, D, Dv, q.dtype)
    if keep is not None:
        operands += _kept_operands(keep, Tq, Tk, q_block)
        in_specs += [
            pl.BlockSpec((1, kv_block, Tq), lambda b, j: (b // H, j, 0)),
            pl.BlockSpec((1, nq, 1, q_block),
                         lambda b, j: (b // H, 0, 0, 0))]
        vmem += 2 * _padded(kv_block, Tq, "float32")
    dq, dk, dv = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((BH, Tq, D), q.dtype),
                   jax.ShapeDtypeStruct((BH, Tk, D), k.dtype),
                   jax.ShapeDtypeStruct((BH, Tk, Dv), v.dtype)],
        grid=(BH, Tk // kv_block),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, Tq, D), head),
            pl.BlockSpec((1, kv_block, D), kv),
            pl.BlockSpec((1, kv_block, Dv), kv),
        ],
        scratch_shapes=[pltpu.VMEM((Tq, D), jnp.float32)],
        compiler_params=_compiler_params(vmem),
        interpret=interpret,
        name="flash_attention_bwd",
    )(*operands)

    dq = dq.reshape(B, H, Tq, D)[:, :, :t_orig]
    dk = dk.reshape(B, H, Tk, D)[:, :, :t_orig]
    dv = dv.reshape(B, H, Tk, Dv)[:, :, :t_orig]
    return dq, dk, dv


def _dense_attention(q, k, v, scale, causal, keep=None):
    """XLA reference path (shapes too small to tile; the tests' oracle).
    With ``keep`` it returns (o, lse) as ``flash_attention`` does."""
    prec = jax.lax.Precision.HIGHEST if q.dtype == jnp.float32 else None
    qf = q.astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, k.astype(jnp.float32),
                   preferred_element_type=jnp.float32, precision=prec) * scale
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((Tq, Tk), jnp.bool_), Tk - Tq)
        s = jnp.where(mask, s, _NEG_INF)
    if keep is not None:
        scores, least = jax.lax.stop_gradient(keep)
        kept = scores.swapaxes(1, 2) >= least[:, :, None]     # (B, Tq, Tk)
        s = jnp.where(kept[:, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
                   precision=prec).astype(q.dtype)
    if keep is None:
        return o
    return o, jax.lax.stop_gradient(jax.nn.logsumexp(s, axis=-1))


@functools.lru_cache(maxsize=32)
def _make_flash_kept(scale, causal, q_block, kv_block, interpret):
    """The call with kept keys: (q, k, v, scores, least) -> (out, lse
    (B, H, T)).  No gradient reaches the selection's two arrays, and
    none is taken from lse."""
    @jax.custom_vjp
    def fa(q, k, v, scores, least):
        return fa_fwd(q, k, v, scores, least)[0]

    def fa_fwd(q, k, v, scores, least):
        B, H, T, _ = q.shape
        out, lse = _flash_fwd(q, k, v, scale, causal, q_block, kv_block,
                              interpret, (scores, least))
        out, lse = remat.keep(out), remat.keep(lse)
        rows = lse.reshape(B, H, -1)[:, :, :T]
        return (out, rows), (q, k, v, scores, least, out, lse)

    def fa_bwd(res, g):
        q, k, v, scores, least, o, lse = res
        grads = _flash_bwd(q, k, v, o, lse, g[0], scale, causal, q_block,
                           kv_block, interpret, (scores, least))
        return grads + (jnp.zeros_like(scores), jnp.zeros_like(least))

    fa.defvjp(fa_fwd, fa_bwd)
    return fa


@functools.lru_cache(maxsize=32)
def _make_flash(scale, causal, q_block, kv_block, interpret):
    @jax.custom_vjp
    def fa(q, k, v):
        out, _ = _flash_fwd(q, k, v, scale, causal, q_block, kv_block,
                            interpret)
        return out

    def fa_fwd(q, k, v):
        out, lse = _flash_fwd(q, k, v, scale, causal, q_block, kv_block,
                              interpret)
        # a unit of recomputation keeps these two and forms q, k, v again
        # from its projections: without the marks the forward kernel
        # would run a second time for them (ops/remat.py)
        out, lse = remat.keep(out), remat.keep(lse)
        return out, (q, k, v, out, lse)

    def fa_bwd(res, g):
        q, k, v, o, lse = res
        return _flash_bwd(q, k, v, o, lse, g, scale, causal, q_block,
                          kv_block, interpret)

    fa.defvjp(fa_fwd, fa_bwd)
    return fa


def flash_attention(q, k, v, causal=False, scale=None, q_block=128,
                    kv_block=128, keep=None):
    """Streaming-softmax attention over (B, H, T, D); ``v`` may have a
    width of its own, (B, H, T, Dv), which is then the output's.

    ``keep = (scores, least)`` is a set of kept keys per query that the
    heads of a sequence share, as data: scores (B, T, T) float32 with the
    KEYS on axis 1 and the queries on axis 2, least (B, T); query t
    attends key s only where ``scores[b, s, t] >= least[b, t]`` (and
    where ``causal`` lets it).  The kernels compare a tile at a time, so
    no mask is ever formed.  The call then returns (out, lse): lse
    (B, H, T) float32, the logsumexp of each query's scaled scores over
    its kept keys.  No gradient reaches ``keep`` and none is taken from
    lse.  A query must keep at least one key.

    Pallas kernel on TPU; interpret-mode on CPU (slow — tests only).
    Falls back to the dense XLA path when shapes are too small to tile.
    Inside a ``head_sharding_scope`` (ops/pallas/partition.py) the call
    is shard_mapped over the scope's batch and heads axes.
    """
    B, H, T, D = q.shape
    scale = float(scale if scale is not None else 1.0 / math.sqrt(D))
    if T < 16 or D % 8 != 0 or v.shape[-1] % 8 != 0:
        return _dense_attention(q, k, v, scale, causal, keep)
    q_block = min(q_block, T)
    kv_block = min(kv_block, T)
    interpret = jax.default_backend() == "cpu"
    counters.bump(KERNEL_NAME)
    if keep is not None:
        return _make_flash_kept(scale, causal, q_block, kv_block,
                                interpret)(q, k, v, *keep)
    fa = _make_flash(scale, causal, q_block, kv_block, interpret)
    # inside a sharded training step or tp>1 decoder program GSPMD
    # cannot partition the kernel: split it over batch and heads
    return shard_attention(fa, B, H)(q, k, v)


@register_op("flash_attention", aliases=("_contrib_flash_attention",))
def flash_attention_op(q, k, v, causal=False, scale=None, q_block=128,
                       kv_block=128):
    return flash_attention(q, k, v, causal=causal, scale=scale,
                           q_block=q_block, kv_block=kv_block)
