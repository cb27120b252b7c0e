"""Kimi Delta Attention (KDA): the gated delta rule with a decay per key
channel, computed in chunks (Kimi Linear, arXiv:2510.26692).

Per head, with state ``S`` (K x V), ``S_0 = 0``:

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t                       a_t = exp(g_t) in (0, 1]^K

The sequence is cut into chunks of ``C`` positions.  Within a chunk, with
``G_r`` the sum of ``g`` up to row ``r`` and ``S`` the state the chunk
starts from, the rule unrolls to

    (I + A) U = b (V - (K e^G) S)      A_ri = b_r sum_c k_rc k_ic e^(G_r-G_i),  i < r
    O   = (Q e^G) S + M U              M_ri =     sum_c q_rc k_ic e^(G_r-G_i),  i <= r
    S'  = Diag(e^(G_C)) S + (K e^(G_C-G))^T U

so everything that does not involve ``S`` — the decayed products ``A``
and ``M``, the inverse ``T = (I + A)^-1`` and ``W = T b K e^G``,
``U0 = T b V`` — is parallel over chunks: the Pallas kernel
``kda_chunk_fwd`` forms it a chunk a grid step (``_chunk_math``).  Its
backward ``kda_chunk_bwd`` (``_chunk_bwd_math``) is written by hand and
forms no product of the forward again: it is handed ``T``, which the
forward that the backward pass runs (``kda_chunk_fwd_inverse``) writes
out beside the operands, so ``dA = -T^T dT T^T`` is two products, and
``A`` and ``M`` are bilinear in operands that are decayed again element
by element.  Only

    U = U0 - W S,   O = (Q e^G) S + M U,   S' = Diag(e^(G_C)) S + Khat^T U

runs chunk after chunk: that pass is the Pallas kernel ``kda_state_fwd``
(the state stays in VMEM across the chunk axis of the grid), and its
backward ``kda_state_bwd`` walks the chunks in reverse from the states
that the forward, run again as ``kda_state_fwd_states``, writes out.

No exponent is ever positive: ``A`` and ``M`` are built by halving — the
block of rows in the later half of a span against the columns of its
earlier half takes its reference at the first later row, so both factors
decay — and the inverse is built up the same spans
(``[[T1, 0], [-T2 A21 T1, T2]]``, as ``T - T A21 T`` on the whole tile).
Each partial sum of ``g`` is formed over its own span, never as a
difference of two long sums: all of a chunk's sums are one product of a
0/1 matrix (exact in bfloat16) with the three bfloat16 pieces of ``g`` —
the three of ``Precision.HIGHEST``'s six passes that do not multiply by
zero.  Every other product takes float32 operands at ``HIGHEST``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...base import register_op
from ..remat import keep
from ..short_conv import causal_short_conv
from . import counters

__all__ = ["kda", "kda_mixer", "kernel_specs"]

CHUNK_FWD_NAME = "kda_chunk_fwd"
CHUNK_FWD_INVERSE_NAME = "kda_chunk_fwd_inverse"  # .. and writes T as well
CHUNK_BWD_NAME = "kda_chunk_bwd"
FWD_NAME = "kda_state_fwd"
FWD_STATES_NAME = "kda_state_fwd_states"    # the forward the backward runs
BWD_NAME = "kda_state_bwd"
CHUNK = 64

_HI = jax.lax.Precision.HIGHEST


def kernel_specs(B, H, T, K, V=None, chunk=CHUNK, interpret=False):
    """KernelSpec descriptors (mxtpu.analysis.kernel_check) of the four
    kernels a backward pass issues at this geometry, in its order: the
    chunks' operands forward as the backward runs it (writing every
    chunk's inverse, transposed), the state pass forward likewise (writing
    every chunk's starting state), the state pass backward, the chunks'
    operands backward (which reads the inverse)."""
    from ...analysis.kernel_check import (BlockOperand, KernelSpec,
                                          ScratchOperand)

    V = K if V is None else V
    C = chunk
    N = math.ceil(T / C)
    BH = B * H
    at = lambda b, n: (b, n, 0, 0)             # noqa: E731
    back = lambda b, n: (b, N - 1 - n, 0, 0)   # noqa: E731

    def blocks(imap, kinds):
        return [BlockOperand(name, kind, (1, 1, rows, cols),
                             (BH, N, rows, cols), "float32", imap,
                             strict_dims=())
                for name, kind, rows, cols in kinds]

    rows = [("q", C, K), ("k", C, K), ("bk", C, K), ("bv", C, V),
            ("g", C, K)]
    operands = [("w", C, K), ("u0", C, V), ("qg", C, K), ("m", C, C),
                ("khat", C, K), ("gamma", 1, K)]
    inverse = [("t_t", C, C)]
    ins = lambda names, pre="": [(pre + n, "in", r, c)        # noqa: E731
                                 for n, r, c in names]
    outs = lambda names, pre="": [(pre + n, "out", r, c)      # noqa: E731
                                  for n, r, c in names]
    tag = "[float32,T=%d,K=%d,V=%d,C=%d]" % (T, K, V, C)
    return [
        KernelSpec(CHUNK_FWD_INVERSE_NAME + tag, grid=(BH, N),
                   operands=blocks(at, ins(rows) + outs(operands + inverse)),
                   interpret=interpret),
        KernelSpec(FWD_STATES_NAME + tag, grid=(BH, N),
                   operands=blocks(at, ins(operands) + [
                       ("o", "out", C, V), ("states", "out", V, K)]),
                   scratch=[ScratchOperand("state", (V, K), "float32")],
                   interpret=interpret),
        KernelSpec(BWD_NAME + tag, grid=(BH, N),
                   operands=blocks(back, ins(operands) + [
                       ("states", "in", V, K), ("do", "in", C, V)]
                       + outs(operands, "d")),
                   scratch=[ScratchOperand("dstate", (V, K), "float32")],
                   interpret=interpret),
        KernelSpec(CHUNK_BWD_NAME + tag, grid=(BH, N),
                   operands=blocks(at, ins(rows + inverse)
                                   + ins(operands, "d") + outs(rows, "d")),
                   interpret=interpret)]


# ------------------------------------------------------------ the kernels
#
# The state is kept transposed, (V, K): the decay of a chunk then scales
# its lanes, and the three ways a product meets it are the three forms
# the flash kernels use (a @ b.T, a @ b, a.T @ b).

_dot = functools.partial(jax.lax.dot_general, precision=_HI,
                         preferred_element_type=jnp.float32)
_A_BT = (((1,), (1,)), ((), ()))
_A_B = (((1,), (0,)), ((), ()))
_AT_B = (((0,), (0,)), ((), ()))


def _fwd_kernel(w_ref, u0_ref, qg_ref, m_ref, khat_ref, gamma_ref, o_ref,
                *rest, save_states):
    if save_states:
        states_ref, st = rest
    else:
        (st,) = rest

    @pl.when(pl.program_id(1) == 0)
    def _():
        st[...] = jnp.zeros_like(st)

    s = st[...]                                     # (V, K)
    if save_states:
        states_ref[0, 0] = s
    u = u0_ref[0, 0] - _dot(w_ref[0, 0], s, _A_BT)              # (C, V)
    o_ref[0, 0] = _dot(qg_ref[0, 0], s, _A_BT) + _dot(m_ref[0, 0], u, _A_B)
    st[...] = gamma_ref[0, 0] * s + _dot(u, khat_ref[0, 0], _AT_B)


def _bwd_kernel(w_ref, u0_ref, qg_ref, m_ref, khat_ref, gamma_ref,
                states_ref, do_ref, dw_ref, du0_ref, dqg_ref, dm_ref,
                dkhat_ref, dgamma_ref, dst):
    @pl.when(pl.program_id(1) == 0)
    def _():
        dst[...] = jnp.zeros_like(dst)

    s, ds_next = states_ref[0, 0], dst[...]         # (V, K) both
    w, do = w_ref[0, 0], do_ref[0, 0]
    u = u0_ref[0, 0] - _dot(w, s, _A_BT)
    du = _dot(m_ref[0, 0], do, _AT_B) + _dot(khat_ref[0, 0], ds_next, _A_BT)
    du0_ref[0, 0] = du
    dw_ref[0, 0] = -_dot(du, s, _A_B)
    dqg_ref[0, 0] = _dot(do, s, _A_B)
    dm_ref[0, 0] = _dot(do, u, _A_BT)
    dkhat_ref[0, 0] = _dot(u, ds_next, _A_B)
    dgamma_ref[0, 0] = jnp.sum(s * ds_next, axis=0, keepdims=True)
    dst[...] = (_dot(do, qg_ref[0, 0], _AT_B) + gamma_ref[0, 0] * ds_next
                - _dot(du, w, _AT_B))


def _specs(shapes, imap):
    return [pl.BlockSpec((1, 1) + s[2:], imap) for s in shapes]


_SEQUENTIAL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def _state_fwd(ops, save_states, interpret):
    w, u0, qg, m, khat, gamma = ops
    BH, N, C, K = w.shape
    V = u0.shape[-1]
    at = lambda b, n: (b, n, 0, 0)             # noqa: E731
    outs = [(BH, N, C, V)] + ([(BH, N, V, K)] if save_states else [])
    name = FWD_STATES_NAME if save_states else FWD_NAME
    counters.bump(name)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, save_states=save_states),
        out_shape=[jax.ShapeDtypeStruct(s, jnp.float32) for s in outs],
        grid=(BH, N),
        in_specs=_specs([x.shape for x in ops], at),
        out_specs=_specs(outs, at),
        scratch_shapes=[pltpu.VMEM((V, K), jnp.float32)],
        compiler_params=_SEQUENTIAL,
        interpret=interpret, name=name)(*ops)


def _state_bwd(ops, states, do, interpret):
    BH, N = do.shape[:2]
    V, K = states.shape[2:]
    back = lambda b, n: (b, N - 1 - n, 0, 0)   # noqa: E731
    ins = tuple(ops) + (states, do)
    outs = [x.shape for x in ops]
    counters.bump(BWD_NAME)
    return pl.pallas_call(
        _bwd_kernel,
        out_shape=[jax.ShapeDtypeStruct(s, jnp.float32) for s in outs],
        grid=(BH, N),
        in_specs=_specs([x.shape for x in ins], back),
        out_specs=_specs(outs, back),
        scratch_shapes=[pltpu.VMEM((V, K), jnp.float32)],
        compiler_params=_SEQUENTIAL,
        interpret=interpret, name=BWD_NAME)(*ins)


@functools.lru_cache(maxsize=None)
def _make_state_pass(interpret):
    """O from the chunks' operands: the one pass over the chunks, with
    its own backward (the chunks' starting states are not kept: the
    backward runs the forward again to write them, as the flash kernel
    re-forms its scores)."""
    @jax.custom_vjp
    def state_pass(*ops):
        return _state_fwd(ops, False, interpret)[0]

    def fwd(*ops):
        return _state_fwd(ops, False, interpret)[0], ops

    def bwd(ops, do):
        _, states = _state_fwd(ops, True, interpret)
        return tuple(_state_bwd(ops, states, do, interpret))

    state_pass.defvjp(fwd, bwd)
    return state_pass


# ------------------------------------------------- parallel over chunks
#
# One chunk's operands from its rows, as whole (C, C) and (C, K) tiles —
# masks and products, no reshape below a tile.  ``_chunk_math`` is the body
# of the forward kernel and ``_chunk_bwd_math``, written by hand, of the
# backward one: ``jax.vjp(_chunk_math)`` is what the tests hold it to.
# (As batched XLA products the same algebra cost the chip's compiler 24 s
# and 58 MB of code an instance, four instances a layer: PERF.md, PR 29.)

def _halvings(C):
    """The half-spans of a chunk of C rows: 1, 2, .. C / 2."""
    return [1 << i for i in range(C.bit_length() - 1)]


def _tile_indices(C):
    return (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0),
            jax.lax.broadcasted_iota(jnp.int32, (C, C), 1))


def _pair(row, col, h):
    """Rows of a later half against the columns of their span's earlier
    half, and nothing else."""
    span = 2 * h
    return (((row & (span - 1)) >= h) & ((col & -span) == (row & -span))
            & ((col & (span - 1)) < h))


def _span_matrix(C):
    """The 0/1 matrix whose product with g gives every partial sum of g a
    chunk needs, a block of C rows each, none of them positive.  A block a
    halving: with ``first`` the first later row of the span a row lies in,
    for a later row the sum over the rows after ``first`` up to it, for an
    earlier row over the rows after it up to ``first``.  Then G (the rows
    up to a row) and G_C - G (the rows after it).  bfloat16 holds 0 and 1
    exactly."""
    row, col = _tile_indices(C)
    blocks = []
    for h in _halvings(C):
        first = (row & -(2 * h)) + h
        later = (row & (2 * h - 1)) >= h
        blocks.append((later & (col > first) & (col <= row))
                      | (~later & (col > row) & (col <= first)))
    blocks += [col <= row, col > row]
    return jnp.concatenate([jnp.where(b, 1.0, 0.0) for b in blocks],
                           axis=0).astype(jnp.bfloat16)


def _top(x):
    """float32 ``x`` with its significand cut to bfloat16's eight bits."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32) & jnp.int32(-65536)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _exact_product(spans, x, dims):
    """``spans`` (0/1, bfloat16) times float32 ``x`` along ``dims``, as
    ``Precision.HIGHEST`` gives it: ``x`` is the sum of three bfloat16
    pieces, and of HIGHEST's six passes the three that take a lower piece
    of ``spans`` multiply by zero.  The other three, as one product."""
    K = x.shape[1]
    hi = _top(x)
    mid = _top(x - hi)
    pieces = jnp.concatenate([hi, mid, x - hi - mid], axis=1)
    p = jax.lax.dot_general(spans, pieces.astype(jnp.bfloat16), dims,
                            preferred_element_type=jnp.float32)
    return p[:, :K] + (p[:, K:2 * K] + p[:, 2 * K:])


@jax.custom_vjp
def _span_sums(spans, g):
    """Every partial sum of g, ((halvings + 2) C, K).  (Linear in g; the
    rule is written out because the pieces are cut bit by bit.)"""
    return _exact_product(spans, g, _A_B)


_span_sums.defvjp(
    lambda spans, g: (_span_sums(spans, g), spans),
    lambda spans, ct: (jnp.zeros_like(spans),
                       _exact_product(spans, ct, _AT_B)))


def _decays(spans, g):
    """exp of every partial sum of g: a (C, K) tile a halving, then e^G,
    e^(G_C - G) and e^(G_C) (1, K)."""
    C = g.shape[0]
    sums = _span_sums(spans, g)
    *halvings, decay, after = (jnp.exp(sums[i:i + C])
                               for i in range(0, sums.shape[0], C))
    return halvings, decay, after, jnp.exp(jnp.sum(g, axis=0, keepdims=True))


def _chunk_math(q, k, bk, bv, g):
    """(W, U0, Qg, M, Khat, gamma, T^T) of one chunk.  q, k, g (C, K); bk
    = beta k (C, K) and bv = beta v (C, V): the rows' write strength comes
    folded in.  C a power of two.

    A, M and T are held transposed: a halving's two decayed products are
    then one product, (K d) [bK d; Q d]^T, whose 2 C columns fill the
    matrix unit's width, and the backward pass wants T^T."""
    C, K = k.shape
    row, col = _tile_indices(C)
    decays, decay, after, gamma = _decays(_span_matrix(C), g)
    t_t = jnp.where(row == col, 1.0, 0.0)
    m_t = t_t * jnp.sum(q * k, axis=1, keepdims=True)   # spans of one row
    for h, d in zip(_halvings(C), decays):
        # both factors decay, the later rows from `first` on and the
        # earlier ones up to it; what is no pair of the two is masked away
        pair_t = _pair(col, row, h)
        am_t = _dot(k * d, jnp.concatenate([bk * d, q * d], axis=0),
                    _A_BT)                                      # (C, 2 C)
        a21_t = jnp.where(pair_t, am_t[:, :C], 0.0)
        m_t = m_t + jnp.where(pair_t, am_t[:, C:], 0.0)
        # [[T1, 0], [-T2 A21 T1, T2]] of every span at once; the spans of
        # one row start from T = I, where that is I - A21
        t_t = t_t - (a21_t if h == 1 else
                     _dot(_dot(t_t, a21_t, _A_B), t_t, _A_B))
    wu = _dot(t_t, jnp.concatenate([bk * decay, bv], axis=1), _AT_B)
    return wu[:, :K], wu[:, K:], q * decay, m_t.T, k * after, gamma, t_t


def _chunk_bwd_math(q, k, bk, bv, g, t_t, dw, du0, dqg, dm, dkhat, dgamma):
    """(dq, dk, dbk, dbv, dg) of one chunk from its rows, ``t_t`` = T^T
    as the forward wrote it (T = (I + A)^-1), and the cotangents of (W,
    U0, Qg, M, Khat, gamma).  No product of the forward is formed again:
    ``dA = -T^T dT T^T``, and A and M are bilinear in their decayed
    operands, which are formed again element by element."""
    C, K = k.shape
    row, col = _tile_indices(C)
    spans = _span_matrix(C)
    decays, decay, after, gamma = _decays(spans, g)
    # W = T (bK e^G), U0 = T (bV)
    dwu = jnp.concatenate([dw, du0], axis=1)
    dt = _dot(dwu, jnp.concatenate([bk * decay, bv], axis=1), _A_BT)
    back = _dot(t_t, dwu, _A_B)
    dp, dbv = back[:, :K], back[:, K:]
    da = -_dot(_dot(t_t, dt, _A_B), t_t, _A_B)
    own = jnp.sum(jnp.where(row == col, dm, 0.0), axis=1, keepdims=True)
    dq = dqg * decay + own * k
    dk = dkhat * after + own * q
    dbk = dp * decay
    dsums = []
    for h, d in zip(_halvings(C), decays):
        pair = _pair(row, col, h)
        cts = jnp.concatenate([jnp.where(pair, da, 0.0),
                               jnp.where(pair, dm, 0.0)], axis=0)   # (2C, C)
        # of the later rows' (bK d; Q d) and of the earlier rows' K d: the
        # mask has left nothing in the other rows
        late = _dot(cts, k * d, _A_B)                           # (2 C, K)
        early = _dot(cts, jnp.concatenate([bk * d, q * d], axis=0), _AT_B)
        dbk = dbk + late[:C] * d
        dq = dq + late[C:] * d
        dk = dk + early * d
        # a log decay's cotangent: value times cotangent
        dsums.append((late[:C] * bk + late[C:] * q + early * k) * d)
    dsums += [(dp * bk + dqg * q) * decay, dkhat * k * after]
    dg = _exact_product(spans, jnp.concatenate(dsums, axis=0), _AT_B)
    return dq, dk, dbk, dbv, dg + dgamma * gamma


def _chunk_fwd_kernel(*refs):
    """Writes as many of ``_chunk_math``'s values as it has outputs: the
    six operands, and T^T after them where the backward pass asks."""
    ins, outs = refs[:5], refs[5:]
    for ref, value in zip(outs, _chunk_math(*(r[0, 0] for r in ins))):
        ref[0, 0] = value


def _chunk_bwd_kernel(*refs):
    ins, outs = refs[:12], refs[12:]
    for ref, value in zip(outs, _chunk_bwd_math(*(r[0, 0] for r in ins))):
        ref[0, 0] = value


_PARALLEL = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"))


def _operand_shapes(BH, N, C, K, V):
    return [(BH, N, C, K), (BH, N, C, V), (BH, N, C, K), (BH, N, C, C),
            (BH, N, C, K), (BH, N, 1, K)]


def _chunk_call(kernel, name, ins, outs, interpret):
    BH, N = ins[0].shape[:2]
    at = lambda b, n: (b, n, 0, 0)             # noqa: E731
    counters.bump(name)
    return pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct(s, jnp.float32) for s in outs],
        grid=(BH, N),
        in_specs=_specs([x.shape for x in ins], at),
        out_specs=_specs(outs, at),
        compiler_params=_PARALLEL,
        interpret=interpret, name=name)(*ins)


@functools.lru_cache(maxsize=None)
def _make_chunk_operands(interpret):
    """(W, U0, Qg, M, Khat, gamma) of every chunk from (BH, N, C, .)
    rows: one kernel forward, one backward.  Under differentiation the
    forward also writes every chunk's inverse (T^T, C x C a chunk), the
    one thing the backward kernel is handed beside the rows."""
    def forward(ins, name, inverse):
        (BH, N, C, K), V = ins[0].shape, ins[3].shape[-1]
        outs = _operand_shapes(BH, N, C, K, V) + (
            [(BH, N, C, C)] if inverse else [])
        return tuple(_chunk_call(_chunk_fwd_kernel, name, ins, outs,
                                 interpret))

    @jax.custom_vjp
    def chunk_operands(*ins):
        return forward(ins, CHUNK_FWD_NAME, False)

    def fwd(*ins):
        *ops, t_t = forward(ins, CHUNK_FWD_INVERSE_NAME, True)
        return tuple(ops), (ins, t_t)

    def bwd(kept, cts):
        ins, t_t = kept
        return tuple(_chunk_call(_chunk_bwd_kernel, CHUNK_BWD_NAME,
                                 tuple(ins) + (t_t,) + tuple(cts),
                                 [x.shape for x in ins], interpret))

    chunk_operands.defvjp(fwd, bwd, optimize_remat=True)
    return chunk_operands


#: heads that go through a mixer's core at a time: between the projections
#: and the output lie some twenty arrays of T x K a head (convolved,
#: activated and normalised q, k, v, the decay, the chunks' operands, their
#: cotangents), 134 MB each at 32 heads x 8,192 x 128; so the heads are
#: taken in groups, one after another, each formed again in the backward
#: pass from the group's inputs
HEADS_AT_A_TIME = 4


def heads_per_call(heads):
    """How many of ``heads`` one call of a kernel takes (with every row
    of the batch): the largest divisor up to ``HEADS_AT_A_TIME``."""
    return max(d for d in range(1, min(HEADS_AT_A_TIME, heads) + 1)
               if heads % d == 0)


def _by_groups_of_heads(fn, seqs, per_head=()):
    """``fn(seqs, per_head)`` over the heads a group at a time, under
    ``jax.checkpoint``: the backward pass keeps ``seqs`` ((B, T, H, ..)
    each) and ``per_head`` ((H, ..) each) and forms a group's everything
    again.  ``fn`` returns (B, T, heads, V); so does this."""
    fn = jax.checkpoint(fn)
    H = seqs[0].shape[2]
    per = heads_per_call(H)
    if per == H:
        return fn(tuple(seqs), tuple(per_head))
    groups = H // per
    seqs = tuple(jnp.moveaxis(a.reshape(a.shape[:2] + (groups, per)
                                        + a.shape[3:]), 2, 0) for a in seqs)
    per_head = tuple(a.reshape((groups, per) + a.shape[1:])
                     for a in per_head)
    o = jax.lax.map(lambda xs: fn(*xs), (seqs, per_head))  # (G, B, T, per, V)
    o = jnp.moveaxis(o, 0, 2)
    return o.reshape(o.shape[:2] + (H,) + o.shape[4:])


def _recurrence(q, k, v, g, beta, chunk):
    """The chunked rule for float32 (B, T, heads, .) inputs."""
    B, T, H, _ = k.shape
    pad = (-T) % chunk
    N = (T + pad) // chunk

    def chunks(a):
        a = jnp.moveaxis(a, 1, 2)                           # (B, H, T, ..)
        a = jnp.pad(a, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 3))
        return a.reshape((B * H, N, chunk) + a.shape[3:])

    interpret = jax.default_backend() == "cpu"
    b = beta[..., None]
    ops = _make_chunk_operands(interpret)(
        *map(chunks, (q, k, b * k, b * v, g)))
    o = _make_state_pass(interpret)(*ops)
    o = o.reshape(B, H, N * chunk, o.shape[-1])[:, :, :T]
    return jnp.moveaxis(o, 1, 2)


def _check_chunk(chunk):
    if chunk & (chunk - 1):
        raise ValueError("chunk must be a power of two, got %r" % (chunk,))


def kda(q, k, v, g, beta, chunk=CHUNK):
    """Kimi Delta Attention over (B, T, H, .): ``q``, ``k`` and the log
    decay ``g`` (<= 0) of width K, ``v`` of width V, ``beta`` (B, T, H).
    ``q`` and ``k`` come normalised and scaled as the model wants them.
    Returns (B, T, H, V) in ``v``'s dtype; the algebra is float32.

    ``chunk`` (a power of two) is the length the sequence is cut into;
    a length that is no multiple of it is padded with positions that
    neither decay nor write.  The backward pass keeps the five inputs
    and nothing else: the chunks' operands and states are formed again,
    ``HEADS_AT_A_TIME`` heads at a time.  The output is marked for the
    unit of recomputation that holds the call (ops/remat.py): a unit
    that kept its input alone would run this forward a second time for
    nothing but the output."""
    _check_chunk(chunk)
    seqs = tuple(a.astype(jnp.float32) for a in (q, k, v, g, beta))
    o = _by_groups_of_heads(
        lambda xs, _: _recurrence(*xs, chunk=chunk), seqs)
    return keep(o.astype(v.dtype))


def _l2_normalize(x, eps):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def kda_mixer(q, k, v, f, gate, beta, q_conv, k_conv, v_conv, a_log,
              dt_bias, norm_weight, eps=1e-5, l2_eps=1e-6, chunk=CHUNK):
    """Everything of a KDA mixer between its projections, head by head.

    ``q``, ``k``, ``v`` (B, T, H, K) are the raw projections: each goes
    through its causal short convolution (``*_conv`` (H * K, W)) and
    SiLU; ``q`` and ``k`` are then divided by their norm per head and
    ``q`` scaled by K^-1/2.  ``f`` (B, T, H, K) gives the log decay
    ``-exp(a_log[h]) * softplus(f + dt_bias)``; ``beta`` (B, T, H) is the
    write strength as it is used.  The recurrence's output is RMS-normed
    per head (``norm_weight`` (K,), ``eps``) and gated by
    ``sigmoid(gate)``.  Returns (B, T, H, K) in ``v``'s dtype.

    One fused op and not five because of what the backward pass keeps:
    the five projections and this op's output — a third of what the
    pieces keep one by one (a dozen more arrays of B x T x H x K).  Under
    recomputation per unit the projections are formed again and the
    output, marked here outside the groups' own checkpoint, is kept
    (ops/remat.py): B x T x H x K buys back a whole forward of the
    mixer."""
    _check_chunk(chunk)
    H, K = q.shape[2:]
    f32 = lambda a: a.astype(jnp.float32)                   # noqa: E731
    norm_weight = f32(norm_weight)

    def heads(seqs, per_head):
        q, k, v, f, gate, beta = seqs
        q_conv, k_conv, v_conv, a_log, dt_bias = per_head
        q, k, v = (jax.nn.silu(causal_short_conv(x, w)) for x, w in
                   ((q, q_conv), (k, k_conv), (v, v_conv)))
        q = _l2_normalize(q, l2_eps) * K ** -0.5
        k = _l2_normalize(k, l2_eps)
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(f + dt_bias)
        o = _recurrence(q, k, v, g, beta, chunk)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
        return o * norm_weight * jax.nn.sigmoid(gate)

    W = q_conv.shape[-1]
    o = _by_groups_of_heads(
        heads, tuple(map(f32, (q, k, v, f, gate, beta))),
        (f32(q_conv).reshape(H, K, W), f32(k_conv).reshape(H, K, W),
         f32(v_conv).reshape(H, K, W), f32(a_log),
         f32(dt_bias).reshape(H, K)))
    return keep(o.astype(v.dtype))


@register_op("kda", aliases=("_contrib_kda",))
def kda_op(q, k, v, g, beta, chunk=CHUNK):
    return kda(q, k, v, g, beta, chunk=chunk)


@register_op("kda_mixer", aliases=("_contrib_kda_mixer",))
def kda_mixer_op(q, k, v, f, gate, beta, q_conv, k_conv, v_conv, a_log,
                 dt_bias, norm_weight, eps=1e-5, l2_eps=1e-6, chunk=CHUNK):
    return kda_mixer(q, k, v, f, gate, beta, q_conv, k_conv, v_conv, a_log,
                     dt_bias, norm_weight, eps=eps, l2_eps=l2_eps,
                     chunk=chunk)
