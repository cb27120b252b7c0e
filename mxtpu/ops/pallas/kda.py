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
``kda_chunk_fwd`` forms it a chunk a grid step (``_chunk_math``), and
``kda_chunk_bwd`` is the same function's ``jax.vjp`` inside a kernel.
Only

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
Each partial sum of ``g`` is formed over its own span (a product with a
0/1 matrix), never as a difference of two long sums.
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
from . import counters

__all__ = ["kda", "kda_mixer", "kernel_specs"]

CHUNK_FWD_NAME = "kda_chunk_fwd"
CHUNK_BWD_NAME = "kda_chunk_bwd"
FWD_NAME = "kda_state_fwd"
FWD_STATES_NAME = "kda_state_fwd_states"    # the forward the backward runs
BWD_NAME = "kda_state_bwd"
CHUNK = 64

_HI = jax.lax.Precision.HIGHEST


def kernel_specs(B, H, T, K, V=None, chunk=CHUNK, interpret=False):
    """KernelSpec descriptors (mxtpu.analysis.kernel_check) of the four
    kernels a backward pass issues at this geometry, in its order: the
    chunks' operands forward, the state pass forward as the backward runs
    it (writing every chunk's starting state), the state pass backward,
    the chunks' operands backward."""
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
    ins = lambda names, pre="": [(pre + n, "in", r, c)        # noqa: E731
                                 for n, r, c in names]
    outs = lambda names, pre="": [(pre + n, "out", r, c)      # noqa: E731
                                  for n, r, c in names]
    tag = "[float32,T=%d,K=%d,V=%d,C=%d]" % (T, K, V, C)
    return [
        KernelSpec(CHUNK_FWD_NAME + tag, grid=(BH, N),
                   operands=blocks(at, ins(rows) + outs(operands)),
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
                   operands=blocks(at, ins(rows) + ins(operands, "d")
                                   + outs(rows, "d")),
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
# masks and products, no reshape below a tile — so that the same function
# is the body of the forward kernel and, through ``jax.vjp``, of the
# backward one.  (As batched XLA products the same algebra cost the chip's
# compiler 24 s and 58 MB of code an instance, four instances a layer:
# PERF.md, PR 29.)

def _chunk_math(q, k, bk, bv, g):
    """(W, U0, Qg, M, Khat, gamma) of one chunk.  q, k, g (C, K); bk =
    beta k (C, K) and bv = beta v (C, V): the rows' write strength comes
    folded in.  C a power of two."""
    C = k.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    own = jax.lax.broadcasted_iota(jnp.int32, k.shape, 0)      # a row's index
    ones = lambda mask: jnp.where(mask, 1.0, 0.0)               # noqa: E731
    t_inv = ones(row == col)
    m = t_inv * _dot(q, k, _A_BT)                   # spans of one row: q.k
    h = 1
    while h < C:
        span = 2 * h
        # the first later row of the span a row lies in, and which half
        first = (row & -span) + h
        later = (row & (span - 1)) >= h
        # partial sums of g, never positive: for a later row over the rows
        # after `first` up to it, for an earlier row over the rows after it
        # up to `first`
        sums = ones((later & (col > first) & (col <= row))
                    | (~later & (col > row) & (col <= first)))
        decay = jnp.exp(_dot(sums, g, _A_B))
        late = (own & (span - 1)) >= h
        k_up = jnp.where(late, 0.0, k * decay)
        # rows of a later half against the columns of their span's earlier
        # half, and nothing else
        pair = later & ((col & -span) == (row & -span)) & (col < first)
        a21 = jnp.where(pair, _dot(jnp.where(late, bk * decay, 0.0), k_up,
                                   _A_BT), 0.0)
        m = m + jnp.where(pair, _dot(jnp.where(late, q * decay, 0.0), k_up,
                                     _A_BT), 0.0)
        # [[T1, 0], [-T2 A21 T1, T2]] of every span at once
        t_inv = t_inv - _dot(_dot(t_inv, a21, _A_B), t_inv, _A_B)
        h = span
    decay = jnp.exp(_dot(ones(col <= row), g, _A_B))            # e^G
    after = jnp.exp(_dot(ones(col > row), g, _A_B))             # e^(G_C - G)
    gamma = jnp.exp(jnp.sum(g, axis=0, keepdims=True))          # e^(G_C)
    return (_dot(t_inv, bk * decay, _A_B), _dot(t_inv, bv, _A_B), q * decay,
            m, k * after, gamma)


def _chunk_fwd_kernel(*refs):
    ins, outs = refs[:5], refs[5:]
    for ref, value in zip(outs, _chunk_math(*(r[0, 0] for r in ins))):
        ref[0, 0] = value


def _chunk_bwd_kernel(*refs):
    ins, cts, outs = refs[:5], refs[5:11], refs[11:]
    _, back = jax.vjp(_chunk_math, *(r[0, 0] for r in ins))
    for ref, value in zip(outs, back(tuple(r[0, 0] for r in cts))):
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
    rows: one kernel forward, one backward (which forms the forward again
    inside, chunk by chunk)."""
    def forward(*ins):
        (BH, N, C, K), V = ins[0].shape, ins[3].shape[-1]
        return tuple(_chunk_call(_chunk_fwd_kernel, CHUNK_FWD_NAME, ins,
                                 _operand_shapes(BH, N, C, K, V), interpret))

    @jax.custom_vjp
    def chunk_operands(*ins):
        return forward(*ins)

    def bwd(ins, cts):
        return tuple(_chunk_call(_chunk_bwd_kernel, CHUNK_BWD_NAME,
                                 tuple(ins) + tuple(cts),
                                 [x.shape for x in ins], interpret))

    chunk_operands.defvjp(lambda *ins: (forward(*ins), ins), bwd)
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


def _short_conv(x, filt):
    """Causal depthwise convolution over time: x (B, T, heads, K), filt
    (heads, K, W), one filter a channel, left-padded with zeros."""
    W, T = filt.shape[-1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0), (0, 0)))
    return sum(padded[:, j:j + T] * filt[..., j] for j in range(W))


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
        q, k, v = (jax.nn.silu(_short_conv(x, w)) for x, w in
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
