"""Mixture-of-Experts ops (SURVEY §2.3 row 59 — EP/MoE, absent in the
reference).

Two expert layers live here.  ``switch_moe`` is the static-capacity one:
softmax router, one-hot dispatch/combine einsums of shape (S, E, C)
(the GShard/Switch-Transformer formulation that GSPMD turns into expert
all-to-alls when the expert dimension is sharded over the mesh "ep"
axis), tokens over capacity dropped.  It is what the serving layers use
today (``SwitchMoE.decode_forward`` / ``prefill_forward``,
``MoEDecoderLayer`` in the engines).  For training use
``moe_expert_share``: no capacity, no dropped token, no (S, E, C) tensor
— a sigmoid top-k router over all experts, the pairs that fall to the
experts this share holds sorted by expert and run through grouped
products.

Routing: top-1 (Switch, default) or top-k (GShard top-2) — the discrete
choice gets gradients through the selected gate probabilities
(straight-through) plus the load-balancing auxiliary loss; optional
router z-loss (ST-MoE) and input jitter (Switch appendix) stabilize
training at scale.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..base import register_op


@register_op("switch_moe", num_outputs=2)
def switch_moe(x, router_w, w1, w2, capacity_factor=1.25,
               activation="swish", top_k=1, normalize_gates=True,
               capacity=None, *,
               router_jitter=0.0, z_loss_weight=0.0, _training=False,
               _key=None):
    """Routed expert FFN (Switch top-1 / GShard top-k).

    router_jitter onward is keyword-only: invoke_op's RNG-key injection
    is gated on kwargs["router_jitter"], so a positional spelling would
    silently disable the jitter it asks for.

    x (B, T, d) or (S, d); router_w (E, d) — Dense (out, in) layout;
    w1 (E, d, h); w2 (E, h, d).  Returns (y, aux): y matches x's shape
    with dropped-token rows zeroed (callers add the residual); aux is
    the E * sum(f_e * p_e) load-balancing scalar plus, when
    z_loss_weight > 0, the router z-loss (mean logsumexp(logits)^2 —
    ST-MoE's logit-magnitude regularizer).

    top_k > 1: each token is dispatched to its k best experts; capacity
    is filled first-choice-first (GShard's priority order), and with
    normalize_gates the k selected probabilities are renormalized to
    sum to 1.

    router_jitter: multiplicative uniform noise on the router INPUT in
    (1-eps, 1+eps), training only (Switch Transformer appendix B) —
    needs the injected RNG key (the op is registered key-needing, like
    Dropout).

    capacity_factor <= 0 disables the capacity limit entirely (capacity
    = S): the incremental-decode configuration, where a step sees only
    B tokens and the training capacity would spuriously drop them.

    capacity (static int, optional): explicit per-expert slot count
    overriding the capacity_factor formula.  Chunked prefill uses this
    to budget from the FULL prompt length rather than the chunk it
    happens to see (ADVICE r5), so a small chunk is never squeezed into
    a spuriously tiny capacity.
    """
    orig_shape = x.shape
    d = orig_shape[-1]
    xf = x.reshape(-1, d)
    S = xf.shape[0]
    E = router_w.shape[0]
    k = int(top_k)
    cdt = jnp.float32

    xr = xf.astype(cdt)
    if router_jitter and _training and _key is not None:
        noise = jax.random.uniform(_key, xr.shape, cdt,
                                   1.0 - router_jitter,
                                   1.0 + router_jitter)
        xr = xr * noise
    logits = jnp.dot(xr, router_w.astype(cdt).T)              # (S, E)
    gates = jax.nn.softmax(logits, axis=-1)

    if capacity is not None:
        capacity = max(1, int(capacity))
    elif capacity_factor <= 0:
        capacity = S * k  # unbounded: nothing can drop
    else:
        # k-scaled per GShard: top-k dispatches k*S assignments, so the
        # per-expert budget scales with k or second choices mass-drop
        capacity = max(1, int(math.ceil(k * S / E * capacity_factor)))

    topv, topi = jax.lax.top_k(gates, k)                      # (S, k)
    if k > 1 and normalize_gates:
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)

    # (k, S, E) one-hots; capacity fills in choice-priority order: every
    # token's first choice outranks any token's second choice (GShard)
    oh = jax.nn.one_hot(jnp.swapaxes(topi, 0, 1), E, dtype=cdt)
    flat = oh.reshape(k * S, E)                 # k-major: choice 0 first
    pos = jnp.cumsum(flat, axis=0) * flat                     # 1-based
    my_pos = jnp.sum(pos, axis=-1).reshape(k, S)
    within = (my_pos >= 1) & (my_pos <= capacity)
    slot = jax.nn.one_hot((my_pos - 1).astype(jnp.int32), capacity,
                          dtype=cdt) * within[..., None].astype(cdt)
    # dispatch mask (S, E, C): sum over choices (disjoint slots)
    disp = jnp.einsum("kse,ksc->sec", oh, slot)
    # combine weights carry the per-choice gate values
    comb = jnp.einsum("kse,ksc,sk->sec", oh, slot, topv)

    xe = jnp.einsum("sec,sd->ecd", disp, xf.astype(cdt))
    h = jnp.einsum("ecd,edh->ech", xe, w1.astype(cdt))
    if activation == "swish":
        h = h * jax.nn.sigmoid(h)
    elif activation == "gelu":
        h = jax.nn.gelu(h)
    else:
        h = jax.nn.relu(h)
    ye = jnp.einsum("ech,ehd->ecd", h, w2.astype(cdt))
    y = jnp.einsum("sec,ecd->sd", comb, ye)

    # load-balancing loss over FIRST choices (Switch; GShard uses the
    # same first-choice fraction for top-2)
    frac = jnp.mean(oh[0], axis=0)
    prob = jnp.mean(gates, axis=0)
    aux = E * jnp.sum(jax.lax.stop_gradient(frac) * prob)
    if z_loss_weight:
        z = jax.scipy.special.logsumexp(logits, axis=-1)
        aux = aux + z_loss_weight * jnp.mean(jnp.square(z))
    return y.reshape(orig_shape).astype(x.dtype), aux.astype(jnp.float32)


#: sorted (token, expert) pairs that go through the grouped products at a
#: time, as a measure: a tile takes up to half as many again
#: (``_tile_rows``).  The held pairs are taken in as many tiles as they
#: fill, so time and memory follow the load this share really has; a
#: share that every token chose for every slot just takes more tiles.
PAIRS_PER_TILE = 4096


def _tile_rows(pairs, expected):
    """Rows of a tile for ``pairs`` (token, expert) pairs of which an
    even router sends ``expected`` to this share.  A tile costs what its
    rows cost however few pairs it holds, and large tiles run the grouped
    products nearer their ceiling than small ones, so the tiles are the
    fewest, of at most one and a half ``PAIRS_PER_TILE``, that hold the
    even load and half a ``PAIRS_PER_TILE`` more (in whole quarters of
    it): the even load never ends at a tile's edge, where every
    fluctuation of the routers would decide whether one more tile runs.
    2,048 expected pairs take one tile of 4,096, and 4,096 one of 6,144."""
    need = expected + PAIRS_PER_TILE // 2
    n = math.ceil(need / (1.5 * PAIRS_PER_TILE))
    quarter = max(1, PAIRS_PER_TILE // 4)
    return min(math.ceil(need / n / quarter) * quarter, pairs)


def _tile(xf, pair_weight, w_gate, w_up, w_down, order, bounds, i, k, rows,
          hi):
    """What tile ``i`` of the sorted held pairs adds to the result:
    (S, d).  ``order`` lists pair ids (token * k + slot) sorted by held
    expert, held pairs first; ``bounds`` (held + 1,) are the experts'
    boundaries in it."""
    S = xf.shape[0]
    start = i * rows
    pair = jax.lax.dynamic_slice_in_dim(order, start, rows)
    token = pair // k
    here = ((start + jnp.arange(rows)) < bounds[-1])[:, None]
    sizes = jnp.diff(jnp.clip(bounds - start, 0, rows))
    grouped = functools.partial(jax.lax.ragged_dot, group_sizes=sizes,
                                precision=hi)
    x = jnp.where(here, xf[token], 0)                        # (rows, d)
    h = jax.nn.silu(grouped(x, w_gate)) * grouped(x, w_up)
    out = jnp.where(here, grouped(h, w_down), 0)
    out = out * pair_weight[pair][:, None].astype(out.dtype)
    return jax.ops.segment_sum(out, token, num_segments=S)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _held_experts(xf, pair_weight, w_gate, w_up, w_down, order, bounds, k,
                  rows, hi):
    """Sum over the held pairs of weight * expert(x): a loop over as many
    tiles of the sorted pairs as are held (a trip count known only on
    the device), forward and backward, each tile formed again in the
    backward pass."""
    tiles = -(-bounds[-1] // rows)

    def add(i, y):
        return y + _tile(xf, pair_weight, w_gate, w_up, w_down, order,
                         bounds, i, k, rows, hi)

    return jax.lax.fori_loop(0, tiles, add, jnp.zeros_like(xf))


def _held_experts_fwd(xf, pair_weight, w_gate, w_up, w_down, order, bounds,
                      k, rows, hi):
    y = _held_experts(xf, pair_weight, w_gate, w_up, w_down, order, bounds,
                      k, rows, hi)
    return y, (xf, pair_weight, w_gate, w_up, w_down, order, bounds)


def _held_experts_bwd(k, rows, hi, kept, dy):
    *inputs, order, bounds = kept
    tiles = -(-bounds[-1] // rows)

    def add(i, grads):
        _, back = jax.vjp(lambda *a: _tile(*a, order, bounds, i, k, rows,
                                           hi), *inputs)
        return tuple(g + d for g, d in zip(grads, back(dy)))

    grads = jax.lax.fori_loop(0, tiles, add,
                              tuple(jnp.zeros_like(a) for a in inputs))
    return grads + (None, None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


@register_op("moe_expert_share", num_outputs=2)
def moe_expert_share(x, router_w, select_bias, w_gate, w_up, w_down,
                     held_first=0, top_k=8, renormalize=True,
                     scale=1.0, score="sigmoid", renorm_eps=0.0):
    """The routed part of an expert layer that holds a share of the
    experts (expert parallelism: one rank's part of the result).

    x (.., d); router_w (E, d) over ALL ``E`` experts; select_bias (E,),
    added to the scores for the choice only (no gradient reaches it);
    w_gate, w_up (held, d, f) and w_down (held, f, d): the gated experts
    ``held_first .. held_first + held - 1`` this share holds.

    Every token chooses its ``top_k`` experts among all ``E`` by
    ``score(x router_w^T) + select_bias`` — ``score`` "sigmoid", each
    expert's own, or "softmax" over all ``E``, in float32 — and weighs
    them by the scores themselves, renormalised over all the chosen
    (held here or not; ``renorm_eps``, 0 by default, is added to their
    sum) and scaled by ``scale``.  The (token, expert)
    pairs that fall to held experts are sorted by expert and go, a tile
    at a time (``_tile_rows``: about ``PAIRS_PER_TILE``), through three
    grouped products (``lax.ragged_dot``),
    ``w_down(silu(w_gate x) * w_up x)``; there is no capacity and no
    pair is dropped.  Pairs that fall to experts held elsewhere add
    nothing here: on one chip there is no exchange.

    Returns (y, load): y like x, and load (held + 1,) int32 — the pairs
    each held expert received, then the pairs that fell elsewhere.
    """
    shape, d = x.shape, x.shape[-1]
    held, k = w_gate.shape[0], int(top_k)
    hi = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
    xf = x.reshape(-1, d)
    score_fn = {"sigmoid": jax.nn.sigmoid, "softmax": jax.nn.softmax}[score]
    scores = score_fn(jnp.dot(
        xf.astype(jnp.float32), router_w.astype(jnp.float32).T,
        precision=jax.lax.Precision.HIGHEST))                 # (S, E)
    _, chosen = jax.lax.top_k(
        scores + jax.lax.stop_gradient(select_bias.astype(jnp.float32)), k)
    weight = jnp.take_along_axis(scores, chosen, axis=-1)     # (S, k)
    if renormalize:
        total = jnp.sum(weight, -1, keepdims=True)
        # no ``+ 0.0`` in the program of a caller that passes none
        weight = weight / (total + renorm_eps if renorm_eps else total)
    weight = weight * scale

    local = (chosen - held_first).reshape(-1)                 # (S * k,)
    mine = (local >= 0) & (local < held)
    group = jnp.where(mine, local, held)          # elsewhere: sorted last
    load = jnp.sum(jax.nn.one_hot(group, held + 1, dtype=jnp.int32), 0)
    rows = _tile_rows(group.size, group.size * held / router_w.shape[0])
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    order = jnp.pad(order, (0, (-order.size) % rows))
    bounds = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(load[:held])])
    y = _held_experts(xf, jnp.where(mine, weight.reshape(-1), 0), w_gate,
                      w_up, w_down, order, bounds, k, rows, hi)
    return y.reshape(shape).astype(x.dtype), load
