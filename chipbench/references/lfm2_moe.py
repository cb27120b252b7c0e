"""Plain reference for pre-training an LFM2 expert decoder
(``model_type: lfm2_moe``, LFM2-8B-A1B), in jax.numpy float32.

Imports nothing of mxtpu and takes nothing the program made.  It owns
the weights' recipe (``weight_shapes`` / ``init_weights``), the frozen
selection bias of the routers (``selection_bias``), the loss
(``loss_sum``) and MXNet's Adam rule.  Products at a chosen precision,
RMSNorm, the gated MLP and Adam are Kimi-Linear's reference's and the
rotation GLM-4.7-Flash's (column j paired with column j + d/2), imported
unchanged.  The only blocking is over rows: attention a block of query
rows at a time (``ROW_BLOCK``) and the head's cross-entropy a block of
rows at a time (``HEAD_ROWS``); no kernel, no tile.

Layer i is ``h += Mix_i(RMSNorm(h))``, ``h += FF_i(RMSNorm(h))``, every
RMSNorm with a learned gain and ``norm_eps``.  With ``u`` the normed
input, T positions:

    layer_types[i] == "conv":
        [b | c | x] = u W_in          three chunks of C columns, in that order
        z = b * x
        y_t = sum_{j=0..L-1} w[:, j] * z_{t-(L-1)+j}      zeros before t = 0
        Mix = (c * y) W_out           no bias, no activation
    layer_types[i] == "full_attention":
        q, k, v = u W_q, u W_k, u W_v     (T, A, d), (T, G, d), (T, G, d)
        q, k <- RMSNorm over a head's d columns, a gain of d each
        q, k <- rotary at rope_theta over all d columns
        o[t, a] = softmax_{s <= t}(q[t, a] . k[s, a // (A / G)] / sqrt(d)) v
        Mix = concat_a(o) W_o
    i < num_dense_layers:  FF = W_2(silu(W_1 x) * W_3 x)
    otherwise:  s = sigmoid(x W_r^T) over all experts; the 4 largest of
        s + bias chosen; weights s[chosen] / (sum s[chosen] + 1e-6) x
        routed_scaling_factor; this share's experts run on every token
        and weighed by what the token gave them; no shared expert

and ``logits = RMSNorm(h^L) E^T`` with E the embedding.  The loss is the
next token's cross-entropy, a mean over the positions.

Departures from the published description, each also under ``assumed``
in the configuration's file: the chunks' order, the head norms, the
shared embedding and the ``1e-6`` are as the released modelling code
has them by memory; the rotation pairs column j with column j + d/2; the
selection bias is frozen at a seeded draw and takes no gradient; no
auxiliary loss, no token dropped; this share's experts only
(``num_experts`` held of ``num_experts_total``, from
``held_experts_first``), the vocabulary a slice.

``cfg["fault"]`` (never set in a configuration's file) serves the faults
that ``correct`` has to catch: ``"conv_taps_left_out"`` lets the
convolution read the current position alone, ``"gates_left_out"`` takes
the convolution of ``x`` without ``b`` and ``c``, ``"experts_left_out"``
drops the held experts' output.  ``matmul`` is as in the Kimi-Linear
reference.
"""

import math

import jax
import jax.numpy as jnp

from chipbench.references import kimi_linear as base
from chipbench.references.glm4_moe_lite import rotate

BETA1, BETA2, ADAM_EPS = base.BETA1, base.BETA2, base.ADAM_EPS
adam_rule, adam_step = base.adam_rule, base.adam_step

ROW_BLOCK = 128             # query rows of attention formed at a time
HEAD_ROWS = 1024            # rows of logits formed at a time in the loss
RENORM_EPS = 1e-6           # added to the chosen scores' sum


# ------------------------------------------------------------------ shapes

def is_expert_layer(cfg, i):
    return i >= cfg["num_dense_layers"]


def layer_types(cfg):
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def weight_shapes(cfg):
    """{name: (shape, kind)}; kind says how ``init_weights`` fills it."""
    C, V = cfg["hidden_size"], cfg["vocab_size"]
    A, G, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               head_dim(cfg))
    F, Fm = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    E, held = cfg["num_experts_total"], cfg["num_experts"]
    shapes = {"embed": ((V, C), "matrix"), "norm": ((C,), "ones")}
    for i, kind in enumerate(layer_types(cfg)):
        p = "layer%d." % i
        shapes[p + "mix_norm"] = ((C,), "ones")
        shapes[p + "ffn_norm"] = ((C,), "ones")
        if kind == "conv":
            shapes.update({
                p + "conv_in": ((3 * C, C), "matrix"),
                p + "conv_filter": ((C, cfg["conv_L_cache"]), "filter"),
                p + "conv_out": ((C, C), "matrix")})
        elif kind == "full_attention":
            shapes.update({
                p + "q": ((A * D, C), "matrix"),
                p + "k": ((G * D, C), "matrix"),
                p + "v": ((G * D, C), "matrix"),
                p + "q_norm": ((D,), "ones"), p + "k_norm": ((D,), "ones"),
                p + "out": ((C, A * D), "matrix")})
        else:
            raise ValueError("unknown layer type %r" % (kind,))
        if is_expert_layer(cfg, i):
            shapes.update({
                p + "router": ((E, C), "matrix"),
                p + "experts_gate": ((held, C, Fm), "matrix"),
                p + "experts_up": ((held, C, Fm), "matrix"),
                p + "experts_down": ((held, Fm, C), "matrix")})
        else:
            shapes.update({p + "gate": ((F, C), "matrix"),
                           p + "up": ((F, C), "matrix"),
                           p + "down": ((C, F), "matrix")})
    return shapes


def init_weights(cfg, seed, dtype=jnp.float32):
    """All weights from ``seed``, made on the device a leaf at a time and
    brought to the host: N(0, initializer_range) matrices, the embedding
    among them, the convolutions' filters N(0, conv_filter_range), unit
    gains (``assumed`` in the configuration's file says why each)."""
    std = cfg.get("initializer_range", 0.02)
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    return {name: jax.device_get(base._leaf(
                jax.random.fold_in(key, n), shape, kind,
                cfg["conv_filter_range"] if kind == "filter" else std,
                dtype))
            for n, (name, (shape, kind)) in enumerate(sorted(
                weight_shapes(cfg).items()))}


def selection_bias(cfg):
    """{layer index: (num_experts_total,) float32} for every expert
    layer: the bias added to the router's scores for the choice of
    experts only (``use_expert_bias``).  The family moves it by a rule
    outside the gradient; here it is frozen at a draw fixed by the
    configuration (``router_bias``: seed and standard deviation), zeros
    without ``use_expert_bias``."""
    spec = cfg["router_bias"]
    key = jax.random.PRNGKey(spec["seed"])
    std = spec["std"] if cfg.get("use_expert_bias", True) else 0.0
    return {i: std * jax.random.normal(
                jax.random.fold_in(key, i), (cfg["num_experts_total"],),
                jnp.float32)
            for i in range(cfg["num_hidden_layers"])
            if is_expert_layer(cfg, i)}


# ------------------------------------------------------------------ mixers

def gated_short_conv(cfg, bcx, filt):
    """(B, T, 3C) and (C, L) -> (B, T, C): ``c * conv(b * x)``."""
    fault = cfg.get("fault")
    b, c, x = jnp.split(bcx, 3, axis=-1)
    z = x if fault == "gates_left_out" else b * x
    L, T = filt.shape[-1], z.shape[1]
    if fault == "conv_taps_left_out":
        y = z * filt[:, L - 1]
    else:
        zp = jnp.pad(z, ((0, 0), (L - 1, 0), (0, 0)))
        y = sum(zp[:, j:j + T] * filt[:, j] for j in range(L))
    return y if fault == "gates_left_out" else c * y


def _conv_mixer(cfg, w, p, u, matmul):
    y = gated_short_conv(cfg, base._dense(matmul, u, w[p + "conv_in"]),
                         w[p + "conv_filter"])
    return base._dense(matmul, y, w[p + "conv_out"])


def grouped_causal_attention(matmul, q, k, v):
    """softmax(q k^T / sqrt(d)) v under a causal mask, a block of query
    rows at a time.  q (B, T, A, d); k, v (B, T, G, d): query head a
    reads key head a // (A / G).  Returns (B, T, A, d)."""
    B, T, A, D = q.shape
    G = k.shape[2]
    q = q.reshape(B, T, G, A // G, D)
    rows = math.gcd(T, ROW_BLOCK)

    @jax.checkpoint
    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, 1)
        s = base._einsum(matmul, "bqgjd,bkgd->bgjqk", qb, k) / math.sqrt(D)
        seen = (start + jnp.arange(rows))[:, None] >= jnp.arange(T)[None]
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return base._einsum(matmul, "bgjqk,bkgd->bqgjd", a, v)

    o = jax.lax.map(block, jnp.arange(0, T, rows))
    return jnp.moveaxis(o, 0, 1).reshape(B, T, A, D)


def _attention(cfg, w, p, u, matmul):
    A, G, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               head_dim(cfg))
    theta, eps = cfg["rope_theta"], cfg["norm_eps"]
    B, T, _ = u.shape
    q = base._rms_norm(base._dense(matmul, u, w[p + "q"]).reshape(
        B, T, A, D), w[p + "q_norm"], eps)
    k = base._rms_norm(base._dense(matmul, u, w[p + "k"]).reshape(
        B, T, G, D), w[p + "k_norm"], eps)
    v = base._dense(matmul, u, w[p + "v"]).reshape(B, T, G, D)
    o = grouped_causal_attention(matmul, rotate(q, theta), rotate(k, theta),
                                 v)
    return base._dense(matmul, o.reshape(B, T, A * D), w[p + "out"])


# ----------------------------------------------------------------- experts

def route(cfg, scores, bias):
    """(chosen experts (.., k), their weights (.., k)) from the sigmoid
    scores over all experts: the k largest of score + bias, weighted by
    the scores themselves, renormalised over the chosen and scaled."""
    _, chosen = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + RENORM_EPS)
    return chosen, picked * cfg["routed_scaling_factor"]


def _expert_layer(cfg, w, p, x, bias, matmul):
    first, held = cfg["held_experts_first"], cfg["num_experts"]
    if cfg.get("fault") == "experts_left_out":
        return jnp.zeros_like(x)
    scores = jax.nn.sigmoid(base._dense(matmul, x, w[p + "router"]))
    chosen, weights = route(cfg, scores, bias)
    # every held expert on every token, weighed by what the token gave it
    share = jnp.stack([jnp.sum(jnp.where(chosen == first + e, weights, 0.0),
                               -1) for e in range(held)])       # (held, B, T)
    h = jax.nn.silu(base._einsum(matmul, "btc,ecf->ebtf", x,
                                 w[p + "experts_gate"])) \
        * base._einsum(matmul, "btc,ecf->ebtf", x, w[p + "experts_up"])
    return base._einsum(matmul, "ebtf,efc->btc", h * share[..., None],
                        w[p + "experts_down"])


# ------------------------------------------------------------------- model

def hidden_of(cfg, w, tokens, matmul="highest"):
    """(B, T) int tokens -> the final norm's output (B, T, C).  Each half
    of a layer under ``jax.checkpoint``: the backward pass holds one
    half's values at a time."""
    eps = cfg["norm_eps"]
    biases = selection_bias(cfg)
    x = w["embed"][tokens]
    for i, kind in enumerate(layer_types(cfg)):
        p = "layer%d." % i

        @jax.checkpoint
        def mix(x, w, p=p, kind=kind):
            inner = _conv_mixer if kind == "conv" else _attention
            return x + inner(cfg, w, p, base._rms_norm(
                x, w[p + "mix_norm"], eps), matmul)

        @jax.checkpoint
        def ffn(x, w, p=p, i=i):
            h = base._rms_norm(x, w[p + "ffn_norm"], eps)
            if not is_expert_layer(cfg, i):
                return x + base._swiglu(matmul, h, w[p + "gate"],
                                        w[p + "up"], w[p + "down"])
            return x + _expert_layer(cfg, w, p, h, biases[i], matmul)

        part = {k: v for k, v in w.items() if k.startswith(p)}
        x = ffn(mix(x, part), part)
    return base._rms_norm(x, w["norm"], eps)


def logits_of(cfg, w, tokens, matmul="highest"):
    """(B, T) int tokens -> (B, T, V) float32 logits: the embedding is
    the head."""
    return base._dense(matmul, hidden_of(cfg, w, tokens, matmul),
                       w["embed"])


def loss_sum(cfg, w, tokens, labels, matmul="highest"):
    """Sum (not mean) of the cross-entropy over every position, the head
    with its cross-entropy over checkpointed blocks of rows.
    (``train_lm.reference_first_steps`` divides the sum over the blocks
    of rows by batch x seq.)"""
    T = tokens.shape[1]
    rows = math.gcd(T, HEAD_ROWS)
    x = hidden_of(cfg, w, tokens, matmul)

    @jax.checkpoint
    def block(start):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, rows, 1)
        logp = jax.nn.log_softmax(
            base._dense(matmul, cut(x), w["embed"]), -1)
        return -jnp.sum(jnp.take_along_axis(logp, cut(labels)[..., None],
                                            -1))

    return jax.lax.map(block, jnp.arange(0, T, rows)).sum()
