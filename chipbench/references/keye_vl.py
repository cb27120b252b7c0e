"""Plain reference for pre-training Keye-VL-2.0-30B-A3B's language model
(``model_type: KeyeVL2``) in the sparse stage of its attention's indexer
(DeepSeek Sparse Attention: the DeepSeek-V3.2-Exp report's lightning
indexer), in jax.numpy float32.

Imports nothing of mxtpu and takes nothing the program made.  It owns
the weights' recipe (``weight_shapes`` / ``init_weights``), the two-term
loss (``loss_sum``) and MXNet's Adam rule.  Products at a chosen
precision, RMSNorm and Adam are Kimi-Linear's reference's, imported
unchanged.  The only blocking is over rows: attention, with the
indexer's scores, selection and loss, a block of query rows at a time
(``ROW_BLOCK``), and the head's cross-entropy a block of rows at a time
(``HEAD_ROWS``); no kernel, no tile.

Every layer is ``x += Attn(RMSNorm(x))``, ``x += Experts(RMSNorm(x))``.
With ``h = RMSNorm(x)``, ``hbar = stop_gradient(h)``, T positions, causal:

    q, k, v = h W_q, h W_k, h W_v   (T, 32, 128), (T, 4, 128), (T, 4, 128)
    q, k <- RMSNorm over the 128 columns, a learned gain each
    q, k <- rotary at theta over all 128 columns: of the 64 frequencies
            the first 16 turn by position stream 0, the next 24 by
            stream 1, the last 24 by stream 2 (``mrope_section``)
    qI = hbar W_qI (T, 16, 64);  kI = LayerNorm(hbar W_kI) (T, 64)
    wI = hbar W_w / sqrt(16) / sqrt(64) (T, 16)
    qI, kI <- rotary on their 64 columns by stream 0
    I[t, s] = sum_j wI[t, j] relu(qI[t, j] . kI[s])              s <= t
    tau[t] = the topk-th largest of I[t, 0..t]   (-inf where t < topk)
    S_t = {s <= t : I[t, s] >= tau[t]}           (ties are all kept)
    o[t, a] = sum_{s in S_t} softmax_{S_t}(q[t, a] . k[s, a // 8]
              / sqrt(128)) v[s, a // 8];    y = concat_a(o) W_o
    pbar[t, s] = stop_gradient(mean_a p[t, a, s])
    L_I = (1 / T) sum_t KL(pbar[t, S_t] || softmax_{S_t} I[t, .])

No gradient passes through the selection.  The experts: ``softmax(h
W_r)`` over all experts in float32, the 8 largest, renormalised to sum
1, this share's experts run on every token and weighed by what the
token gave them.  The loss is the next token's cross-entropy, a mean
over the positions, plus ``L_I`` summed over the layers.

Departures from the published description, each also under ``assumed``
in the configuration's file: the head norms, the rotation's pairing
(column j with column j + d/2) and its sections in blocks, the indexer's
form (the released DeepSeek-V3.2 indexer's, without its Hadamard
rotation and FP8), ties kept, the indexer's loss and its weight 1, no
auxiliary loss and no dropped token; this share's experts only
(``num_experts`` held of ``num_experts_total``), the vocabulary a slice.

``cfg["fault"]`` (never set in a configuration's file) serves the faults
that ``correct`` has to catch: ``"selection_left_out"`` keeps every
causal key, ``"indexer_loss_left_out"`` drops ``L_I`` (the indexer's
leaves then take no gradient), ``"experts_left_out"`` drops the held
experts' output.  ``matmul`` is as in the Kimi-Linear reference.
"""

import math

import jax
import jax.numpy as jnp

from chipbench.references import kimi_linear as base

BETA1, BETA2, ADAM_EPS = base.BETA1, base.BETA2, base.ADAM_EPS
adam_rule, adam_step = base.adam_rule, base.adam_step

ROW_BLOCK = 128             # query rows of attention formed at a time
HEAD_ROWS = 1024            # rows of logits formed at a time in the loss
INDEX_NORM_EPS = 1e-6       # the LayerNorm on the indexer's key
RESIDUAL_PROJECTIONS = ("out", "experts_down")  # what a layer adds to x by


# ------------------------------------------------------------------ shapes

def weight_shapes(cfg):
    """{name: (shape, kind)}; kind says how ``init_weights`` fills it."""
    C, V = cfg["hidden_size"], cfg["vocab_size"]
    A, G, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    sa = cfg["sa_config"]
    Hi, d = sa["indexer_num_heads"], sa["indexer_head_dim"]
    E, held, F = (cfg["num_experts_total"], cfg["num_experts"],
                  cfg["moe_intermediate_size"])
    shapes = {"embed": ((V, C), "matrix"), "norm": ((C,), "ones"),
              "lm_head": ((V, C), "matrix")}
    for i in range(cfg["num_hidden_layers"]):
        p = "layer%d." % i
        shapes.update({
            p + "mix_norm": ((C,), "ones"),
            p + "q": ((A * D, C), "matrix"), p + "k": ((G * D, C), "matrix"),
            p + "v": ((G * D, C), "matrix"), p + "q_norm": ((D,), "ones"),
            p + "k_norm": ((D,), "ones"), p + "out": ((C, A * D), "matrix"),
            p + "index_q": ((Hi * d, C), "matrix"),
            p + "index_k": ((d, C), "matrix"),
            p + "index_k_gain": ((d,), "ones"),
            p + "index_k_bias": ((d,), "zeros"),
            p + "index_w": ((Hi, C), "matrix"),
            p + "ffn_norm": ((C,), "ones"),
            p + "router": ((E, C), "matrix"),
            p + "experts_gate": ((held, C, F), "matrix"),
            p + "experts_up": ((held, C, F), "matrix"),
            p + "experts_down": ((held, F, C), "matrix")})
    return shapes


def init_weights(cfg, seed, dtype=jnp.float32):
    """All weights from ``seed``, made on the device a leaf at a time and
    brought to the host: N(0, initializer_range) matrices, the embedding
    N(0, embedding_range), the two projections that write into the
    residual stream N(0, residual_projection_range), unit gains, zero
    bias (``assumed`` in the configuration's file says why each)."""
    std = cfg.get("initializer_range", 0.02)
    stds = {"embed": cfg.get("embedding_range", std)}
    for i in range(cfg["num_hidden_layers"]):
        for leaf in RESIDUAL_PROJECTIONS:
            stds["layer%d.%s" % (i, leaf)] = cfg.get(
                "residual_projection_range", std)
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    return {name: jax.device_get(base._leaf(
                jax.random.fold_in(key, n), shape, kind,
                stds.get(name, std), dtype))
            for n, (name, (shape, kind)) in enumerate(sorted(
                weight_shapes(cfg).items()))}


def text_positions(T):
    """(3, T): on text the three position streams are the token's index."""
    return jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (3, T))


# --------------------------------------------------------------- attention

def rotate(x, positions, theta, sections=None):
    """Rotary positions over the last axis of x (B, T, .., d), column
    j < d/2 paired with column j + d/2.  ``positions`` (streams, T):
    frequency f turns by the stream its section names (``sections``, in
    blocks from the first; one section of all d/2 frequencies, stream 0,
    when None)."""
    half = x.shape[-1] // 2
    sections = [half] if sections is None else sections
    assert sum(sections) == half, (sections, half)
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    stream = jnp.repeat(jnp.arange(len(sections)), jnp.array(sections),
                        total_repeat_length=half)
    angle = positions.astype(jnp.float32)[stream].T * freq      # (T, d/2)
    angle = angle.reshape((1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,))
    sin, cos = jnp.sin(angle), jnp.cos(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gain + bias


def indexed_attention(cfg, q, k, v, q_idx, k_idx, w_idx, matmul):
    """(o (B, T, A, D), the indexer's loss summed over the rows (B,), the
    pairs kept (B,)), a block of query rows at a time.  q (B, T, A, D);
    k, v (B, T, G, D); q_idx (B, T, Hi, d); k_idx (B, T, d); w_idx
    (B, T, Hi)."""
    B, T, A, D = q.shape
    group = A // k.shape[2]
    topk = cfg["sa_config"]["topk"]
    rows = math.gcd(T, ROW_BLOCK)
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    everything = cfg.get("fault") == "selection_left_out" or T <= topk

    @jax.checkpoint
    def block(start):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, rows, 1)
        at = start + jnp.arange(rows)
        seen = at[:, None] >= jnp.arange(T)[None]                # (R, T)
        z = base._einsum(matmul, "bqjd,bkd->bqjk", cut(q_idx), k_idx)
        index = jnp.sum(cut(w_idx)[..., None] * jax.nn.relu(z), axis=2)
        fixed = jax.lax.stop_gradient(jnp.where(seen, index, -jnp.inf))
        if everything:
            kept = jnp.broadcast_to(seen, fixed.shape)
        else:
            least = jax.lax.top_k(fixed, topk)[0][..., -1]       # (B, R)
            least = jnp.where(at >= topk, least, -jnp.inf)
            kept = seen & (fixed >= least[..., None])
        s = base._einsum(matmul, "bqhd,bkhd->bhqk", cut(q), k) / math.sqrt(D)
        a = jax.nn.softmax(jnp.where(kept[:, None], s, -jnp.inf), axis=-1)
        o = base._einsum(matmul, "bhqk,bkhd->bqhd", a, v)
        pbar = jax.lax.stop_gradient(jnp.mean(a, axis=1))        # (B, R, T)
        log_index = jax.nn.log_softmax(jnp.where(kept, index, -jnp.inf), -1)
        some = kept & (pbar > 0.0)
        kl = jnp.where(some, pbar * (jnp.log(jnp.where(some, pbar, 1.0))
                                     - jnp.where(some, log_index, 0.0)), 0.0)
        return o, jnp.sum(kl, (1, 2)), jnp.sum(kept, (1, 2), jnp.float32)

    o, kl, kept = jax.lax.map(block, jnp.arange(0, T, rows))
    return (jnp.moveaxis(o, 0, 1).reshape(B, T, A, D), kl.sum(0),
            kept.sum(0))


def _attention(cfg, w, p, h, positions, matmul):
    """(y, the indexer's loss of this layer: a mean over the positions,
    (B,); the pairs kept (B,))."""
    A, G, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    sa = cfg["sa_config"]
    Hi, d = sa["indexer_num_heads"], sa["indexer_head_dim"]
    theta, eps = cfg["rope_theta"], cfg["rms_norm_eps"]
    sections = cfg["rope_scaling"]["mrope_section"]
    B, T, _ = h.shape
    q = base._rms_norm(base._dense(matmul, h, w[p + "q"]).reshape(
        B, T, A, D), w[p + "q_norm"], eps)
    k = base._rms_norm(base._dense(matmul, h, w[p + "k"]).reshape(
        B, T, G, D), w[p + "k_norm"], eps)
    v = base._dense(matmul, h, w[p + "v"]).reshape(B, T, G, D)
    q, k = (rotate(a, positions, theta, sections) for a in (q, k))
    hbar = jax.lax.stop_gradient(h)
    q_idx = rotate(base._dense(matmul, hbar, w[p + "index_q"]).reshape(
        B, T, Hi, d), positions[:1], theta)
    k_idx = rotate(_layer_norm(base._dense(matmul, hbar, w[p + "index_k"]),
                               w[p + "index_k_gain"], w[p + "index_k_bias"],
                               INDEX_NORM_EPS), positions[:1], theta)
    w_idx = base._dense(matmul, hbar, w[p + "index_w"]) \
        * (1.0 / math.sqrt(Hi * d))
    o, kl, kept = indexed_attention(cfg, q, k, v, q_idx, k_idx, w_idx,
                                    matmul)
    return base._dense(matmul, o.reshape(B, T, A * D), w[p + "out"]), \
        kl / T, kept


# ----------------------------------------------------------------- experts

def _expert_layer(cfg, w, p, x, matmul):
    first, held = cfg["held_experts_first"], cfg["num_experts"]
    if cfg.get("fault") == "experts_left_out":
        return jnp.zeros_like(x)
    scores = jax.nn.softmax(base._dense(matmul, x, w[p + "router"]), -1)
    weights, chosen = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    # every held expert on every token, weighed by what the token gave it
    share = jnp.stack([jnp.sum(jnp.where(chosen == first + e, weights, 0.0),
                               -1) for e in range(held)])       # (held, B, T)
    h = jax.nn.silu(base._einsum(matmul, "btc,ecf->ebtf", x,
                                 w[p + "experts_gate"])) \
        * base._einsum(matmul, "btc,ecf->ebtf", x, w[p + "experts_up"])
    return base._einsum(matmul, "ebtf,efc->btc", h * share[..., None],
                        w[p + "experts_down"])


# ------------------------------------------------------------------- model

def hidden_of(cfg, w, tokens, positions=None, matmul="highest"):
    """(B, T) int tokens -> (the final norm's output (B, T, C), the
    indexers' loss summed over the layers (B,), the pairs kept summed
    over the layers (B,)).  Each half of a layer under
    ``jax.checkpoint``."""
    eps = cfg["rms_norm_eps"]
    T = tokens.shape[1]
    positions = text_positions(T) if positions is None else positions
    x = w["embed"][tokens]
    index_loss = kept = 0.0
    for i in range(cfg["num_hidden_layers"]):
        p = "layer%d." % i

        @jax.checkpoint
        def mix(x, w, p=p):
            y, kl, n = _attention(cfg, w, p, base._rms_norm(
                x, w[p + "mix_norm"], eps), positions, matmul)
            return x + y, kl, n

        @jax.checkpoint
        def ffn(x, w, p=p):
            return x + _expert_layer(cfg, w, p, base._rms_norm(
                x, w[p + "ffn_norm"], eps), matmul)

        part = {k: v for k, v in w.items() if k.startswith(p)}
        x, kl, n = mix(x, part)
        x = ffn(x, part)
        index_loss, kept = index_loss + kl, kept + n
    return base._rms_norm(x, w["norm"], eps), index_loss, kept


def logits_of(cfg, w, tokens, positions=None, matmul="highest"):
    """(logits (B, T, V), the indexers' loss (B,), the pairs kept (B,))."""
    x, index_loss, kept = hidden_of(cfg, w, tokens, positions, matmul)
    return base._dense(matmul, x, w["lm_head"]), index_loss, kept


def index_weight(cfg):
    return 0.0 if cfg.get("fault") == "indexer_loss_left_out" \
        else cfg["index_loss_weight"]


def loss_terms(cfg, w, tokens, labels, positions=None, matmul="highest"):
    """(the cross-entropy summed over all B T positions, the indexers'
    loss summed over the sequences): the head with its cross-entropy over
    checkpointed blocks of rows."""
    T = tokens.shape[1]
    rows = math.gcd(T, HEAD_ROWS)
    x, index_loss, _ = hidden_of(cfg, w, tokens, positions, matmul)

    @jax.checkpoint
    def block(start):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, rows, 1)
        logp = jax.nn.log_softmax(
            base._dense(matmul, cut(x), w["lm_head"]), -1)
        return -jnp.sum(jnp.take_along_axis(logp, cut(labels)[..., None],
                                            -1))

    return jax.lax.map(block, jnp.arange(0, T, rows)).sum(), \
        jnp.sum(index_loss)


def loss_sum(cfg, w, tokens, labels, matmul="highest"):
    """B T times the two-term loss of these rows (text positions): the
    cross-entropy's sum plus T times the weighted sum of the sequences'
    indexer losses.  (``train_lm.reference_first_steps`` divides the sum
    over the blocks of rows by batch x seq.)"""
    main, index_loss = loss_terms(cfg, w, tokens, labels, None, matmul)
    return main + index_weight(cfg) * tokens.shape[1] * index_loss
