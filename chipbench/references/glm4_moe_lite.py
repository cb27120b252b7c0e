"""Plain reference for pre-training a GLM-4.7-Flash decoder
(``model_type: glm4_moe_lite``; the multi-token-prediction module is
DeepSeek-V3's, arXiv:2412.19437 section 2.2), in jax.numpy float32.

Imports nothing of mxtpu and takes nothing the program made.  It owns
the weights' recipe (``weight_shapes`` / ``init_weights``), the frozen
selection bias of the routers (``selection_bias``), the two-term loss
(``loss_sum``) and MXNet's Adam rule (``adam_step``).  Where the
mathematics is Kimi-Linear's reference's it is imported from there
unchanged (``references/kimi_linear.py``: products at a chosen
precision, RMSNorm, the gated MLP, dense causal attention over blocks of
rows, the expert layer that runs every held expert on every token, Adam).

Every half layer is ``x += inner(RMSNorm(x))``, latent attention then
the feed-forward part: a dense SwiGLU for the first
``first_k_dense_replace`` layers, the expert layer after.  Latent
attention (expanded form):

    c_q = RMSNorm(x W_qa);  q = c_q W_qb -> heads of [q_n | q_r]
    [c_kv | k_r] = x W_kva;  [k_n | v] per head = RMSNorm(c_kv) W_kvb
    q_r, k_r <- RoPE(., position);  k_r is one vector a position,
    shared by the heads;  o = softmax([q_n|q_r][k_n|k_r]^T / sqrt(dk)
    + causal) v;  y = o W_o

The prediction module, the layer after the last:

    h'_i = W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)]
    h''  = Block(h');  logits^m_i = Head(RMSNorm_m(h''_i)), target t_{i+2}

with ``h_i`` the last layer's output before the final norm and the
model's own embedding and head.  The loss is ``CE(logits, labels) +
lambda CE(logits^m[:-1], labels[1:])``, each a mean over its positions.

Departures from the published description, each also under ``assumed``
in the configuration's file:
- the rotation pairs column j with column j + d/2 (the "rotate half"
  layout), not columns 2j and 2j + 1: with seeded weights that is a
  fixed permutation of W_qb's and W_kva's rotary columns;
- the concatenation puts the embedding's half first, as the released
  code does (the paper writes the other order: a permutation of W_eh's
  rows);
- the selection bias is frozen at a seeded draw and takes no gradient;
  no auxiliary loss, no token dropped;
- this share's experts only (``n_routed_experts`` held of
  ``num_experts_total``, from ``held_experts_first``): what the absent
  experts would add is left out; the vocabulary is a slice.

``cfg["fault"]`` (never set in a configuration's file) serves the faults
that ``correct`` has to catch: ``"experts_left_out"`` drops the held
experts' output, ``"mtp_left_out"`` sets lambda to 0 (the module's
leaves then take no gradient), ``"rope_left_out"`` leaves the rotation
out.  ``matmul`` is as in the Kimi-Linear reference.
"""

import math

import jax
import jax.numpy as jnp

from chipbench.references import kimi_linear as base

BETA1, BETA2, ADAM_EPS = base.BETA1, base.BETA2, base.ADAM_EPS
adam_rule, adam_step = base.adam_rule, base.adam_step

MTP = "mtp."
HEAD_ROWS = 1024            # rows of logits formed at a time in the loss


# ------------------------------------------------------------------ shapes

def is_expert_layer(cfg, i):
    return i >= cfg["first_k_dense_replace"]


def _attention_shapes(cfg, p):
    C, A = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    qr, rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return {p + "mix_norm": ((C,), "ones"),
            p + "q_a": ((qr, C), "matrix"), p + "q_norm": ((qr,), "ones"),
            p + "q_b": ((A * (dn + dr), qr), "matrix"),
            p + "dkv": ((rank + dr, C), "matrix"),
            p + "kv_norm": ((rank,), "ones"),
            p + "ukv": ((A * (dn + dv), rank), "matrix"),
            p + "out": ((C, A * dv), "matrix")}


def _expert_shapes(cfg, p):
    C, Fm = cfg["hidden_size"], cfg["moe_intermediate_size"]
    E, held = cfg["num_experts_total"], cfg["n_routed_experts"]
    Fs = Fm * cfg["n_shared_experts"]
    return {p + "ffn_norm": ((C,), "ones"),
            p + "router": ((E, C), "matrix"),
            p + "experts_gate": ((held, C, Fm), "matrix"),
            p + "experts_up": ((held, C, Fm), "matrix"),
            p + "experts_down": ((held, Fm, C), "matrix"),
            p + "shared_gate": ((Fs, C), "matrix"),
            p + "shared_up": ((Fs, C), "matrix"),
            p + "shared_down": ((C, Fs), "matrix")}


def weight_shapes(cfg):
    """{name: (shape, kind)}; kind says how ``init_weights`` fills it."""
    C, V, F = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    shapes = {"embed": ((V, C), "matrix"), "norm": ((C,), "ones"),
              "lm_head": ((V, C), "matrix")}
    for i in range(cfg["num_hidden_layers"]):
        p = "layer%d." % i
        shapes.update(_attention_shapes(cfg, p))
        if is_expert_layer(cfg, i):
            shapes.update(_expert_shapes(cfg, p))
        else:
            shapes.update({p + "ffn_norm": ((C,), "ones"),
                           p + "gate": ((F, C), "matrix"),
                           p + "up": ((F, C), "matrix"),
                           p + "down": ((C, F), "matrix")})
    if cfg["num_nextn_predict_layers"]:
        shapes.update({MTP + "enorm": ((C,), "ones"),
                       MTP + "hnorm": ((C,), "ones"),
                       MTP + "eh_proj": ((C, 2 * C), "matrix"),
                       MTP + "norm": ((C,), "ones")})
        shapes.update(_attention_shapes(cfg, MTP))
        shapes.update(_expert_shapes(cfg, MTP))
    return shapes


def init_weights(cfg, seed, dtype=jnp.float32):
    """All weights from ``seed``, made on the device a leaf at a time and
    brought to the host (as the Kimi-Linear reference makes its own):
    N(0, initializer_range) matrices, the embedding N(0,
    embedding_range) (``assumed`` says why it is wider), unit norm
    gains."""
    std = cfg.get("initializer_range", 0.02)
    stds = {"embed": cfg.get("embedding_range", std)}
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    return {name: jax.device_get(base._leaf(
                jax.random.fold_in(key, n), shape, kind,
                stds.get(name, std), dtype))
            for n, (name, (shape, kind)) in enumerate(sorted(
                weight_shapes(cfg).items()))}


def selection_bias(cfg):
    """{layer index: (num_experts_total,) float32} for every expert
    layer, the prediction module's under index ``num_hidden_layers`` (it
    is the layer after the last): the bias added to the router's scores
    for the choice of experts only.  The family trains it by a rule
    outside the gradient (``topk_method: noaux_tc``,
    ``e_score_correction_bias``); here it is frozen at a draw fixed by
    the configuration (``router_bias``: seed and standard deviation)."""
    spec = cfg["router_bias"]
    key = jax.random.PRNGKey(spec["seed"])
    layers = [i for i in range(cfg["num_hidden_layers"])
              if is_expert_layer(cfg, i)]
    if cfg["num_nextn_predict_layers"]:
        layers.append(cfg["num_hidden_layers"])
    return {i: spec["std"] * jax.random.normal(
                jax.random.fold_in(key, i), (cfg["num_experts_total"],),
                jnp.float32) for i in layers}


# --------------------------------------------------------------- attention

def rotate(x, theta):
    """Rotary positions over the last axis of x (B, T, .., d), position
    t on axis 1: column j < d/2 pairs with column j + d/2 and the pair
    turns by t * theta^(-2j/d)."""
    T, half = x.shape[1], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * freq     # (T, d/2)
    angle = angle.reshape((1, T) + (1,) * (x.ndim - 3) + (half,))
    sin, cos = jnp.sin(angle), jnp.cos(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _attention(cfg, w, p, x, matmul):
    A = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    B, T, _ = x.shape
    cq = base._rms_norm(base._dense(matmul, x, w[p + "q_a"]),
                        w[p + "q_norm"], eps)
    q = base._dense(matmul, cq, w[p + "q_b"]).reshape(B, T, A, dn + dr)
    ckv = base._dense(matmul, x, w[p + "dkv"])
    c, k_shared = ckv[..., :rank], ckv[..., rank:]
    kv = base._dense(matmul, base._rms_norm(c, w[p + "kv_norm"], eps),
                     w[p + "ukv"]).reshape(B, T, A, dn + dv)
    q_rot = q[..., dn:]
    if cfg.get("fault") != "rope_left_out":
        q_rot = rotate(q_rot, cfg["rope_theta"])
        k_shared = rotate(k_shared, cfg["rope_theta"])
    q = jnp.concatenate([q[..., :dn], q_rot], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_shared[:, :, None, :], (B, T, A, dr))], -1)
    o = base.causal_attention(matmul, q, k, kv[..., dn:])
    return base._dense(matmul, o.reshape(B, T, A * dv), w[p + "out"])


def _as_kimi_linear(cfg):
    """The keys the imported expert layer reads, under its names."""
    return {"num_experts_per_token": cfg["num_experts_per_tok"],
            "moe_renormalize": cfg["norm_topk_prob"],
            "routed_scaling_factor": cfg["routed_scaling_factor"],
            "held_experts_first": cfg["held_experts_first"],
            "num_experts": cfg["n_routed_experts"],
            "fault": cfg.get("fault")}


# ------------------------------------------------------------------- model

def _part(w, p):
    return {k: v for k, v in w.items() if k.startswith(p)}


def _decoder_layer(cfg, w, p, x, bias, matmul):
    """One layer, each half under ``jax.checkpoint``: the backward pass
    holds one half's values at a time.  ``bias`` None: a dense layer."""
    eps = cfg["rms_norm_eps"]

    @jax.checkpoint
    def mix(x, w):
        return x + _attention(cfg, w, p, base._rms_norm(
            x, w[p + "mix_norm"], eps), matmul)

    @jax.checkpoint
    def ffn(x, w):
        h = base._rms_norm(x, w[p + "ffn_norm"], eps)
        if bias is None:
            return x + base._swiglu(matmul, h, w[p + "gate"], w[p + "up"],
                                    w[p + "down"])
        return x + base._expert_layer(_as_kimi_linear(cfg), w, p, h, bias,
                                      matmul)

    w = _part(w, p)
    return ffn(mix(x, w), w)


def hidden_of(cfg, w, tokens, matmul="highest"):
    """(B, T) int tokens -> the last layer's output (B, T, C), before the
    final norm."""
    biases = selection_bias(cfg)
    x = w["embed"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = _decoder_layer(cfg, w, "layer%d." % i, x, biases.get(i), matmul)
    return x


def mtp_hidden_of(cfg, w, tokens, hidden, matmul="highest"):
    """The prediction module's output before its final norm.  Position i
    is fed the embedding of token i + 1; the last position, which has
    none, is fed id 0 and is in no loss."""
    eps = cfg["rms_norm_eps"]
    following = jnp.concatenate([tokens[:, 1:],
                                 jnp.zeros_like(tokens[:, :1])], 1)

    @jax.checkpoint
    def combine(e, h, w):
        both = jnp.concatenate([base._rms_norm(e, w[MTP + "enorm"], eps),
                                base._rms_norm(h, w[MTP + "hnorm"], eps)],
                               -1)         # the embedding's half first
        return base._dense(matmul, both, w[MTP + "eh_proj"])

    x = combine(w["embed"][following], hidden, _part(w, MTP))
    return _decoder_layer(cfg, w, MTP, x,
                          selection_bias(cfg)[cfg["num_hidden_layers"]],
                          matmul)


def _head(cfg, w, x, norm, matmul):
    return base._dense(matmul, base._rms_norm(x, w[norm],
                                              cfg["rms_norm_eps"]),
                       w["lm_head"])


def logits_of(cfg, w, tokens, matmul="highest"):
    """(B, T) int tokens -> (logits, the module's logits), each
    (B, T, V) float32 (the second None without the module)."""
    hidden = hidden_of(cfg, w, tokens, matmul)
    logits = _head(cfg, w, hidden, "norm", matmul)
    if not cfg["num_nextn_predict_layers"]:
        return logits, None
    return logits, _head(cfg, w, mtp_hidden_of(cfg, w, tokens, hidden,
                                               matmul), MTP + "norm", matmul)


def mtp_weight(cfg):
    return 0.0 if cfg.get("fault") == "mtp_left_out" else cfg["mtp_weight"]


def loss_terms(cfg, w, tokens, labels, matmul="highest"):
    """(sum of the main cross-entropy over all B T positions, sum of the
    module's over its B (T - 1)): ``labels[:, i]`` is token i + 1, so the
    module's target at position i is ``labels[:, i + 1]``.  Each head
    with its cross-entropy is taken over checkpointed blocks of rows:
    a block's logits are alive at a time, not two heads' (8192 x 19360
    float32 each) beside the weights, gradients and Adam state."""

    T = tokens.shape[1]
    rows = math.gcd(T, HEAD_ROWS)

    def head_loss(x, norm, head, labels, counted):
        @jax.checkpoint
        def block(start):
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, rows, 1)
            logp = jax.nn.log_softmax(_head(
                cfg, {"norm": norm, "lm_head": head}, cut(x), "norm",
                matmul), -1)
            picked = jnp.take_along_axis(logp, cut(labels)[..., None],
                                         -1)[..., 0]
            return -jnp.sum(jnp.where(cut(counted[None]), picked, 0.0))

        return jax.lax.map(block, jnp.arange(0, T, rows)).sum()

    hidden = hidden_of(cfg, w, tokens, matmul)
    main = head_loss(hidden, w["norm"], w["lm_head"], labels,
                     jnp.ones((T,), bool))
    if not cfg["num_nextn_predict_layers"]:
        return main, 0.0
    mtp_hidden = mtp_hidden_of(cfg, w, tokens, hidden, matmul)
    # position i's target is labels[:, i + 1]; the last position has
    # none (a filler stands there) and is not counted
    ahead = jnp.concatenate([labels[:, 1:], labels[:, :1]], 1)
    return main, head_loss(mtp_hidden, w[MTP + "norm"], w["lm_head"], ahead,
                           jnp.arange(T) < T - 1)


def loss_sum(cfg, w, tokens, labels, matmul="highest"):
    """B T times the two-term loss of these rows: the main term's sum
    plus lambda times the module's sum scaled from its T - 1 positions
    to T.  (``train_lm.reference_first_steps`` divides the sum over the
    blocks of rows by batch x seq, which then gives main mean + lambda x
    the module's mean.)"""
    T = tokens.shape[1]
    main, mtp = loss_terms(cfg, w, tokens, labels, matmul)
    return main + mtp_weight(cfg) * mtp * (T / (T - 1.0))
