"""Plain reference for BERT pre-training (Devlin et al. 2018, post-LN
encoder, MLM cross-entropy over every position), in jax.numpy float32.

Imports nothing of mxtpu and takes nothing the program made.  It owns
the weights' recipe (``weight_shapes`` / ``init_weights``: the paper's
N(0, 0.02) matrices and embeddings, zero biases, unit LayerNorm gains),
the loss (``loss_sum``), and the published Adam rule as MXNet states it
(``adam_step``: bias correction folded into the rate, epsilon outside
it).  Departures from the paper, shared with the program so that the two
compute one function: no segment embedding is added (one segment), no
pooler or NSP head enters the loss, the MLM head is one untied
projection with a bias, GELU is the tanh form, LayerNorm eps 1e-5.

``matmul`` chooses how a product is formed: "highest" is float32 at
full precision (the reference); "bf16x3" forms it from three bfloat16
passes (what XLA calls ``high``), "bf16" from one — the lower-precision
controls of the benchmark's ``correct``.
"""

import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
BETA1, BETA2, ADAM_EPS = 0.9, 0.999, 1e-8


def weight_shapes(cfg):
    """{name: (shape, kind)}; kind is "matrix", "zeros" or "ones"."""
    C, F = cfg["hidden_size"], cfg["intermediate_size"]
    V, P = cfg["vocab_size"], cfg["max_position_embeddings"]
    shapes = {
        "word_embed": ((V, C), "matrix"),
        "position_embed": ((P, C), "matrix"),
        "embed_ln.gamma": ((C,), "ones"),
        "embed_ln.beta": ((C,), "zeros"),
        "mlm.weight": ((V, C), "matrix"),
        "mlm.bias": ((V,), "zeros"),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = "layer%d." % i
        shapes.update({
            p + "qkv.weight": ((3 * C, C), "matrix"),
            p + "qkv.bias": ((3 * C,), "zeros"),
            p + "out.weight": ((C, C), "matrix"),
            p + "out.bias": ((C,), "zeros"),
            p + "ln1.gamma": ((C,), "ones"),
            p + "ln1.beta": ((C,), "zeros"),
            p + "ffn1.weight": ((F, C), "matrix"),
            p + "ffn1.bias": ((F,), "zeros"),
            p + "ffn2.weight": ((C, F), "matrix"),
            p + "ffn2.bias": ((C,), "zeros"),
            p + "ln2.gamma": ((C,), "ones"),
            p + "ln2.beta": ((C,), "zeros"),
        })
    return shapes


def init_weights(cfg, seed, dtype=jnp.float32):
    """All weights in one jitted call on the device, from ``seed``."""
    shapes = weight_shapes(cfg)
    std = cfg.get("initializer_range", 0.02)

    @jax.jit
    def make(key):
        out = {}
        for n, (name, (shape, kind)) in enumerate(sorted(shapes.items())):
            if kind == "matrix":
                out[name] = (std * jax.random.normal(
                    jax.random.fold_in(key, n), shape, jnp.float32)
                ).astype(dtype)
            else:
                out[name] = jnp.full(shape, float(kind == "ones"), dtype)
        return out

    return make(jax.random.PRNGKey(seed % (2 ** 31 - 1)))


# ------------------------------------------------------------------ matmul

def _split(x):
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _einsum(matmul, spec, a, b):
    if matmul == "highest":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    dot = lambda x, y: jnp.einsum(spec, x, y,
                                  preferred_element_type=jnp.float32)
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    if matmul == "bf16":
        return dot(a_hi, b_hi)
    if matmul == "bf16x3":
        return dot(a_hi, b_hi) + (dot(a_hi, b_lo) + dot(a_lo, b_hi))
    raise ValueError("unknown matmul %r" % (matmul,))


def _layer_norm(x, gamma, beta):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * gamma + beta


def _dense(matmul, x, w, b):
    return _einsum(matmul, "btc,fc->btf", x, w) + b


def logits_of(cfg, w, tokens, matmul="highest"):
    """(B, T) int tokens -> (B, T, V) float32 MLM logits."""
    B, T = tokens.shape
    H = cfg["num_attention_heads"]
    C = cfg["hidden_size"]
    D = C // H
    x = w["word_embed"][tokens] + w["position_embed"][None, :T]
    x = _layer_norm(x, w["embed_ln.gamma"], w["embed_ln.beta"])
    for i in range(cfg["num_hidden_layers"]):
        p = "layer%d." % i
        qkv = _dense(matmul, x, w[p + "qkv.weight"], w[p + "qkv.bias"])
        q, k, v = (qkv[:, :, j * C:(j + 1) * C].reshape(B, T, H, D)
                   for j in range(3))
        s = _einsum(matmul, "bqhd,bkhd->bhqk", q, k) / math.sqrt(D)
        a = jax.nn.softmax(s, axis=-1)
        o = _einsum(matmul, "bhqk,bkhd->bqhd", a, v).reshape(B, T, C)
        o = _dense(matmul, o, w[p + "out.weight"], w[p + "out.bias"])
        x = _layer_norm(x + o, w[p + "ln1.gamma"], w[p + "ln1.beta"])
        h = _dense(matmul, x, w[p + "ffn1.weight"], w[p + "ffn1.bias"])
        h = jax.nn.gelu(h, approximate=True)
        h = _dense(matmul, h, w[p + "ffn2.weight"], w[p + "ffn2.bias"])
        x = _layer_norm(x + h, w[p + "ln2.gamma"], w[p + "ln2.beta"])
    return _dense(matmul, x, w["mlm.weight"], w["mlm.bias"])


def loss_sum(cfg, w, tokens, labels, matmul="highest"):
    """Sum (not mean) of the cross-entropy over every position, so that
    blocks of rows add up."""
    logp = jax.nn.log_softmax(logits_of(cfg, w, tokens, matmul), axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return -picked.sum()


@jax.jit
def _adam_update(w, grads, mean, var, rate):
    mean = {k: BETA1 * mean[k] + (1 - BETA1) * grads[k] for k in w}
    var = {k: BETA2 * var[k] + (1 - BETA2) * jnp.square(grads[k])
           for k in w}
    new = {k: w[k] - rate * mean[k] / (jnp.sqrt(var[k]) + ADAM_EPS)
           for k in w}
    return new, mean, var


def adam_step(w, grads, state, lr, t):
    """MXNet's Adam at step ``t`` (from 1): bias correction folded into
    the rate, epsilon outside it.  Returns (weights, (mean, var))."""
    rate = lr * math.sqrt(1.0 - BETA2 ** t) / (1.0 - BETA1 ** t)
    new, mean, var = _adam_update(w, grads, state[0], state[1],
                                  jnp.float32(rate))
    return new, (mean, var)
