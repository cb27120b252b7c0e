"""Plain reference for a Mistral/Llama-style decoder (pre-RMSNorm,
grouped-query attention with rotary embedding in the rotate-half layout,
gated SiLU MLP, untied head), in jax.numpy float32, full causal forward:
no cache, no paging, no batching of requests.

Imports nothing of mxtpu and takes nothing the program made.  It owns the
weights' recipe: every matrix N(0, initializer_range) from the seed,
rounded to the type the model is served in (the numbers the server holds
are then exactly the numbers here), norm gains one.  ``init_leaves``
makes a few leaves at a time on the device (one compiled program for
every layer), the same numbers whoever asks: the program is loaded
layer by layer, and the reference runs layer by layer and never holds
the model whole.

``matmul``: "highest" is float32 at full precision (the reference);
"int8" rounds both operands of every product to 8 bits with one scale
per row (W8A8) — the lower-precision control of ``correct``.
"""

import math

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("attn_norm", "qkv", "out", "ffn_norm", "gate", "up", "down")


def _dims(cfg):
    C, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return C, H, cfg["num_key_value_heads"], cfg.get("head_dim", C // H)


def weight_shapes(cfg):
    C, H, KV, D = _dims(cfg)
    F, V = cfg["intermediate_size"], cfg["vocab_size"]
    shapes = {"embed": (V, C), "norm": (C,), "lm_head": (V, C)}
    for i in range(cfg["num_hidden_layers"]):
        p = "layer%d." % i
        shapes.update({
            p + "attn_norm": (C,), p + "qkv": ((H + 2 * KV) * D, C),
            p + "out": (C, H * D), p + "ffn_norm": (C,),
            p + "gate": (F, C), p + "up": (F, C), p + "down": (C, F)})
    return shapes


_MAKERS = {}


def _make(cfg, names, dtype, seed):
    """The leaves ``names``: matrices N(0, initializer_range) from the
    seed folded with the leaf's place among all the model's names (so a
    leaf has the same numbers however few are made with it), rounded to
    ``dtype``; gains one.  One compiled program per set of shapes: the
    places are arguments, so every layer shares one."""
    shapes = weight_shapes(cfg)
    order = {name: n for n, name in enumerate(sorted(shapes))}
    std = cfg.get("initializer_range", 0.02)
    leaf_shapes = tuple(shapes[name] for name in names)
    made = _MAKERS.get((leaf_shapes, str(dtype), std))
    if made is None:
        def make(key, places):
            out = []
            for n, shape in enumerate(leaf_shapes):
                if len(shape) == 1:
                    out.append(jnp.ones(shape, dtype))
                else:
                    out.append((std * jax.random.normal(
                        jax.random.fold_in(key, places[n]), shape,
                        jnp.float32)).astype(dtype))
            return out

        made = _MAKERS[(leaf_shapes, str(dtype), std)] = jax.jit(make)
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    places = jnp.asarray([order[name] for name in names], jnp.uint32)
    return dict(zip(names, made(key, places)))


def init_leaves(cfg, seed, names, dtype):
    """The same numbers for ``names`` alone."""
    return _make(cfg, list(names), dtype, seed)


# ------------------------------------------------------------------ matmul

def _quant8(x):
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def _dense(matmul, x, w):
    """x (..., C) times w (F, C) transposed."""
    if matmul == "int8":
        x, w = _quant8(x), _quant8(w)
    elif matmul != "highest":
        raise ValueError("unknown matmul %r" % (matmul,))
    return jnp.einsum("...c,fc->...f", x, w,
                      precision=jax.lax.Precision.HIGHEST)


def _rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, theta):
    """x (T, heads, D): rotate-half pairs (x[:D/2], x[D/2:]), position t."""
    T, _, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    sin, cos = jnp.sin(angle)[:, None, :], jnp.cos(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def layer(cfg, w, x, matmul="highest"):
    """One decoder layer over one sequence x (T, C), causal."""
    C, H, KV, D = _dims(cfg)
    T = x.shape[0]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = _rms_norm(x, w["attn_norm"], eps)
    qkv = _dense(matmul, h, w["qkv"])
    q = _rope(qkv[:, :H * D].reshape(T, H, D), theta)
    k = _rope(qkv[:, H * D:(H + KV) * D].reshape(T, KV, D), theta)
    v = qkv[:, (H + KV) * D:].reshape(T, KV, D)
    q = q.reshape(T, KV, H // KV, D)    # head h = kv * rep + r
    s = jnp.einsum("qgrd,kgd->grqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(D)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("grqk,kgd->qgrd", a, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(T, H * D)
    x = x + _dense(matmul, o, w["out"])
    h = _rms_norm(x, w["ffn_norm"], eps)
    h = jax.nn.silu(_dense(matmul, h, w["gate"])) * _dense(matmul, h,
                                                           w["up"])
    return x + _dense(matmul, h, w["down"])


_layer = jax.jit(layer, static_argnums=(0, 3))


class _Frozen(dict):
    """A configuration as a hashable static argument."""

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other


def logits_at(cfg, seed, dtype, sequences, rows, matmul="highest"):
    """For each of ``sequences`` (1-D int arrays, each padded by the
    caller to a length that repeats, so that few programs compile), the
    float32 logits at its positions ``rows``: the model's forward pass
    layer by layer, weights made per layer from the seed in ``dtype`` and
    widened to float32.  Returns [(len(rows_i), V) arrays]."""
    frozen = _Frozen(cfg)
    f32 = lambda tree: {k.split(".")[-1]: v.astype(jnp.float32)
                        for k, v in tree.items()}
    embed = f32(init_leaves(cfg, seed, ["embed"], dtype))["embed"]
    xs = [embed[jnp.asarray(s)] for s in sequences]
    del embed
    for i in range(cfg["num_hidden_layers"]):
        w = f32(init_leaves(cfg, seed, ["layer%d.%s" % (i, leaf)
                                        for leaf in LAYER_LEAVES], dtype))
        xs = [_layer(frozen, w, x, matmul) for x in xs]
    tail = f32(init_leaves(cfg, seed, ["norm", "lm_head"], dtype))
    out = []
    for x, at in zip(xs, rows):
        h = _rms_norm(x[jnp.asarray(at)], tail["norm"], cfg["rms_norm_eps"])
        out.append(_dense(matmul, h, tail["lm_head"]))
    return out
