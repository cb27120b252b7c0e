"""Plain reference for pre-training a Kimi-Linear decoder (Kimi Linear
technical report, arXiv:2510.26692; ``model_type: kimi_linear``), in
jax.numpy float32.

Imports nothing of mxtpu and takes nothing the program made.  It owns
the weights' recipe (``weight_shapes`` / ``init_weights``), the frozen
selection bias of the routers (``selection_bias``), the loss
(``loss_sum``) and MXNet's Adam rule (``adam_step``).

The layers, numbered from 1 as ``linear_attn_config`` numbers them:
pre-norm residual blocks ``x += Mix_l(RMSNorm(x))``,
``x += FFN_l(RMSNorm(x))``; ``Mix_l`` is Kimi Delta Attention (KDA) for
``l`` in ``kda_layers`` and NoPE latent attention (MLA) for ``l`` in
``full_attn_layers``; ``FFN_l`` is a dense SwiGLU for
``l <= first_k_dense_replace`` and the expert layer after.

KDA is computed as the recurrence itself, one position at a time:

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

in a ``lax.scan`` over single steps that is checkpointed in blocks of
steps (so that the backward pass holds one block's states, not 8,192):
that is blocking of the loop, and knows nothing of the chunk algebra
the program uses.  Attention is a dense masked softmax over blocks of
rows.  The expert layer runs every expert this share holds
(``num_experts``, the first of them ``held_experts_first``) on every
token and weighs the result by what the token gave that expert, routed by a
sigmoid router over all ``num_experts_total``; what the absent experts
would add is left out, as in the program.

``cfg["fault"]`` (never set in a configuration's file) serves the
faults that ``correct`` has to catch: ``"experts_left_out"`` drops the
held experts' output, ``"decay_is_one"`` sets KDA's decay to 1.

``matmul`` chooses how a product is formed: "highest" is float32 at full
precision (the reference); "bf16x3" forms it from three bfloat16 passes
(what XLA calls ``high``), "bf16" from one: the lower-precision controls.
"""

import functools
import math

import jax
import jax.numpy as jnp

BETA1, BETA2, ADAM_EPS = 0.9, 0.999, 1e-8
L2_EPS = 1e-6               # q/|q|, k/|k| of KDA: x * rsqrt(sum x^2 + eps)
SCAN_BLOCK = 128            # steps of the recurrence per checkpointed block
ROW_BLOCK = 256             # rows of attention scores formed at a time


# ------------------------------------------------------------------ shapes

def layer_kinds(cfg):
    """[(mixer, ffn)] of the layers the model has, from the published
    lists: mixer "kda" or "mla", ffn "dense" or "moe"."""
    lin = cfg["linear_attn_config"]
    kinds = []
    for l in range(1, cfg["num_hidden_layers"] + 1):
        if l in lin["kda_layers"]:
            mixer = "kda"
        elif l in lin["full_attn_layers"]:
            mixer = "mla"
        else:
            raise ValueError("layer %d is in neither list" % l)
        kinds.append((mixer, "dense" if l <= cfg["first_k_dense_replace"]
                      else "moe"))
    return kinds


def weight_shapes(cfg):
    """{name: (shape, kind)}; kind says how ``init_weights`` fills it."""
    C, V = cfg["hidden_size"], cfg["vocab_size"]
    lin = cfg["linear_attn_config"]
    H, K = lin["num_heads"], lin["head_dim"]
    W = lin["short_conv_kernel_size"]
    R = cfg["assumed_sizes"]["kda_gate_rank"]
    A = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    F, Fm = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    E, held = cfg["num_experts_total"], cfg["num_experts"]
    shapes = {"embed": ((V, C), "matrix"), "norm": ((C,), "ones"),
              "lm_head": ((V, C), "matrix")}
    for i, (mixer, ffn) in enumerate(layer_kinds(cfg)):
        p = "layer%d." % i
        shapes[p + "mix_norm"] = ((C,), "ones")
        shapes[p + "ffn_norm"] = ((C,), "ones")
        if mixer == "kda":
            for n in "qkv":
                shapes[p + n] = ((H * K, C), "matrix")
                shapes[p + n + "_conv"] = ((H * K, W), "filter")
            shapes.update({
                p + "f_down": ((R, C), "matrix"),
                p + "f_up": ((H * K, R), "matrix"),
                p + "A_log": ((H,), "a_log"),
                p + "dt_bias": ((H * K,), "dt_bias"),
                p + "beta": ((H, C), "matrix"),
                p + "g_down": ((R, C), "matrix"),
                p + "g_up": ((H * K, R), "matrix"),
                p + "g_up_bias": ((H * K,), "zeros"),
                p + "o_norm": ((K,), "ones"),
                p + "out": ((C, H * K), "matrix")})
        else:
            shapes.update({
                p + "q": ((A * (dn + dr), C), "matrix"),
                p + "dkv": ((rank + dr, C), "matrix"),
                p + "kv_norm": ((rank,), "ones"),
                p + "ukv": ((A * (dn + dv), rank), "matrix"),
                p + "out": ((C, A * dv), "matrix")})
        if ffn == "dense":
            shapes.update({p + "gate": ((F, C), "matrix"),
                           p + "up": ((F, C), "matrix"),
                           p + "down": ((C, F), "matrix")})
        else:
            shapes.update({
                p + "router": ((E, C), "matrix"),
                p + "experts_gate": ((held, C, Fm), "matrix"),
                p + "experts_up": ((held, C, Fm), "matrix"),
                p + "experts_down": ((held, Fm, C), "matrix"),
                p + "shared_gate": ((Fm, C), "matrix"),
                p + "shared_up": ((Fm, C), "matrix"),
                p + "shared_down": ((C, Fm), "matrix")})
    return shapes


def init_weights(cfg, seed, dtype=jnp.float32):
    """All weights from ``seed``, made on the device a leaf at a time and
    brought to the host (numpy): the program and the reference each place
    their own copy on the device, and the starting point that both are
    compared against takes none of its memory.  (Fetching a leaf waits
    for whatever the device was still running: a step in flight holds
    gigabytes that the next transfer would not fit beside.)
    N(0, initializer_range) matrices, embeddings and filters of the short
    convolutions, unit norm gains, and KDA's decay
    parameters as the released code draws them (``A_log`` the log of
    U(1, 16), ``dt_bias`` the inverse softplus of a step log-uniform in
    [0.001, 0.1])."""
    std = cfg.get("initializer_range", 0.02)
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    out = {}
    for n, (name, (shape, kind)) in enumerate(sorted(
            weight_shapes(cfg).items())):
        out[name] = jax.device_get(_leaf(jax.random.fold_in(key, n), shape,
                                         kind, std, dtype))
    return out


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _leaf(key, shape, kind, std, dtype):
    if kind in ("matrix", "filter"):
        x = std * jax.random.normal(key, shape, jnp.float32)
    elif kind == "a_log":
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        x = dt + jnp.log(-jnp.expm1(-dt))
    else:
        x = jnp.full(shape, float(kind == "ones"), jnp.float32)
    return x.astype(dtype)


def selection_bias(cfg):
    """{layer index: (num_experts_total,) float32}: the bias added to the
    router's scores for the choice of experts only.  The family trains it
    by a rule outside the gradient; here it is frozen at a draw fixed by
    the configuration (``router_bias``: seed and standard deviation), the
    same in every run."""
    spec = cfg["router_bias"]
    key = jax.random.PRNGKey(spec["seed"])
    return {i: spec["std"] * jax.random.normal(
                jax.random.fold_in(key, i), (cfg["num_experts_total"],),
                jnp.float32)
            for i, (_, ffn) in enumerate(layer_kinds(cfg)) if ffn == "moe"}


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


def _dense(matmul, x, w):
    return _einsum(matmul, "btc,fc->btf", x, w)


def _rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain


def _swiglu(matmul, x, gate, up, down):
    return _dense(matmul, jax.nn.silu(_dense(matmul, x, gate))
                  * _dense(matmul, x, up), down)


# --------------------------------------------------------------------- KDA

def _short_conv(x, filt):
    """Causal depthwise convolution over time, one filter a channel,
    left-padded with zeros: y_t = sum_j filt[:, j] * x_{t - (W-1) + j}."""
    W = filt.shape[-1]
    T = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    return sum(xp[:, j:j + T] * filt[:, j] for j in range(W))


def _l2_normalize(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPS)


def kda_recurrence(matmul, q, k, v, g, beta):
    """o_t of the gated delta rule, step by step.  q, k, g (B, T, H, K);
    v (B, T, H, V); beta (B, T, H); g the log of the decay (<= 0)."""
    B, T, H, K = q.shape
    block = math.gcd(T, SCAN_BLOCK)

    def one(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[..., None] * S                     # Diag(a) S
        kS = _einsum(matmul, "bhk,bhkv->bhv", k_t, S)
        S = S + (b_t[..., None] * k_t)[..., None] * (v_t - kS)[..., None, :]
        return S, _einsum(matmul, "bhk,bhkv->bhv", q_t, S)

    @jax.checkpoint
    def steps(S, xs):
        return jax.lax.scan(one, S, xs)

    xs = tuple(jnp.moveaxis(a, 1, 0).reshape((T // block, block)
                                             + a.shape[:1] + a.shape[2:])
               for a in (q, k, v, g, beta))
    S0 = jnp.zeros((B, H, K, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(steps, S0, xs)
    return jnp.moveaxis(o.reshape((T,) + o.shape[2:]), 0, 1)


def _kda_mixer(cfg, w, p, x, matmul):
    lin = cfg["linear_attn_config"]
    H, K = lin["num_heads"], lin["head_dim"]
    B, T, _ = x.shape
    heads = lambda a: a.reshape(B, T, H, K)
    q, k, v = (heads(jax.nn.silu(_short_conv(_dense(matmul, x, w[p + n]),
                                             w[p + n + "_conv"])))
               for n in "qkv")
    q = _l2_normalize(q) * K ** -0.5
    k = _l2_normalize(k)
    f = _dense(matmul, _dense(matmul, x, w[p + "f_down"]), w[p + "f_up"])
    g = -jnp.exp(w[p + "A_log"])[:, None] * heads(
        jax.nn.softplus(f + w[p + "dt_bias"]))
    if cfg.get("fault") == "decay_is_one":
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(_dense(matmul, x, w[p + "beta"]))
    o = kda_recurrence(matmul, q, k, v, g, beta)
    gate = _dense(matmul, _dense(matmul, x, w[p + "g_down"]),
                  w[p + "g_up"]) + w[p + "g_up_bias"]
    o = _rms_norm(o, w[p + "o_norm"], cfg["rms_norm_eps"]) \
        * jax.nn.sigmoid(heads(gate))
    return _dense(matmul, o.reshape(B, T, H * K), w[p + "out"])


# --------------------------------------------------------------------- MLA

def causal_attention(matmul, q, k, v):
    """softmax(q k^T / sqrt(Dk)) v under a causal mask, a block of rows
    at a time.  q, k (B, T, H, Dk); v (B, T, H, Dv)."""
    B, T, H, Dk = q.shape
    rows = math.gcd(T, ROW_BLOCK)

    @jax.checkpoint
    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, 1)
        s = _einsum(matmul, "bqhd,bkhd->bhqk", qb, k) / math.sqrt(Dk)
        seen = (start + jnp.arange(rows))[:, None] >= jnp.arange(T)[None]
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _einsum(matmul, "bhqk,bkhd->bqhd", a, v)

    o = jax.lax.map(block, jnp.arange(0, T, rows))      # (T/rows, B, rows..)
    return jnp.moveaxis(o, 0, 1).reshape(B, T, H, v.shape[-1])


def _mla_mixer(cfg, w, p, x, matmul):
    A = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    B, T, _ = x.shape
    q = _dense(matmul, x, w[p + "q"]).reshape(B, T, A, dn + dr)
    ckv = _dense(matmul, x, w[p + "dkv"])
    c, k_shared = ckv[..., :rank], ckv[..., rank:]
    kv = _dense(matmul, _rms_norm(c, w[p + "kv_norm"], cfg["rms_norm_eps"]),
                w[p + "ukv"]).reshape(B, T, A, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_shared[:, :, None, :], (B, T, A, dr))], -1)
    o = causal_attention(matmul, q, k, kv[..., dn:])     # no rotary: NoPE
    return _dense(matmul, o.reshape(B, T, A * dv), w[p + "out"])


# ----------------------------------------------------------------- experts

def route(cfg, scores, bias):
    """(chosen experts (.., k), their weights (.., k)) from the sigmoid
    scores over all experts: the k largest of score + bias, weighted by
    the scores themselves, renormalised over the chosen and scaled."""
    _, chosen = jax.lax.top_k(scores + bias, cfg["num_experts_per_token"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["moe_renormalize"]:
        picked = picked / jnp.sum(picked, -1, keepdims=True)
    return chosen, picked * cfg["routed_scaling_factor"]


def _expert_layer(cfg, w, p, x, bias, matmul):
    first, held = cfg["held_experts_first"], cfg["num_experts"]
    scores = jax.nn.sigmoid(_dense(matmul, x, w[p + "router"]))
    chosen, weights = route(cfg, scores, bias)
    y = _swiglu(matmul, x, w[p + "shared_gate"], w[p + "shared_up"],
                w[p + "shared_down"])
    if cfg.get("fault") == "experts_left_out":
        return y
    # every held expert on every token, weighed by what the token gave it
    # (nothing, for most): the held experts side by side in one product
    share = jnp.stack([jnp.sum(jnp.where(chosen == first + e, weights, 0.0),
                               -1) for e in range(held)])       # (held, B, T)
    h = jax.nn.silu(_einsum(matmul, "btc,ecf->ebtf", x,
                            w[p + "experts_gate"])) \
        * _einsum(matmul, "btc,ecf->ebtf", x, w[p + "experts_up"])
    return y + _einsum(matmul, "ebtf,efc->btc", h * share[..., None],
                       w[p + "experts_down"])


# ------------------------------------------------------------------- model

def logits_of(cfg, w, tokens, matmul="highest"):
    """(B, T) int tokens -> (B, T, V) float32 logits."""
    eps = cfg["rms_norm_eps"]
    biases = selection_bias(cfg)
    x = w["embed"][tokens]
    for i, (mixer, ffn) in enumerate(layer_kinds(cfg)):
        p = "layer%d." % i

        @jax.checkpoint
        def layer(x, w, p=p, mixer=mixer, ffn=ffn, i=i):
            mix = _kda_mixer if mixer == "kda" else _mla_mixer
            x = x + mix(cfg, w, p, _rms_norm(x, w[p + "mix_norm"], eps),
                        matmul)
            h = _rms_norm(x, w[p + "ffn_norm"], eps)
            if ffn == "dense":
                return x + _swiglu(matmul, h, w[p + "gate"], w[p + "up"],
                                   w[p + "down"])
            return x + _expert_layer(cfg, w, p, h, biases[i], matmul)

        x = layer(x, {k: v for k, v in w.items() if k.startswith(p)})
    return _dense(matmul, _rms_norm(x, w["norm"], eps), w["lm_head"])


def loss_sum(cfg, w, tokens, labels, matmul="highest"):
    """Sum (not mean) of the cross-entropy over every position."""
    logp = jax.nn.log_softmax(logits_of(cfg, w, tokens, matmul), axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return -picked.sum()


# -------------------------------------------------------------------- Adam

def adam_rule(w, grads, mean, var, rate):
    """One Adam update at an effective ``rate`` (the bias correction
    folded in): (weights, mean, var)."""
    mean = {k: BETA1 * mean[k] + (1 - BETA1) * grads[k] for k in w}
    var = {k: BETA2 * var[k] + (1 - BETA2) * jnp.square(grads[k])
           for k in w}
    new = {k: w[k] - rate * mean[k] / (jnp.sqrt(var[k]) + ADAM_EPS)
           for k in w}
    return new, mean, var


_adam_update = jax.jit(adam_rule)


def adam_step(w, grads, state, lr, t):
    """MXNet's Adam at step ``t`` (from 1): bias correction folded into
    the rate, epsilon outside it.  Returns (weights, (mean, var))."""
    rate = lr * math.sqrt(1.0 - BETA2 ** t) / (1.0 - BETA1 ** t)
    new, mean, var = _adam_update(w, grads, state[0], state[1],
                                  jnp.float32(rate))
    return new, (mean, var)
