"""Kimi-Linear decoders (Kimi Linear, arXiv:2510.26692; ``model_type:
kimi_linear``): a pre-norm decoder whose layers are of two kinds by the
published lists — Kimi Delta Attention (KDA, a gated delta rule with a
decay per channel: ``F.kda_mixer``) and NoPE latent attention (MLA: low-rank
keys and values, one shared key part, no rotary) — with a leading dense
SwiGLU layer and expert layers after it that hold a share of the experts
(``F.moe_expert_share``: sigmoid router over all experts, top-k,
renormalised and scaled, no capacity and no dropped token, plus one
shared expert computed whole).

Training only: the expanded form of latent attention.  The absorbed form
with a latent cache, the recurrent state in the cache manager and expert
layers in the paged engine are serving's and not here yet (ROADMAP
M3-M5).

Each residual half of a layer (``x + Mix(norm(x))``, ``x + FFN(norm(x))``)
is a unit of recomputation (``remat_unit``): under
``SPMDTrainer(remat=True)`` the backward pass keeps its input and forms
the rest again.
"""

from __future__ import annotations

import weakref

from ..gluon import nn
from ..gluon.block import HybridBlock
from .transformer import RMSNorm

__all__ = ["KDAMixer", "LatentAttention", "GatedMLP", "ExpertShare",
           "KimiLinearLM", "kimi_linear_from_config", "expert_loads"]


def _dense(units, in_units, prefix, use_bias=False):
    return nn.Dense(units, use_bias=use_bias, flatten=False,
                    in_units=in_units, prefix=prefix)


class KDAMixer(HybridBlock):
    """Kimi Delta Attention: q, k, v through a short causal convolution
    and SiLU, q and k normalised per head, a decay per head and key
    channel from a low-rank projection, a write strength per head, the
    recurrence, then a per-head RMSNorm gated by a sigmoid of a second
    low-rank projection (all of that ``F.kda_mixer``), and the output
    projection."""

    def __init__(self, units, num_heads, head_dim, conv_size=4,
                 gate_rank=None, eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        self._heads, self._dim, self._eps = num_heads, head_dim, eps
        inner = num_heads * head_dim
        rank = gate_rank or head_dim
        with self.name_scope():
            for n in "qkv":
                setattr(self, n + "_proj", _dense(inner, units, n + "_"))
                setattr(self, n + "_conv", self.params.get(
                    n + "_conv", shape=(inner, conv_size), init="xavier"))
            self.f_down = _dense(rank, units, "f_down_")
            self.f_up = _dense(inner, rank, "f_up_")
            self.a_log = self.params.get("a_log", shape=(num_heads,),
                                         init="zeros")
            self.dt_bias = self.params.get("dt_bias", shape=(inner,),
                                           init="zeros")
            self.beta_proj = _dense(num_heads, units, "beta_")
            self.g_down = _dense(rank, units, "g_down_")
            self.g_up = _dense(inner, rank, "g_up_", use_bias=True)
            self.o_norm = RMSNorm(head_dim, eps=eps, prefix="o_norm_")
            self.out_proj = _dense(units, inner, "out_")

    def hybrid_forward(self, F, x, q_conv, k_conv, v_conv, a_log, dt_bias):
        B, T, _ = x.shape
        heads = (B, T, self._heads, self._dim)
        o = F.kda_mixer(
            self.q_proj(x).reshape(heads), self.k_proj(x).reshape(heads),
            self.v_proj(x).reshape(heads),
            self.f_up(self.f_down(x)).reshape(heads),
            self.g_up(self.g_down(x)).reshape(heads),
            F.sigmoid(self.beta_proj(x)), q_conv, k_conv, v_conv, a_log,
            dt_bias, self.o_norm.weight.data(x.context), eps=self._eps)
        return self.out_proj(o.reshape((B, T, -1)))


class LatentAttention(HybridBlock):
    """Multi-head latent attention, expanded form: keys and values come
    up from a normed low-rank latent, every head's key is its own part
    beside one part shared by all heads, values have a width of their
    own; causal flash attention.  The query is one full-rank projection,
    or with ``q_rank`` low-rank too (down, RMSNorm, up).  Without
    ``rope_base`` there are no positions (NoPE); with it the query's last
    ``shared_dim`` columns and the shared key part are rotated by their
    position (``F.rope``: pairs are a column and the one half a width
    on)."""

    def __init__(self, units, num_heads, kv_rank, nope_dim, shared_dim,
                 v_dim, eps=1e-5, q_rank=None, rope_base=None, **kwargs):
        super().__init__(**kwargs)
        self._heads, self._rank = num_heads, kv_rank
        self._nope, self._shared, self._v = nope_dim, shared_dim, v_dim
        self._q_rank, self._rope = q_rank, rope_base
        q_units = num_heads * (nope_dim + shared_dim)
        with self.name_scope():
            if q_rank is None:
                self.q_proj = _dense(q_units, units, "q_")
            else:
                self.q_a_proj = _dense(q_rank, units, "q_a_")
                self.q_norm = RMSNorm(q_rank, eps=eps, prefix="q_norm_")
                self.q_b_proj = _dense(q_units, q_rank, "q_b_")
            self.dkv_proj = _dense(kv_rank + shared_dim, units, "dkv_")
            self.kv_norm = RMSNorm(kv_rank, eps=eps, prefix="kv_norm_")
            self.ukv_proj = _dense(num_heads * (nope_dim + v_dim), kv_rank,
                                   "ukv_")
            self.out_proj = _dense(units, num_heads * v_dim, "out_")

    def hybrid_forward(self, F, x):
        B, T, _ = x.shape
        H, dn, ds = self._heads, self._nope, self._shared
        q = self.q_proj(x) if self._q_rank is None else \
            self.q_b_proj(self.q_norm(self.q_a_proj(x)))
        q = q.reshape((B, T, H, dn + ds))
        ckv = self.dkv_proj(x)
        kv = self.ukv_proj(self.kv_norm(ckv[:, :, :self._rank])).reshape(
            (B, T, H, dn + self._v))
        shared = ckv[:, :, self._rank:]
        if self._rope is not None:
            shared = F.rope(shared, base=self._rope)
        shared = F.broadcast_to(shared.reshape((B, T, 1, ds)), (B, T, H, ds))
        k = F.concat(kv[:, :, :, :dn], shared, dim=-1)
        q, k, v = (a.transpose((0, 2, 1, 3)) for a in (q, k, kv[:, :, :, dn:]))
        if self._rope is not None:
            q = F.concat(q[:, :, :, :dn],
                         F.rope(q[:, :, :, dn:], base=self._rope), dim=-1)
        o = F.flash_attention(q, k, v, causal=True)       # (B, H, T, v_dim)
        return self.out_proj(o.transpose((0, 2, 1, 3)).reshape((B, T, -1)))


class GatedMLP(HybridBlock):
    """``down(silu(gate x) * up x)``."""

    def __init__(self, units, hidden_size, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.gate_proj = _dense(hidden_size, units, "gate_")
            self.up_proj = _dense(hidden_size, units, "up_")
            self.down_proj = _dense(units, hidden_size, "down_")

    def hybrid_forward(self, F, x):
        return self.down_proj(F.swish(self.gate_proj(x)) * self.up_proj(x))


_EXPERT_LAYERS = weakref.WeakSet()


class ExpertShare(HybridBlock):
    """An expert layer that holds ``held = (first, count)`` of
    ``num_experts_total`` gated experts (one expert-parallel rank's
    share; the whole layer when ``held`` is None) and ``num_shared``
    shared experts.  The router scores all experts, each by a sigmoid of
    its own or (``score="softmax"``) by a softmax over them all, and
    ``renorm_eps`` is added to the chosen scores' sum where they are
    renormalised; what the experts held elsewhere would add is not in
    the result (on one chip there is no exchange).

    ``select_bias`` (added to the scores for the choice only) is frozen:
    the family moves it by a rule outside the gradient, which is not
    here.  ``load`` holds, from the newest forward pass, the pairs each
    held expert received and then the pairs that fell elsewhere;
    ``load_sum`` their running sums (float32).  Both stay on the device
    (``expert_loads`` reads them)."""

    def __init__(self, units, hidden_size, num_experts_total, top_k,
                 held=None, routed_scale=1.0, renormalize=True,
                 num_shared=1, score="sigmoid", renorm_eps=0.0, **kwargs):
        super().__init__(**kwargs)
        first, count = held if held is not None else (0, num_experts_total)
        if not 0 <= first <= first + count <= num_experts_total:
            raise ValueError("held %r is not within %d experts"
                             % (held, num_experts_total))
        self._first, self._k = first, top_k
        self._scale, self._renorm = routed_scale, renormalize
        self._score, self._renorm_eps = score, renorm_eps
        with self.name_scope():
            self.router = _dense(num_experts_total, units, "router_")
            self.select_bias = self.params.get(
                "select_bias", shape=(num_experts_total,), init="zeros",
                grad_req="null")
            self.experts_gate = self.params.get(
                "experts_gate", shape=(count, units, hidden_size),
                init="xavier")
            self.experts_up = self.params.get(
                "experts_up", shape=(count, units, hidden_size),
                init="xavier")
            self.experts_down = self.params.get(
                "experts_down", shape=(count, hidden_size, units),
                init="xavier")
            self.load = self.params.get(
                "load", shape=(count + 1,), init="zeros", grad_req="null",
                dtype="int32")
            self.load_sum = self.params.get(
                "load_sum", shape=(count + 1,), init="zeros",
                grad_req="null")
            self.shared = GatedMLP(units, hidden_size * num_shared,
                                   prefix="shared_") if num_shared else None
        _EXPERT_LAYERS.add(self)

    def cast(self, dtype):
        super().cast(dtype)
        self.load.cast("int32")             # counts stay counts
        self.load_sum.cast("float32")

    def hybrid_forward(self, F, x, select_bias, experts_gate, experts_up,
                       experts_down, load, load_sum):
        from .. import autograd

        y, now = F.moe_expert_share(
            x, self.router.weight.data(x.context), select_bias,
            experts_gate, experts_up, experts_down, held_first=self._first,
            top_k=self._k, renormalize=self._renorm, scale=self._scale,
            score=self._score, renorm_eps=self._renorm_eps)
        with autograd.pause():
            self.load.data(None)._rebind(now.data)
            self.load_sum.data(None)._rebind(
                load_sum.data + now.data.astype(load_sum.data.dtype))
        return y if self.shared is None else y + self.shared(x)


def expert_loads():
    """{layer prefix: {"held": [pairs of each held expert], "elsewhere":
    pairs, "held_sum": [...], "elsewhere_sum": ...}} of every live expert
    layer: the newest forward pass and the running sums.  Reads the
    device (a handful of numbers a layer); the ``moe`` source of the
    MetricsRegistry."""
    import numpy as onp

    out = {}
    for layer in list(_EXPERT_LAYERS):
        try:
            now = onp.asarray(layer.load.data()._data)
            total = onp.asarray(layer.load_sum.data()._data)
        except Exception:       # not initialised yet: nothing to report
            continue
        out[layer.prefix.rstrip("_")] = {
            "held": now[:-1].tolist(), "elsewhere": int(now[-1]),
            "held_sum": total[:-1].tolist(),
            "elsewhere_sum": float(total[-1])}
    return out


class _Residual(HybridBlock):
    """``x + inner(norm(x))``: half a decoder layer, and the unit of
    recomputation."""

    remat_unit = True

    def __init__(self, units, inner, eps, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.norm = RMSNorm(units, eps=eps, prefix="norm_")
            self.inner = inner

    def hybrid_forward(self, F, x):
        return x + self.inner(self.norm(x))


class KimiLinearLM(HybridBlock):
    """The decoder: embedding, ``layers`` — a list of (mixer, ffn) with
    mixer "kda" or "mla" and ffn "dense" or "moe" — final RMSNorm, untied
    head.  ``kimi_linear_from_config`` builds it from a published
    config's keys."""

    def __init__(self, vocab_size, units, layers, *, num_heads,
                 kda_heads, kda_head_dim, conv_size=4, kda_gate_rank=None,
                 kv_rank, nope_dim, shared_dim, v_dim, hidden_size,
                 expert_hidden_size, num_experts_total, top_k, held=None,
                 routed_scale=1.0, renormalize=True, num_shared=1,
                 eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.layer_kinds = [tuple(kind) for kind in layers]
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.layers = nn.HybridSequential(prefix="layers_")
            for i, (mixer, ffn) in enumerate(self.layer_kinds):
                p = "layer%d_" % i
                if mixer == "kda":
                    mix = KDAMixer(units, kda_heads, kda_head_dim, conv_size,
                                   kda_gate_rank, eps, prefix=p + "kda_")
                elif mixer == "mla":
                    mix = LatentAttention(units, num_heads, kv_rank,
                                          nope_dim, shared_dim, v_dim, eps,
                                          prefix=p + "mla_")
                else:
                    raise ValueError("unknown mixer %r" % (mixer,))
                if ffn == "dense":
                    ff = GatedMLP(units, hidden_size, prefix=p + "mlp_")
                elif ffn == "moe":
                    ff = ExpertShare(units, expert_hidden_size,
                                     num_experts_total, top_k, held,
                                     routed_scale, renormalize, num_shared,
                                     prefix=p + "moe_")
                else:
                    raise ValueError("unknown ffn %r" % (ffn,))
                self.layers.add(_Residual(units, mix, eps,
                                          prefix=p + "mix_"))
                self.layers.add(_Residual(units, ff, eps,
                                          prefix=p + "ffn_"))
            self.norm = RMSNorm(units, eps=eps, prefix="norm_")
            self.lm_head = _dense(vocab_size, units, "lm_head_")

    def hybrid_forward(self, F, token_ids):
        x = self.embed(token_ids)
        for half in self.layers:
            x = half(x)
        return self.lm_head(self.norm(x))

    def decoder_layer(self, i):
        """(mixer half, ffn half) of layer ``i`` (from 0)."""
        return self.layers[2 * i], self.layers[2 * i + 1]


def kimi_linear_from_config(cfg, held=None, num_experts_total=None,
                            kda_gate_rank=None, **kwargs):
    """``KimiLinearLM`` from a ``kimi_linear`` config's keys.  The model
    takes from ``linear_attn_config``'s lists (numbered from 1) the
    layers it has: ``num_hidden_layers`` of them from the first.
    ``held = (first, count)`` and ``num_experts_total`` make it one
    expert-parallel rank's share; by default it holds all
    ``cfg["num_experts"]``."""
    lin = cfg["linear_attn_config"]
    layers = []
    for l in range(1, cfg["num_hidden_layers"] + 1):
        if l in lin["kda_layers"]:
            mixer = "kda"
        elif l in lin["full_attn_layers"]:
            mixer = "mla"
        else:
            raise ValueError("layer %d is in neither kda_layers nor "
                             "full_attn_layers" % l)
        layers.append((mixer, "dense" if l <= cfg["first_k_dense_replace"]
                       else "moe"))
    return KimiLinearLM(
        cfg["vocab_size"], cfg["hidden_size"], layers,
        num_heads=cfg["num_attention_heads"], kda_heads=lin["num_heads"],
        kda_head_dim=lin["head_dim"],
        conv_size=lin["short_conv_kernel_size"], kda_gate_rank=kda_gate_rank,
        kv_rank=cfg["kv_lora_rank"], nope_dim=cfg["qk_nope_head_dim"],
        shared_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        hidden_size=cfg["intermediate_size"],
        expert_hidden_size=cfg["moe_intermediate_size"],
        num_experts_total=num_experts_total or cfg["num_experts"],
        top_k=cfg["num_experts_per_token"], held=held,
        routed_scale=cfg["routed_scaling_factor"],
        renormalize=cfg["moe_renormalize"],
        num_shared=cfg["num_shared_experts"], eps=cfg["rms_norm_eps"],
        **kwargs)
