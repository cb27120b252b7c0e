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

from ..gluon import nn
from ..gluon.block import HybridBlock
from .parts import (ExpertShare, GatedMLP, LatentAttention, Residual, dense,
                    read_counters)
from .transformer import RMSNorm

__all__ = ["KDAMixer", "KimiLinearLM", "kimi_linear_from_config",
           "expert_loads"]


def expert_loads():
    """{layer prefix: {"held", "elsewhere", "held_sum", "elsewhere_sum"}}
    of every live expert layer (``ExpertShare.counters``): the ``moe``
    source of the MetricsRegistry."""
    return read_counters("moe")


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
                setattr(self, n + "_proj", dense(inner, units, n + "_"))
                setattr(self, n + "_conv", self.params.get(
                    n + "_conv", shape=(inner, conv_size), init="xavier"))
            self.f_down = dense(rank, units, "f_down_")
            self.f_up = dense(inner, rank, "f_up_")
            self.a_log = self.params.get("a_log", shape=(num_heads,),
                                         init="zeros")
            self.dt_bias = self.params.get("dt_bias", shape=(inner,),
                                           init="zeros")
            self.beta_proj = dense(num_heads, units, "beta_")
            self.g_down = dense(rank, units, "g_down_")
            self.g_up = dense(inner, rank, "g_up_", use_bias=True)
            self.o_norm = RMSNorm(head_dim, eps=eps, prefix="o_norm_")
            self.out_proj = dense(units, inner, "out_")

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
                self.layers.add(Residual(units, mix, eps, prefix=p + "mix_"))
                self.layers.add(Residual(units, ff, eps, prefix=p + "ffn_"))
            self.norm = RMSNorm(units, eps=eps, prefix="norm_")
            self.lm_head = dense(vocab_size, units, "lm_head_")

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
