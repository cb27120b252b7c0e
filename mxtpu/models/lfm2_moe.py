"""LFM2 expert decoders (``model_type: lfm2_moe``, LFM2-8B-A1B): a
pre-norm decoder whose layers are of two kinds by the published list
``layer_types`` — a gated short convolution (``"conv"``:
``F.gated_short_conv`` between an input projection to three chunks and
an output projection; no attention, no recurrence, no activation) and
causal grouped-query attention (``"full_attention"``: an RMSNorm with a
gain over each head of q and k, then the rotation over the whole head) —
with ``num_dense_layers`` leading dense SwiGLU layers and expert layers
after them that hold a share of the experts (``ExpertShare``: sigmoid
scores over all experts, top-k of score + frozen bias, renormalised over
the chosen with ``1e-6`` added to their sum, no shared expert).  The
head is the embedding:

    u = RMSNorm(h);  [b | c | x] = u W_in;  y = (c * conv(b * x)) W_out
    q, k, v = u W_q, u W_k, u W_v;  q, k <- RoPE(RMSNorm_head(.))
    o = softmax(q k^T / sqrt(d) + causal) v, a key head for each group
    of query heads;  y = o W_o
    logits = RMSNorm(h^L) E^T

Built with ``return_logits=False`` the model returns the head's input
instead and ``net.loss()`` takes the cross-entropy through the embedding
in blocks of rows (``F.linear_cross_entropy``).

Training only: a convolution's rolling state (the last ``L - 1`` gated
inputs a layer) beside paged keys and values, and the expert layer in
the engines, are serving's and not here (ROADMAP M5, M3).  Each residual
half of a layer is a unit of recomputation.
"""

from __future__ import annotations

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.loss import NextTokenLoss
from .parts import (ExpertShare, GatedMLP, GroupedQueryAttention, Residual,
                    count_under, dense, read_counters)
from .transformer import RMSNorm

__all__ = ["ShortConv", "Lfm2MoeLM", "lfm2_moe_from_config", "conv_counts"]

#: added to the chosen experts' summed scores where they are renormalised
RENORM_EPS = 1e-6


class ShortConv(HybridBlock):
    """The gated short convolution mixer: ``out((c * conv(b * x)))`` with
    ``[b | c | x] = in(u)``, a causal depthwise filter of ``conv_size``
    taps a channel, no bias.

    ``positions`` holds how many positions (B x T a forward pass) went
    through the convolution since the start (``conv_counts`` reads it).
    It stays on the device and nothing in a step reads it."""

    def __init__(self, units, conv_size=3, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.in_proj = dense(3 * units, units, "in_")
            self.conv = self.params.get("conv", shape=(units, conv_size),
                                        init="xavier")
            self.out_proj = dense(units, units, "out_")
            self.positions = self.params.get(
                "positions", shape=(1,), init="zeros", grad_req="null",
                dtype="int32")
        count_under("conv", self)

    def cast(self, dtype):
        super().cast(dtype)
        self.positions.cast("int32")            # a count stays a count

    def counters(self):
        """The positions counted since the start, to be summed over the
        layers."""
        return {"positions": int(self.positions.data()._data.reshape(()))}, {}

    def hybrid_forward(self, F, u, conv, positions):
        from .. import autograd

        y = F.gated_short_conv(self.in_proj(u), conv)
        with autograd.pause():
            self.positions.data(None)._rebind(
                (positions + u.shape[0] * u.shape[1]).data)
        return self.out_proj(y)


def conv_counts():
    """{"positions": the positions that went through a short
    convolution, summed over every live ``ShortConv`` and since the
    start} ({} when there is none): the ``conv`` counters."""
    return read_counters("conv")


class Lfm2MoeLM(HybridBlock):
    """The decoder: embedding, one layer for each entry of
    ``layer_types`` (``"conv"`` or ``"full_attention"``), the first
    ``num_dense`` with a dense MLP and the rest with an expert layer,
    final RMSNorm, and the embedding again as the head.  Called with
    token ids (B, T) it returns the logits, or with
    ``return_logits=False`` the head's input (B, T, units).
    ``lfm2_moe_from_config`` builds it from a published config's keys."""

    def __init__(self, vocab_size, units, layer_types, *, num_dense=2,
                 num_heads, num_kv_heads, conv_size=3, hidden_size,
                 expert_hidden_size, num_experts_total, top_k, held=None,
                 routed_scale=1.0, renormalize=True,
                 rope_base=1000000.0, eps=1e-5, return_logits=True,
                 **kwargs):
        super().__init__(**kwargs)
        self.layer_types = list(layer_types)
        self.num_layers, self.num_dense = len(self.layer_types), num_dense
        self._vocab, self._return_logits = vocab_size, return_logits
        head_dim = units // num_heads
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.layers = nn.HybridSequential(prefix="layers_")
            for i, kind in enumerate(self.layer_types):
                p = "layer%d_" % i
                if kind == "conv":
                    mix = ShortConv(units, conv_size, prefix=p + "conv_")
                elif kind == "full_attention":
                    mix = GroupedQueryAttention(
                        units, num_heads, num_kv_heads, head_dim,
                        rope_base=rope_base, eps=eps, prefix=p + "attn_")
                else:
                    raise ValueError("unknown layer type %r" % (kind,))
                ff = GatedMLP(units, hidden_size, prefix=p + "mlp_") \
                    if i < num_dense else ExpertShare(
                        units, expert_hidden_size, num_experts_total, top_k,
                        held, routed_scale, renormalize, 0, score="sigmoid",
                        renorm_eps=RENORM_EPS, prefix=p + "moe_")
                self.layers.add(Residual(units, mix, eps, prefix=p + "mix_"))
                self.layers.add(Residual(units, ff, eps, prefix=p + "ffn_"))
            self.norm = RMSNorm(units, eps=eps, prefix="norm_")

    def hybrid_forward(self, F, token_ids):
        x = self.embed(token_ids)
        for half in self.layers:
            x = half(x)
        x = self.norm(x)
        if not self._return_logits:
            return x
        return F.FullyConnected(x, self.embed.weight.data(x.context), None,
                                no_bias=True, num_hidden=self._vocab,
                                flatten=False)

    def decoder_layer(self, i):
        """(mixer half, ffn half) of layer ``i`` (from 0)."""
        return self.layers[2 * i], self.layers[2 * i + 1]

    def loss(self):
        """The next token's cross-entropy of whichever this model
        returns (logits, or the head's input)."""
        return NextTokenLoss(None if self._return_logits else self.embed)


def lfm2_moe_from_config(cfg, held=None, num_experts_total=None, **kwargs):
    """``Lfm2MoeLM`` from an ``lfm2_moe`` config's keys: the first
    ``num_hidden_layers`` entries of ``layer_types``.  ``held = (first,
    count)`` and ``num_experts_total`` make it one expert-parallel rank's
    share; by default it holds all ``cfg["num_experts"]``."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    if len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types names %d layers, num_hidden_layers "
                         "is %d" % (len(kinds), cfg["num_hidden_layers"]))
    if cfg.get("conv_bias"):
        raise ValueError("a bias in the convolution mixer is not here: "
                         "conv_bias must be false")
    return Lfm2MoeLM(
        cfg["vocab_size"], cfg["hidden_size"], kinds,
        num_dense=cfg["num_dense_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        conv_size=cfg["conv_L_cache"],
        hidden_size=cfg["intermediate_size"],
        expert_hidden_size=cfg["moe_intermediate_size"],
        num_experts_total=num_experts_total or cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"], held=held,
        routed_scale=cfg["routed_scaling_factor"],
        renormalize=cfg["norm_topk_prob"], rope_base=cfg["rope_theta"],
        eps=cfg["norm_eps"], **kwargs)
