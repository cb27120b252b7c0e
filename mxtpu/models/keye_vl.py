"""Keye-VL-2.0's language model (``model_type: KeyeVL2``, 30B-A3B): a
pre-norm decoder of identical layers, each grouped-query attention whose
keys a learned indexer selects (DeepSeek Sparse Attention, the
DeepSeek-V3.2-Exp report's lightning indexer: ``F.indexed_attention``)
and an expert layer that holds a share of softmax-routed experts
(``ExpertShare(score="softmax")``, no shared expert, no selection bias).

    q, k, v = h W_q, h W_k, h W_v      heads of ``head_dim``, q and k each
                                       through an RMSNorm over the head
    q, k <- rotary with three position streams (``F.mrope``)
    qI, kI, w = hbar W_qI, LayerNorm(hbar W_kI), hbar W_w / sqrt(Hi d)
                                       hbar = stop_gradient(h): the indexer
    qI, kI <- rotary by the first stream
    o, L_I = indexed attention over the top_k keys of every query

``h`` is the layer's normed input.  The model returns (logits, the
indexers' loss summed over the layers) and ``IndexedAttentionLoss``
(``net.loss()``) is its objective: the second term moves the indexers'
parameters and nothing else, the cross-entropy everything else.  Built
with ``return_logits=False`` it returns the head's input instead and
``net.loss()`` takes the cross-entropy through the head in blocks of
rows.

Training only, text only: selection inside paged attention with a cache
for the indexer's keys is serving's, the indexer's dense warm-up stage
and the vision tower whose tokens would give the three position streams
different values are not here (``positions`` takes such streams).  Each
residual half of a layer is a unit of recomputation.
"""

from __future__ import annotations

import math

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.loss import IndexedAttentionLoss
from .parts import ExpertShare, Residual, count_under, dense, read_counters
from .transformer import RMSNorm

__all__ = ["IndexedAttention", "KeyeVLTextLM", "keye_vl_from_config",
           "dsa_counts"]


class IndexedAttention(HybridBlock):
    """Grouped-query attention over the keys its indexer selects; takes
    (h, positions (3, T)) and returns (y, the indexer's loss (B,)).

    ``kept_mean`` holds the kept keys per query of the newest pass,
    ``kept_sum`` and ``kl_sum`` the pairs kept and the indexer's loss
    (a sequence's mean, summed over the sequences) since the start
    (``dsa_counts`` reads them).  They stay on the device and nothing in
    a step reads them."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, *,
                 index_heads, index_dim, top_k, rope_base=10000.0,
                 sections=(16, 24, 24), eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        self._heads, self._kv_heads, self._dim = (num_heads, num_kv_heads,
                                                  head_dim)
        self._index_heads, self._index_dim = index_heads, index_dim
        self._top_k, self._base = top_k, rope_base
        self._sections = tuple(sections)
        with self.name_scope():
            self.q_proj = dense(num_heads * head_dim, units, "q_")
            self.k_proj = dense(num_kv_heads * head_dim, units, "k_")
            self.v_proj = dense(num_kv_heads * head_dim, units, "v_")
            self.q_norm = RMSNorm(head_dim, eps=eps, prefix="q_norm_")
            self.k_norm = RMSNorm(head_dim, eps=eps, prefix="k_norm_")
            self.out_proj = dense(units, num_heads * head_dim, "out_")
            self.index_q = dense(index_heads * index_dim, units, "index_q_")
            self.index_k = dense(index_dim, units, "index_k_")
            self.index_k_norm = nn.LayerNorm(epsilon=eps,
                                             in_channels=index_dim,
                                             prefix="index_k_norm_")
            self.index_w = dense(index_heads, units, "index_w_")
            for name in ("kept_mean", "kept_sum", "kl_sum"):
                setattr(self, name, self.params.get(
                    name, shape=(1,), init="zeros", grad_req="null"))
        count_under("dsa", self)

    def cast(self, dtype):
        super().cast(dtype)
        for name in ("kept_mean", "kept_sum", "kl_sum"):
            getattr(self, name).cast("float32")

    def counters(self):
        """The pairs kept and the indexer's loss since the start, to be
        summed over the layers, and this layer's kept keys per query in
        the newest pass."""
        read = {name: float(getattr(self, name).data()._data.reshape(()))
                for name in ("kept_mean", "kept_sum", "kl_sum")}
        return ({"selected_pairs": read["kept_sum"],
                 "kl_sum": read["kl_sum"]},
                {self.prefix.rstrip("_") + ".kept_mean": read["kept_mean"]})

    def hybrid_forward(self, F, h, positions, kept_mean, kept_sum, kl_sum):
        from .. import autograd

        B, T, _ = h.shape
        H, G, D = self._heads, self._kv_heads, self._dim
        Hi, d = self._index_heads, self._index_dim

        def heads(x, n, width, norm=None):
            x = x.reshape((B, T, n, width))
            if norm is not None:
                x = norm(x)
            return x.transpose((0, 2, 1, 3))

        def turn(x):
            return F.mrope(x, positions, sections=self._sections,
                           base=self._base)

        q = turn(heads(self.q_proj(h), H, D, self.q_norm))
        k = turn(heads(self.k_proj(h), G, D, self.k_norm))
        v = heads(self.v_proj(h), G, D)
        hbar = F.stop_gradient(h)
        first = F.broadcast_to(positions[0:1], (B, T))
        q_idx = F.rope(heads(self.index_q(hbar), Hi, d), base=self._base,
                       offset=first)
        k_idx = F.rope(self.index_k_norm(self.index_k(hbar)),
                       base=self._base, offset=first)
        w_idx = self.index_w(hbar).transpose((0, 2, 1)) \
            * (1.0 / math.sqrt(Hi * d))
        o, kl, kept = F.indexed_attention(q, k, v, q_idx, k_idx, w_idx,
                                          top_k=self._top_k)
        with autograd.pause():
            now = F.sum(kept).reshape((1,))
            self.kept_mean.data(None)._rebind((now / (B * T)).data)
            self.kept_sum.data(None)._rebind((kept_sum + now).data)
            self.kl_sum.data(None)._rebind(
                (kl_sum + F.sum(kl).reshape((1,))).data)
        return self.out_proj(o.transpose((0, 2, 1, 3)).reshape((B, T, -1))), \
            kl


def dsa_counts():
    """{"selected_pairs", "kl_sum", "<layer>.kept_mean"...} of every live
    indexed-attention layer ({} when there is none): the ``dsa``
    counters."""
    return read_counters("dsa")


class KeyeVLTextLM(HybridBlock):
    """The decoder: embedding, ``num_layers`` layers of indexed attention
    and an expert layer, final RMSNorm, untied head.  Called with token
    ids (B, T) and optionally ``positions`` (3, T), the three streams of
    the rotary embedding (each 0..T-1 by default, which is what text
    is), it returns (logits, the indexers' loss (B,)); with
    ``return_logits=False`` the head's input stands in the logits'
    place.  ``keye_vl_from_config`` builds it from a published config's
    keys."""

    def __init__(self, vocab_size, units, num_layers, *, num_heads,
                 num_kv_heads, head_dim, index_heads, index_dim, top_k,
                 expert_hidden_size, num_experts_total, experts_per_token,
                 held=None, renormalize=True, rope_base=10000.0,
                 sections=(16, 24, 24), eps=1e-6, return_logits=True,
                 **kwargs):
        super().__init__(**kwargs)
        self.num_layers = num_layers
        self._return_logits = return_logits
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.layers = nn.HybridSequential(prefix="layers_")
            for i in range(num_layers):
                p = "layer%d_" % i
                mix = IndexedAttention(
                    units, num_heads, num_kv_heads, head_dim,
                    index_heads=index_heads, index_dim=index_dim,
                    top_k=top_k, rope_base=rope_base, sections=sections,
                    eps=eps, prefix=p + "attn_")
                ffn = ExpertShare(units, expert_hidden_size,
                                  num_experts_total, experts_per_token, held,
                                  1.0, renormalize, 0, score="softmax",
                                  prefix=p + "moe_")
                self.layers.add(Residual(units, mix, eps, prefix=p + "mix_"))
                self.layers.add(Residual(units, ffn, eps, prefix=p + "ffn_"))
            self.norm = RMSNorm(units, eps=eps, prefix="norm_")
            self.lm_head = dense(vocab_size, units, "lm_head_")

    def hybrid_forward(self, F, token_ids, positions=None):
        x = self.embed(token_ids)
        if positions is None:
            T = token_ids.shape[1]
            positions = F.broadcast_to(
                F.arange(T, dtype="int32").reshape((1, T)), (3, T))
        index_loss = None
        for i in range(self.num_layers):
            mix, ffn = self.decoder_layer(i)
            x, loss = mix(x, positions)
            index_loss = loss if index_loss is None else index_loss + loss
            x = ffn(x)
        x = self.norm(x)
        return (self.lm_head(x) if self._return_logits else x), index_loss

    def decoder_layer(self, i):
        """(attention half, expert half) of layer ``i`` (from 0)."""
        return self.layers[2 * i], self.layers[2 * i + 1]

    def loss(self, index_weight=1.0):
        """The objective of the indexer's sparse stage: the next token's
        cross-entropy, a mean over the positions, plus ``index_weight``
        times the indexers' loss, of whichever this model returns
        (logits, or the head's input)."""
        return IndexedAttentionLoss(
            index_weight,
            head=None if self._return_logits else self.lm_head)


def keye_vl_from_config(cfg, held=None, num_experts_total=None, **kwargs):
    """``KeyeVLTextLM`` from a ``KeyeVL2`` config's language-model keys.
    ``held = (first, count)`` and ``num_experts_total`` make it one
    expert-parallel rank's share; by default it holds all
    ``cfg["num_experts"]``.  ``intermediate_size`` is not read: with
    ``mlp_only_layers`` empty and ``decoder_sparse_step`` 1 no layer is
    dense."""
    if cfg.get("mlp_only_layers") or cfg.get("decoder_sparse_step", 1) != 1:
        raise ValueError("dense layers among the expert layers are not "
                         "here: mlp_only_layers must be empty and "
                         "decoder_sparse_step 1")
    sa = cfg["sa_config"]
    if sa.get("indexer_num_kv_heads", 1) != 1:
        raise ValueError("the indexer has one key head here, not %r"
                         % (sa["indexer_num_kv_heads"],))
    return KeyeVLTextLM(
        cfg["vocab_size"], cfg["hidden_size"], cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        index_heads=sa["indexer_num_heads"],
        index_dim=sa["indexer_head_dim"], top_k=sa["topk"],
        expert_hidden_size=cfg["moe_intermediate_size"],
        num_experts_total=num_experts_total or cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"], held=held,
        renormalize=cfg["norm_topk_prob"], rope_base=cfg["rope_theta"],
        sections=cfg["rope_scaling"]["mrope_section"],
        eps=cfg["rms_norm_eps"], **kwargs)
