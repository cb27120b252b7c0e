"""SmallThinker expert decoders (PowerInfer's SmallThinker-21BA3B; the
SmallThinker report, arXiv:2507.20984): a pre-norm decoder whose layers
differ by two published lists — ``rope_layout`` (1: q and k rotated by
their position, 0: no position at all, NoPE) and
``sliding_window_layout`` (1: each query attends only the last
``sliding_window_size`` keys, 0: every earlier key) — and whose every
layer holds a share of the routed experts (``ExpertShare``: a softmax
over all experts, top-k renormalised over the chosen, ReGLU experts, no
shared expert) whose router reads the layer's normed input *before*
attention.  For a layer with input x (T, C):

    a = RMSNorm_in(x)
    r = a W_router^T                        over all experts
    q, k, v = a W_q, a W_k, a W_v           no bias, no head norms
    q, k <- RoPE(.) where rope_layout[l] = 1 (rotate-half, whole head)
    o[t] = softmax_{s <= t (and s > t - W where the layer is windowed)}
           (q[t] k[s]^T / sqrt(d)) v[s], a key head for each group of
           query heads;  h = x + o W_o
    b = RMSNorm_post(h)
    E = top-k of r;  w = softmax(r) over E, renormalised
    out = h + sum_{e in E} w_e W_down,e(relu(W_gate,e b) * W_up,e b)

then the final RMSNorm and the untied head.  Built with
``return_logits=False`` the model returns the head's input and
``net.loss()`` takes the cross-entropy through the head in blocks of
rows (``F.linear_cross_entropy``).

Each layer is one unit of recomputation: the router's input lies in its
first half and is read in its second.  Training only: a window's ring
of keys and values in the paged cache, and releasing pages behind a
window, are serving's and not here (ROADMAP M2).
"""

from __future__ import annotations

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.loss import NextTokenLoss
from .parts import ExpertShare, GroupedQueryAttention, dense
from .transformer import RMSNorm

__all__ = ["SmallThinkerLayer", "SmallThinkerLM", "smallthinker_from_config",
           "counters"]


def counters():
    """The model's own counters as {name: number}: none.  Its expert
    layers' loads are ``ExpertShare``'s (``expert_loads``), and the pairs
    a windowed layer attends follow from its shapes alone."""
    return {}


class SmallThinkerLayer(HybridBlock):
    """One decoder layer, ``h = x + attn(a)``, ``h + moe(b, route=a)``
    with ``a = norm_in(x)`` and ``b = norm_post(h)``: the router reads
    the attention's normed input.  A unit of recomputation."""

    remat_unit = True

    def __init__(self, units, attn, moe, eps, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.attn_norm = RMSNorm(units, eps=eps, prefix="attn_norm_")
            self.attn = attn
            self.ffn_norm = RMSNorm(units, eps=eps, prefix="ffn_norm_")
            self.moe = moe

    def hybrid_forward(self, F, x):
        a = self.attn_norm(x)
        h = x + self.attn(a)
        return h + self.moe(self.ffn_norm(h), a)


class SmallThinkerLM(HybridBlock):
    """The decoder: embedding, one ``SmallThinkerLayer`` for each entry
    of ``rope_layout`` / ``window_layout`` (rotary where the first is 1,
    windowed where the second is), final RMSNorm, untied head.  Called
    with token ids (B, T) it returns the logits, or with
    ``return_logits=False`` the head's input (B, T, units).
    ``smallthinker_from_config`` builds it from a published config's
    keys."""

    def __init__(self, vocab_size, units, rope_layout, window_layout, *,
                 num_heads, num_kv_heads, head_dim, window,
                 expert_hidden_size, num_experts_total, top_k, held=None,
                 renormalize=True, score="softmax", rope_base=1500000.0,
                 eps=1e-6, return_logits=True, **kwargs):
        super().__init__(**kwargs)
        if len(rope_layout) != len(window_layout):
            raise ValueError("rope_layout has %d entries, window_layout %d"
                             % (len(rope_layout), len(window_layout)))
        self.rope_layout = [int(r) for r in rope_layout]
        self.window_layout = [int(w) for w in window_layout]
        self._vocab, self._return_logits = vocab_size, return_logits
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.layers = nn.HybridSequential(prefix="layers_")
            for i, (rope, windowed) in enumerate(zip(self.rope_layout,
                                                     self.window_layout)):
                p = "layer%d_" % i
                kind = dict(rope_base=rope_base if rope else None, eps=eps,
                            head_norms=False, prefix=p + "attn_")
                attn = GroupedQueryAttention(
                    units, num_heads, num_kv_heads, head_dim,
                    window=window if windowed else None, **kind)
                moe = ExpertShare(units, expert_hidden_size,
                                  num_experts_total, top_k, held, 1.0,
                                  renormalize, 0, score=score, act="relu",
                                  prefix=p + "moe_")
                self.layers.add(SmallThinkerLayer(units, attn, moe, eps,
                                                  prefix=p))
            self.norm = RMSNorm(units, eps=eps, prefix="norm_")
            self.lm_head = dense(vocab_size, units, "lm_head_")

    def hybrid_forward(self, F, token_ids):
        x = self.embed(token_ids)
        for layer in self.layers:
            x = layer(x)
        x = self.norm(x)
        return self.lm_head(x) if self._return_logits else x

    def loss(self):
        """The next token's cross-entropy of whichever this model
        returns (logits, or the head's input)."""
        return NextTokenLoss(None if self._return_logits else self.lm_head)


def smallthinker_from_config(cfg, held=None, num_experts_total=None,
                             **kwargs):
    """``SmallThinkerLM`` from a SmallThinker config's keys: the first
    ``num_hidden_layers`` entries of ``rope_layout`` and
    ``sliding_window_layout``.  ``held = (first, count)`` and
    ``num_experts_total`` make it one expert-parallel rank's share; by
    default it holds all ``cfg["moe_num_primary_experts"]``."""
    n = cfg["num_hidden_layers"]
    rope, windows = (cfg[key][:n] for key in ("rope_layout",
                                               "sliding_window_layout"))
    if len(rope) != n or len(windows) != n:
        raise ValueError("rope_layout and sliding_window_layout must name "
                         "num_hidden_layers = %d layers" % n)
    if cfg.get("tie_word_embeddings"):
        raise ValueError("a head tied to the embedding is not here: "
                         "tie_word_embeddings must be false")
    return SmallThinkerLM(
        cfg["vocab_size"], cfg["hidden_size"], rope, windows,
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=cfg["sliding_window_size"],
        expert_hidden_size=cfg["moe_ffn_hidden_size"],
        num_experts_total=num_experts_total
        or cfg["moe_num_primary_experts"],
        top_k=cfg["moe_num_active_primary_experts"], held=held,
        renormalize=cfg["norm_topk_prob"],
        score="softmax" if cfg["moe_primary_router_apply_softmax"]
        else "sigmoid",
        rope_base=cfg["rope_theta"], eps=cfg["rms_norm_eps"], **kwargs)
