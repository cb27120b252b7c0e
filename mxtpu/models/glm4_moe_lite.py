"""GLM-4.7-Flash decoders (``model_type: glm4_moe_lite``): a pre-norm
decoder in which every layer's mixer is rotary latent attention with a
low-rank query (``LatentAttention`` with ``q_rank`` and ``rope_base``:
the query comes down to ``q_lora_rank``, through an RMSNorm, and up to
heads of ``qk_nope_head_dim + qk_rope_head_dim``; the last
``qk_rope_head_dim`` columns of the query and the one key part all heads
share are rotated by their position), with ``first_k_dense_replace``
dense SwiGLU layers and expert layers after them that hold a share of
the experts (``ExpertShare``: sigmoid router over all experts, top-k of
score + frozen bias, renormalised and scaled, one shared expert), and
the family's multi-token-prediction module as the layer after the last
(DeepSeek-V3, arXiv:2412.19437 section 2.2):

    h'_i   = W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)]
    h''    = Block(h')           an expert layer with weights of its own
    logits^m_i = Head(RMSNorm_m(h''_i))          which predicts t_{i+2}

``h_i`` is the last layer's output before the final norm; embedding and
head are the model's own.  With the module the model returns (logits,
module's logits) and ``MultiTokenLoss`` (``net.loss()``) is its
objective.  Built with ``return_logits=False`` it returns the head's two
inputs instead and ``net.loss()`` takes both cross-entropies through
the head in blocks of rows: at 8,192 positions over 19,360 ids two sets
of float32 logits with what their gradients need are some 4 GB, which a
chip that holds the weights, gradients and Adam state has not.

Training only, the expanded form of latent attention: the absorbed form
over a latent paged cache and the module as a drafter in the engines are
serving's and not here (ROADMAP M4, M9).  Each residual half of a layer,
the module's halves among them, is a unit of recomputation.
"""

from __future__ import annotations

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.loss import MultiTokenLoss
from .parts import (ExpertShare, GatedMLP, LatentAttention, Residual,
                    count_under, dense, read_counters)
from .transformer import RMSNorm

__all__ = ["PredictionModule", "Glm4MoeLiteLM", "glm4_moe_lite_from_config",
           "mtp_counts"]


class PredictionModule(HybridBlock):
    """The multi-token-prediction module without the embedding and the
    head it shares with the model: two norms, the projection of their
    concatenation (the embedding's half first), one decoder layer
    (``mix``, ``ffn``: the inner blocks of its two halves) and its own
    final norm.

    ``positions``, ``loss_sum`` and ``main_loss_sum`` hold, since the
    start, how many positions entered the module's loss and the sums of
    the module's and the main cross-entropies over their positions
    (``count``, which the loss calls; ``mtp_counts`` reads them).  They
    stay on the device and nothing in a step reads them."""

    def __init__(self, units, mix, ffn, eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.enorm = RMSNorm(units, eps=eps, prefix="enorm_")
            self.hnorm = RMSNorm(units, eps=eps, prefix="hnorm_")
            self.eh_proj = dense(units, 2 * units, "eh_proj_")
            self.mix = Residual(units, mix, eps, prefix="mix_")
            self.ffn = Residual(units, ffn, eps, prefix="ffn_")
            self.norm = RMSNorm(units, eps=eps, prefix="norm_")
            self.positions = self.params.get(
                "positions", shape=(1,), init="zeros", grad_req="null",
                dtype="int32")
            self.loss_sum = self.params.get(
                "loss_sum", shape=(1,), init="zeros", grad_req="null")
            self.main_loss_sum = self.params.get(
                "main_loss_sum", shape=(1,), init="zeros", grad_req="null")
        count_under("mtp", self)

    def cast(self, dtype):
        super().cast(dtype)
        self.positions.cast("int32")            # a count stays a count
        self.loss_sum.cast("float32")
        self.main_loss_sum.cast("float32")

    def hybrid_forward(self, F, embedded_next, hidden, positions, loss_sum,
                       main_loss_sum):
        x = self.eh_proj(F.concat(self.enorm(embedded_next),
                                  self.hnorm(hidden), dim=-1))
        return self.norm(self.ffn(self.mix(x)))

    def counters(self):
        """The three counters, to be summed over the modules."""
        read = {name: getattr(self, name).data()._data.reshape(())
                for name in ("positions", "loss_sum", "main_loss_sum")}
        return {name: int(value) if name == "positions" else float(value)
                for name, value in read.items()}, {}

    def count(self, positions, main_sum, mtp_sum):
        """Add one pass's positions and the two terms' sums."""
        from .. import autograd

        with autograd.pause():
            for param, more in ((self.positions, positions),
                                (self.main_loss_sum, main_sum),
                                (self.loss_sum, mtp_sum)):
                held = param.data(None)
                held._rebind(held._data + more._data.astype(
                    held._data.dtype).reshape(held._data.shape))


def mtp_counts():
    """{"positions", "loss_sum", "main_loss_sum"} summed over every live
    prediction module ({} when there is none): the ``mtp`` counters."""
    return read_counters("mtp")


class Glm4MoeLiteLM(HybridBlock):
    """The decoder: embedding, ``num_layers`` layers of latent attention
    and a dense MLP (the first ``num_dense``) or an expert layer, final
    RMSNorm, untied head, and with ``num_nextn`` the prediction module;
    then it returns (logits, the module's logits), the module fed the
    ids one position on (id 0 after the last, a position ``loss()``
    leaves out).  With ``return_logits=False`` the head is left to the
    loss: the model returns the head's input, or inputs, (B, T, units).
    ``glm4_moe_lite_from_config`` builds it from a published config's
    keys."""

    def __init__(self, vocab_size, units, num_layers, *, num_dense=1,
                 num_heads, q_rank, kv_rank, nope_dim, rope_dim, v_dim,
                 hidden_size, expert_hidden_size, num_experts_total, top_k,
                 held=None, routed_scale=1.0, renormalize=True,
                 num_shared=1, rope_base=10000.0, eps=1e-5, num_nextn=1,
                 return_logits=True, **kwargs):
        super().__init__(**kwargs)
        if num_nextn not in (0, 1):
            raise ValueError("one prediction module or none, not %r"
                             % (num_nextn,))

        def attention(prefix):
            return LatentAttention(units, num_heads, kv_rank, nope_dim,
                                   rope_dim, v_dim, eps, q_rank=q_rank,
                                   rope_base=rope_base, prefix=prefix)

        def experts(prefix):
            return ExpertShare(units, expert_hidden_size, num_experts_total,
                               top_k, held, routed_scale, renormalize,
                               num_shared, prefix=prefix)

        self.num_layers, self.num_dense = num_layers, num_dense
        self._return_logits = return_logits
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.layers = nn.HybridSequential(prefix="layers_")
            for i in range(num_layers):
                p = "layer%d_" % i
                ff = GatedMLP(units, hidden_size, prefix=p + "mlp_") \
                    if i < num_dense else experts(p + "moe_")
                self.layers.add(Residual(units, attention(p + "mla_"), eps,
                                         prefix=p + "mix_"))
                self.layers.add(Residual(units, ff, eps, prefix=p + "ffn_"))
            self.norm = RMSNorm(units, eps=eps, prefix="norm_")
            self.lm_head = dense(vocab_size, units, "lm_head_")
            self.mtp = PredictionModule(
                units, attention("mtp_mla_"), experts("mtp_moe_"), eps,
                prefix="mtp_") if num_nextn else None

    def hybrid_forward(self, F, token_ids):
        x = self.embed(token_ids)
        for half in self.layers:
            x = half(x)
        head = self.lm_head if self._return_logits else (lambda h: h)
        out = head(self.norm(x))
        if self.mtp is None:
            return out
        following = F.concat(token_ids[:, 1:],
                             F.zeros_like(token_ids[:, :1]), dim=1)
        return out, head(self.mtp(self.embed(following), x))

    def decoder_layer(self, i):
        """(mixer half, ffn half) of layer ``i`` (from 0)."""
        return self.layers[2 * i], self.layers[2 * i + 1]

    def loss(self, mtp_weight=0.3):
        """The objective the family trains on: the next token's
        cross-entropy plus ``mtp_weight`` times the module's (the token
        after the next; its last position left out), each a mean over
        its positions, of whichever this model returns (logits, or the
        head's inputs).  It counts into the module's counters."""
        if self.mtp is None:
            raise ValueError("this model has no prediction module")
        return MultiTokenLoss(
            mtp_weight, record=self.mtp.count,
            head=None if self._return_logits else self.lm_head)


def glm4_moe_lite_from_config(cfg, held=None, num_experts_total=None,
                              **kwargs):
    """``Glm4MoeLiteLM`` from a ``glm4_moe_lite`` config's keys.
    ``held = (first, count)`` and ``num_experts_total`` make it one
    expert-parallel rank's share; by default it holds all
    ``cfg["n_routed_experts"]``."""
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise ValueError("group-limited routing is not here: n_group and "
                         "topk_group must be 1")
    return Glm4MoeLiteLM(
        cfg["vocab_size"], cfg["hidden_size"], cfg["num_hidden_layers"],
        num_dense=cfg["first_k_dense_replace"],
        num_heads=cfg["num_attention_heads"], q_rank=cfg["q_lora_rank"],
        kv_rank=cfg["kv_lora_rank"], nope_dim=cfg["qk_nope_head_dim"],
        rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        hidden_size=cfg["intermediate_size"],
        expert_hidden_size=cfg["moe_intermediate_size"],
        num_experts_total=num_experts_total or cfg["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"], held=held,
        routed_scale=cfg["routed_scaling_factor"],
        renormalize=cfg["norm_topk_prob"],
        num_shared=cfg["n_shared_experts"], rope_base=cfg["rope_theta"],
        eps=cfg["rms_norm_eps"],
        num_nextn=cfg.get("num_nextn_predict_layers", 0), **kwargs)
