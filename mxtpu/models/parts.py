"""The parts the expert decoders share, and the one registry of the
counters their training step hands back on the device.  A model's own
file holds its mixer, its LM class and its ``*_from_config`` and takes
from ``models/`` only this module and ``transformer``; a part gains an
option only where a model needs one, with a default that keeps every
other model's program (docs/training.md, "Adding a decoder").
"""

from __future__ import annotations

import weakref

from ..base import MXTPUError
from ..gluon import nn
from ..gluon.block import HybridBlock
from .transformer import RMSNorm

__all__ = ["dense", "GatedMLP", "ExpertShare", "LatentAttention",
           "GroupedQueryAttention", "Residual", "count_under",
           "read_counters"]


def dense(units, in_units, prefix, use_bias=False):
    return nn.Dense(units, use_bias=use_bias, flatten=False,
                    in_units=in_units, prefix=prefix)


#: every live counted block -> the source it counts under
_COUNTED = weakref.WeakKeyDictionary()


def count_under(source, block):
    """Enter ``block`` in the registry under ``source`` ("moe", "mtp",
    "dsa", "conv"): ``block.counters()`` returns ({entry: number summed
    over the source's blocks}, {entry: value of this block alone})."""
    _COUNTED[block] = source


def read_counters(source):
    """One dict over every live block counted under ``source`` ({} when
    there is none): the summed entries added up, the blocks' own entries
    beside them.  Reads a few numbers a block from the device."""
    out = {}
    for block, name in list(_COUNTED.items()):
        if name != source:
            continue
        try:
            summed, own = block.counters()
        except MXTPUError:      # not initialised yet: nothing to report
            continue
        for key, value in summed.items():
            out[key] = out.get(key, 0) + value
        out.update(own)
    return out


class GatedMLP(HybridBlock):
    """``down(silu(gate x) * up x)``."""

    def __init__(self, units, hidden_size, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.gate_proj = dense(hidden_size, units, "gate_")
            self.up_proj = dense(hidden_size, units, "up_")
            self.down_proj = dense(units, hidden_size, "down_")

    def hybrid_forward(self, F, x):
        return self.down_proj(F.swish(self.gate_proj(x)) * self.up_proj(x))


class ExpertShare(HybridBlock):
    """An expert layer that holds ``held = (first, count)`` of
    ``num_experts_total`` gated experts (one expert-parallel rank's
    share; the whole layer when ``held`` is None) and ``num_shared``
    shared experts.  The router scores all experts, each by a sigmoid of
    its own or (``score="softmax"``) by a softmax over them all, and
    ``renorm_eps`` is added to the chosen scores' sum where they are
    renormalised; what the experts held elsewhere would add is not in
    the result (on one chip there is no exchange).  The router reads the
    layer's input, or a second input ``route`` where the caller passes
    one (a router placed before attention reads the attention's normed
    input); ``act`` is the experts' gate activation, "silu" (SwiGLU) or
    "relu" (ReGLU).

    ``select_bias`` (added to the scores for the choice only) is frozen:
    the family moves it by a rule outside the gradient, which is not
    here.  ``load`` holds, from the newest forward pass, the pairs each
    held expert received and then the pairs that fell elsewhere;
    ``load_sum`` their running sums (float32).  Both stay on the device
    (the ``moe`` counters read them)."""

    def __init__(self, units, hidden_size, num_experts_total, top_k,
                 held=None, routed_scale=1.0, renormalize=True,
                 num_shared=1, score="sigmoid", renorm_eps=0.0, act="silu",
                 **kwargs):
        super().__init__(**kwargs)
        first, count = held if held is not None else (0, num_experts_total)
        if not 0 <= first <= first + count <= num_experts_total:
            raise ValueError("held %r is not within %d experts"
                             % (held, num_experts_total))
        self._first, self._k = first, top_k
        self._scale, self._renorm = routed_scale, renormalize
        self._score, self._renorm_eps, self._act = score, renorm_eps, act
        with self.name_scope():
            self.router = dense(num_experts_total, units, "router_")
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
        count_under("moe", self)

    def cast(self, dtype):
        super().cast(dtype)
        self.load.cast("int32")             # counts stay counts
        self.load_sum.cast("float32")

    def counters(self):
        """Nothing summed, and {layer prefix: {"held": [pairs of each held
        expert], "elsewhere": pairs, "held_sum": [...], "elsewhere_sum":
        ...}}: the newest forward pass and the running sums."""
        import numpy as onp

        now = onp.asarray(self.load.data()._data)
        total = onp.asarray(self.load_sum.data()._data)
        return {}, {self.prefix.rstrip("_"): {
            "held": now[:-1].tolist(), "elsewhere": int(now[-1]),
            "held_sum": total[:-1].tolist(),
            "elsewhere_sum": float(total[-1])}}

    def hybrid_forward(self, F, x, route=None, *, select_bias, experts_gate,
                       experts_up, experts_down, load, load_sum):
        from .. import autograd

        y, now = F.moe_expert_share(
            x, self.router.weight.data(x.context), select_bias,
            experts_gate, experts_up, experts_down, route,
            held_first=self._first, top_k=self._k, renormalize=self._renorm,
            scale=self._scale, score=self._score,
            renorm_eps=self._renorm_eps, act=self._act)
        with autograd.pause():
            self.load.data(None)._rebind(now.data)
            self.load_sum.data(None)._rebind(
                load_sum.data + now.data.astype(load_sum.data.dtype))
        return y if self.shared is None else y + self.shared(x)


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
                self.q_proj = dense(q_units, units, "q_")
            else:
                self.q_a_proj = dense(q_rank, units, "q_a_")
                self.q_norm = RMSNorm(q_rank, eps=eps, prefix="q_norm_")
                self.q_b_proj = dense(q_units, q_rank, "q_b_")
            self.dkv_proj = dense(kv_rank + shared_dim, units, "dkv_")
            self.kv_norm = RMSNorm(kv_rank, eps=eps, prefix="kv_norm_")
            self.ukv_proj = dense(num_heads * (nope_dim + v_dim), kv_rank,
                                  "ukv_")
            self.out_proj = dense(units, num_heads * v_dim, "out_")

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


class GroupedQueryAttention(HybridBlock):
    """Causal attention in which each of ``num_kv_heads`` key heads
    serves ``num_heads / num_kv_heads`` query heads.  With
    ``head_norms`` (LFM2's) q and k pass an RMSNorm over a head's
    columns, with a gain; with a ``rope_base`` they are then rotated by
    their position over the whole head (``F.rope``: a column pairs with
    the one half a head on), without one they carry no position (NoPE).
    With a ``window`` query t attends only the keys s with t - window <
    s <= t (``F.flash_attention(window=)``).  No bias.  The flash kernel
    takes the keys and values repeated to the query heads (as
    ``ops/dsa.py`` does)."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, *,
                 rope_base=10000.0, eps=1e-5, head_norms=True, window=None,
                 **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise ValueError("%d query heads are no multiple of %d key "
                             "heads" % (num_heads, num_kv_heads))
        self._heads, self._kv_heads, self._dim = (num_heads, num_kv_heads,
                                                  head_dim)
        self._base, self._window = rope_base, window
        with self.name_scope():
            self.q_proj = dense(num_heads * head_dim, units, "q_")
            self.k_proj = dense(num_kv_heads * head_dim, units, "k_")
            self.v_proj = dense(num_kv_heads * head_dim, units, "v_")
            if head_norms:
                self.q_norm = RMSNorm(head_dim, eps=eps, prefix="q_norm_")
                self.k_norm = RMSNorm(head_dim, eps=eps, prefix="k_norm_")
            else:
                self.q_norm = self.k_norm = None
            self.out_proj = dense(units, num_heads * head_dim, "out_")

    def hybrid_forward(self, F, u):
        B, T, _ = u.shape
        H, G, D = self._heads, self._kv_heads, self._dim

        def heads(x, n, norm=None):
            x = x.reshape((B, T, n, D))
            if norm is not None:
                x = norm(x)
            return x.transpose((0, 2, 1, 3))

        def turned(x):
            return x if self._base is None else F.rope(x, base=self._base)

        q = turned(heads(self.q_proj(u), H, self.q_norm))
        k = turned(heads(self.k_proj(u), G, self.k_norm))
        v = heads(self.v_proj(u), G)
        if H != G:
            k, v = (F.repeat(a, repeats=H // G, axis=1) for a in (k, v))
        o = F.flash_attention(q, k, v, causal=True, window=self._window)
        return self.out_proj(o.transpose((0, 2, 1, 3)).reshape((B, T, -1)))


class Residual(HybridBlock):
    """``x + inner(norm(x), *args)``: half a decoder layer, and the unit
    of recomputation.  Where ``inner`` returns ``(y, *side)`` (indexed
    attention's loss) it returns ``(x + y, *side)``."""

    remat_unit = True

    def __init__(self, units, inner, eps, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.norm = RMSNorm(units, eps=eps, prefix="norm_")
            self.inner = inner

    def hybrid_forward(self, F, x, *args):
        y = self.inner(self.norm(x), *args)
        if isinstance(y, tuple):
            return (x + y[0],) + y[1:]
        return x + y
