"""Expert-parallel Mixture-of-Experts blocks (SURVEY §2.3 row 59; no
reference analogue — the reference's distributed story stops at ps-lite
data parallelism.  TPU-first design: static-capacity Switch routing in
ops/moe.py, expert weights sharded over the mesh "ep" axis so GSPMD
lowers dispatch/combine einsums into expert all-to-alls over ICI).
"""

from __future__ import annotations


from ..gluon.block import HybridBlock
from ..parallel.sharding import ShardingRules, PartitionSpec as P

__all__ = ["SwitchMoE", "MoEDecoderLayer", "moe_sharding_rules"]


def _is_tracer(x):
    """True for jit tracers AND Symbols — anything that must not be
    stored on the block as eager state."""
    import jax

    from ..symbol.symbol import Symbol

    if isinstance(x, Symbol):
        return True
    data = getattr(x, "_data", x)
    return isinstance(data, jax.core.Tracer)


class SwitchMoE(HybridBlock):
    """Switch-Transformer FFN: top-1 routed experts, static capacity.

    Dropped tokens (over capacity) contribute zero — use inside a
    residual block.

    Load-balancing aux loss: with ``return_aux=True`` the forward
    returns ``(y, aux)`` so the caller threads aux into the training
    loss — the ONLY mechanism that works under hybridize/SPMDTrainer
    jit, where a Python side effect would leak a tracer.  In eager mode
    ``self.aux_loss`` is additionally updated after each forward as a
    convenience (it is NOT updated inside compiled graphs).
    """

    def __init__(self, units, hidden_size, num_experts,
                 capacity_factor=1.25, activation="swish",
                 return_aux=False, top_k=1, router_jitter=0.0,
                 z_loss_weight=0.0, **kwargs):
        super().__init__(**kwargs)
        self._E = num_experts
        self._cf = capacity_factor
        self._act = activation
        self._return_aux = return_aux
        self._top_k = top_k
        self._jitter = router_jitter
        self._z_loss = z_loss_weight
        with self.name_scope():
            self.router_weight = self.params.get(
                "router_weight", shape=(num_experts, units),
                init="xavier")
            self.experts_w1 = self.params.get(
                "experts_w1", shape=(num_experts, units, hidden_size),
                init="xavier")
            self.experts_w2 = self.params.get(
                "experts_w2", shape=(num_experts, hidden_size, units),
                init="xavier")
        self.aux_loss = None

    def hybrid_forward(self, F, x, router_weight, experts_w1,
                       experts_w2):
        y, aux = F.switch_moe(x, router_weight, experts_w1, experts_w2,
                              capacity_factor=self._cf,
                              activation=self._act, top_k=self._top_k,
                              router_jitter=self._jitter,
                              z_loss_weight=self._z_loss)
        if not _is_tracer(aux):  # eager convenience only — never store
            self.aux_loss = aux  # a tracer on the block (jit leak)
        if self._return_aux:
            return y, aux
        return y

    def decode_forward(self, x):
        """Capacity-UNBOUNDED imperative forward for incremental decode:
        a decode step routes only B tokens, so the training capacity
        (ceil(S/E * cf)) would spuriously zero tokens the full-context
        forward kept.  Inference MoE conventionally drops nothing."""
        from .. import ndarray as nd

        ctx = x.context
        y, _ = nd.switch_moe(x, self.router_weight.data(ctx),
                             self.experts_w1.data(ctx),
                             self.experts_w2.data(ctx),
                             capacity_factor=0.0, activation=self._act,
                             top_k=self._top_k)
        return y

    def prefill_forward(self, x, total_len=None):
        """Imperative forward for CHUNKED prefill: the TRAINING capacity
        (not decode_forward's unbounded capacity = S*k, which at prompt
        scale S = B*T would materialize O(S^2*E*k) dispatch tensors).

        The per-expert capacity budgets from the FULL prompt length
        (``total_len``; ADVICE r5) — a chunk of T tokens out of a
        total_len-token prompt gets ceil(k * B*total_len / E * cf)
        slots, the same number the full-context forward computes, so a
        small chunk is never squeezed into a spuriously tiny capacity.
        Single-chunk prefill (total_len == T) therefore routes
        bit-identically to the full-context forward.  Multi-chunk
        prefill shares the capacity NUMBER but not the competition:
        tokens only contend with their own chunk for expert slots, so
        when capacity binds a later chunk may keep tokens the
        full-context forward dropped (see docs/inference.md)."""
        import math

        from .. import ndarray as nd

        ctx = x.context
        B, T = x.shape[0], x.shape[1]
        total = int(total_len) if total_len is not None else T
        if total < T:
            raise ValueError(
                "prefill total_len %d < chunk length %d" % (total, T))
        k = int(self._top_k)
        if self._cf <= 0:
            capacity = None  # unbounded — switch_moe's own formula
        else:
            capacity = max(1, int(math.ceil(
                k * B * total / self._E * self._cf)))
        y, _ = nd.switch_moe(x, self.router_weight.data(ctx),
                             self.experts_w1.data(ctx),
                             self.experts_w2.data(ctx),
                             capacity_factor=self._cf,
                             activation=self._act, top_k=self._top_k,
                             capacity=capacity)
        return y


class MoEDecoderLayer(HybridBlock):
    """LlamaDecoderLayer with the SwiGLU FFN swapped for SwitchMoE
    (pre-RMSNorm residual structure preserved)."""

    remat_unit = True       # SPMDTrainer(remat=True): gluon/block.py

    def __init__(self, units, hidden_size, num_heads, num_kv_heads,
                 num_experts, capacity_factor=1.25, mesh=None,
                 return_aux=False, **kwargs):
        super().__init__(**kwargs)
        from .transformer import MultiHeadAttention, RMSNorm
        self._return_aux = return_aux
        with self.name_scope():
            self.attn_norm = RMSNorm(units, prefix="attn_norm_")
            self.attn = MultiHeadAttention(
                units, num_heads, num_kv_heads, use_rotary=True,
                causal=True, mesh=mesh, use_bias=False, prefix="attn_")
            self.ffn_norm = RMSNorm(units, prefix="ffn_norm_")
            self.moe = SwitchMoE(units, hidden_size, num_experts,
                                 capacity_factor, return_aux=return_aux,
                                 prefix="moe_")

    def hybrid_forward(self, F, x):
        x = x + self.attn(self.attn_norm(x))
        if self._return_aux:
            y, aux = self.moe(self.ffn_norm(x))
            return x + y, aux
        return x + self.moe(self.ffn_norm(x))

    def cached_forward(self, form, x, cache, *address, total_len=None,
                       **kw):
        """LlamaDecoderLayer.cached_forward with the routed FFN told
        what the form is doing.  A prefill form (PREFILL_FORMS) budgets
        the TRAINING capacity from the FULL prompt length
        (prefill_forward): bounded dispatch memory at prompt scale.
        Every other form runs capacity-unbounded (decode_forward), so
        inactive pool slots — which still flow through a pooled step
        with garbage activations — can never evict a live slot's token
        from an expert.  That unbounded capacity NUMBER is a function
        of the batch (S = B*W tokens), so a W-token verify window is
        not guaranteed to route bit-identically to W sequential steps:
        the serving engines opt MoE blocks OUT of speculation, linear
        and tree windows alike; the verify forms stay reachable for
        parity experiments and future capacity-pinned routing."""
        from .transformer import PREFILL_FORMS

        h, *cache = getattr(self.attn, form)(self.attn_norm(x), *cache,
                                             *address, **kw)
        x = x + h
        h = self.ffn_norm(x)
        if form not in PREFILL_FORMS:
            return x + self.moe.decode_forward(h), tuple(cache)
        if total_len is None:
            # this chunk taken as the prompt's LAST: exact for
            # single-chunk prefill and for the final chunk; earlier
            # chunks should pass the known full prompt length.
            # ``prefill`` has its start as a Python int; under
            # ``prefill_pages`` start_pos may be traced (one program
            # serves every chunk offset) and capacity is a SHAPE, so its
            # default holds for single-chunk ingestion only.
            start = address[0] if form == "prefill" and address else 0
            total_len = start + x.shape[1]
        return x + self.moe.prefill_forward(h, total_len=total_len), \
            tuple(cache)


def moe_sharding_rules(base=None):
    """Expert weights over "ep"; router replicated.  Compose with the
    transformer rules for tp x ep meshes."""
    out = ShardingRules([
        (r"experts_w1$", P("ep", None, None)),
        (r"experts_w2$", P("ep", None, None)),
    ])
    if base is not None:
        out.extend(base)
    return out
