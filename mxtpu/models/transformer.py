"""Transformer encoder (BERT) + decoder (Llama-style) model family.

Parity anchors: the reference's fused attention ops
(src/operator/contrib/transformer.cc — interleaved_matmul_selfatt_qk etc.,
the GluonNLP BERT path) define the encoder math; the decoder family is new
capability (SURVEY §2.3 lists TP/SP as absent upstream).

TPU design decisions:
- Batch-major (N, T, C) activations; fused single QKV projection so the MXU
  sees one large GEMM; fp32 softmax/norm accumulation inside bf16 compute.
- `mesh`-aware attention: with a DeviceMesh whose "sp" axis > 1, attention
  runs as ring attention (parallel/ring_attention.py) — exact,
  bandwidth-optimal over ICI; otherwise one dense fused attention.
- Sharding rules (Megatron layout) ship next to the models:
  `bert_sharding_rules()` / `transformer_lm_sharding_rules()` feed
  parallel.SPMDTrainer for tp/dp/sp execution.
"""

from __future__ import annotations

import math

from .. import ndarray as nd
from ..gluon import nn
from ..gluon.block import Block, HybridBlock
from ..ndarray import NDArray
from ..parallel.sharding import ShardingRules, PartitionSpec as P

__all__ = ["MultiHeadAttention", "TransformerEncoderLayer",
           "TransformerEncoder", "BERTModel", "bert_base",
           "LlamaDecoderLayer", "TransformerLM", "llama_tiny", "llama_3_8b",
           "transformer_lm_sharding_rules", "bert_sharding_rules"]


# -------------------------------------------------------- KV-cache leaves
# A cache leaf is either one float tensor (the original layout) or, with
# cache_dtype="int8", a (payload, scales) PAIR: int8 payload of the same
# shape plus a float32 per-head-per-position scale tensor (payload shape
# minus the trailing D axis).  The helpers below dispatch every cache
# read/write on the leaf form, so the attention math stays written once
# — quantized decode is the same program with a dequantize fused into
# the cache read and a quantize fused into the write.

def _q8cache(leaf):
    """True when a cache leaf is the quantized (payload, scales) pair."""
    return isinstance(leaf, tuple)


def _cache_fp(leaf):
    """Float view of a cache leaf for the attention contraction."""
    return nd._internal_cache_dequant(*leaf) if _q8cache(leaf) else leaf


def _payload(leaf):
    """The payload tensor of a leaf (shape/dtype carrier)."""
    return leaf[0] if _q8cache(leaf) else leaf


def _cache_write(leaf, new, pos):
    if _q8cache(leaf):
        return tuple(nd._internal_cache_write_q8(leaf[0], leaf[1], new,
                                                 pos=pos))
    return nd._internal_cache_write(leaf, new, pos=pos)


def _cache_write_rows(leaf, new, pos):
    if _q8cache(leaf):
        return tuple(nd._internal_cache_write_rows_q8(
            leaf[0], leaf[1], new, pos))
    return nd._internal_cache_write_rows(leaf, new, pos=pos)


def _cache_write_span(leaf, new, pos, valid_len):
    if _q8cache(leaf):
        return tuple(nd._internal_cache_write_span_q8(
            leaf[0], leaf[1], new, pos, valid_len))
    return nd._internal_cache_write_span(leaf, new, pos=pos,
                                         valid_len=valid_len)


def _cache_write_slot(leaf, slot_leaf, slot, pos=0):
    if _q8cache(leaf):
        return tuple(nd._internal_cache_write_slot_q8(
            leaf[0], leaf[1], slot_leaf[0], slot_leaf[1], slot=slot,
            pos=pos))
    return nd._internal_cache_write_slot(leaf, slot_leaf, slot=slot,
                                         pos=pos)


def _paged_write(leaf, new, table, start_pos=0):
    if _q8cache(leaf):
        return tuple(nd._paged_cache_write_q8(leaf[0], leaf[1], new,
                                              table, start_pos=start_pos))
    return nd._paged_cache_write(leaf, new, table, start_pos=start_pos)


def _paged_write_rows(leaf, new, tables, pos):
    if _q8cache(leaf):
        return tuple(nd._paged_cache_write_rows_q8(
            leaf[0], leaf[1], new, tables, pos))
    return nd._paged_cache_write_rows(leaf, new, tables, pos=pos)


def _paged_write_span(leaf, new, tables, pos, valid_len):
    if _q8cache(leaf):
        return tuple(nd._paged_cache_write_span_q8(
            leaf[0], leaf[1], new, tables, pos, valid_len))
    return nd._paged_cache_write_span(leaf, new, tables, pos=pos,
                                      valid_len=valid_len)


def _paged_gather(leaf, table):
    """Sequence-order float view of a paged cache leaf."""
    if _q8cache(leaf):
        return nd._paged_cache_gather_q8(leaf[0], leaf[1], table)
    return nd._paged_cache_gather(leaf, table)


def _page_copy(leaf, src, dst):
    """Copy-on-write page clone — payload AND scales for int8 leaves
    (the same axis-0 page copy applies to both)."""
    if _q8cache(leaf):
        return (nd._paged_block_copy(leaf[0], src=src, dst=dst),
                nd._paged_block_copy(leaf[1], src=src, dst=dst))
    return nd._paged_block_copy(leaf, src=src, dst=dst)


def _paged_kernel_attention(q, pool_k, pool_v, tables, pos, anc=None):
    """Route the paged cache read through the ragged Pallas kernel
    (ops/pallas/paged_attention — tri-state MXTPU_PALLAS_PAGED_ATTN,
    default on where the geometry guard passes); q is (B, H, W, D)
    post-rope, returns (B, H, W, D).  ``anc`` (B, W) int32 swaps the
    triangular W-window mask for the tree ancestor bitmask."""
    if _q8cache(pool_k):
        return nd.paged_decode_attention(
            q, pool_k[0], pool_v[0], tables, pos,
            k_scales=pool_k[1], v_scales=pool_v[1], anc=anc)
    return nd.paged_decode_attention(q, pool_k, pool_v, tables, pos,
                                     anc=anc)


def _paged_prefill_kernel(q, pool_k, pool_v, table, start_pos):
    """Route chunked prefill through the Pallas chunked-prefill kernel
    (ops/pallas/prefill_attention); q is (1, H, T, D) post-rope,
    returns (1, H, T, D) without gathering the full K/V rows."""
    if _q8cache(pool_k):
        return nd.paged_prefill_attention(
            q, pool_k[0], pool_v[0], table, start_pos,
            k_scales=pool_k[1], v_scales=pool_v[1])
    return nd.paged_prefill_attention(q, pool_k, pool_v, table, start_pos)


def _leaf_geometry(pool_k):
    """(D, block_size, pool_dtype) of a paged cache leaf for the kernel
    gates — geometry is static, so the gate verdict is trace-stable."""
    p = _payload(pool_k)
    dt = "int8" if _q8cache(pool_k) else str(p.dtype)
    return int(p.shape[-1]), int(p.shape[-2]), dt


def _paged_attn_on(pool_k=None):
    from ..ops.pallas.paged_attention import paged_attention_enabled
    if pool_k is None:
        return paged_attention_enabled()
    D, bs, dt = _leaf_geometry(pool_k)
    return paged_attention_enabled(D=D, block_size=bs, pool_dtype=dt)


def _paged_prefill_on(pool_k, T, rep, q_dtype):
    from ..ops.pallas.prefill_attention import paged_prefill_enabled
    D, bs, dt = _leaf_geometry(pool_k)
    return paged_prefill_enabled(D=D, block_size=bs, pool_dtype=dt,
                                 T=int(T), rep=int(rep),
                                 q_dtype=str(q_dtype))


class RMSNorm(HybridBlock):
    def __init__(self, units, eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        self._eps = eps
        self.weight = self.params.get("weight", shape=(units,), init="ones")

    def hybrid_forward(self, F, x, weight):
        return F.rms_norm(x, weight, eps=self._eps)


class MultiHeadAttention(HybridBlock):
    """Self-attention with fused QKV, optional GQA/rotary/causal/ring.

    mesh + seq-parallel: when `mesh` has sp>1, the score/value contraction
    runs as ring attention over the "sp" axis (inside the enclosing jit).
    """

    def __init__(self, units, num_heads, num_kv_heads=None, dropout=0.0,
                 use_rotary=False, causal=False, mesh=None, use_bias=True,
                 use_flash=True, **kwargs):
        super().__init__(**kwargs)
        assert units % num_heads == 0
        self._units = units
        self._heads = num_heads
        self._kv_heads = num_kv_heads or num_heads
        assert num_heads % self._kv_heads == 0
        self._head_dim = units // num_heads
        self._dropout = dropout
        self._rotary = use_rotary
        self._causal = causal
        self._mesh = mesh
        self._use_flash = use_flash
        with self.name_scope():
            qkv_units = units + 2 * self._kv_heads * self._head_dim
            self.qkv = nn.Dense(qkv_units, use_bias=use_bias, flatten=False,
                                prefix="qkv_")
            self.out_proj = nn.Dense(units, use_bias=use_bias, flatten=False,
                                     in_units=units, prefix="out_")
            if dropout:
                self.drop = nn.Dropout(dropout)

    def _ring_active(self):
        return self._mesh is not None and self._mesh.size("sp") > 1

    def hybrid_forward(self, F, x, mask=None):
        B, T, _ = x.shape
        H, KV, D = self._heads, self._kv_heads, self._head_dim
        qkv = self.qkv(x)  # (B, T, (H+2KV)*D) — one MXU GEMM
        q = qkv[:, :, :H * D].reshape(B, T, H, D).transpose((0, 2, 1, 3))
        k = qkv[:, :, H * D:(H + KV) * D].reshape(
            B, T, KV, D).transpose((0, 2, 1, 3))
        v = qkv[:, :, (H + KV) * D:].reshape(
            B, T, KV, D).transpose((0, 2, 1, 3))
        if self._rotary:
            q = F.rope(q)
            k = F.rope(k)
        if KV != H:  # GQA: repeat kv heads
            rep = H // KV
            k = F.repeat(k, repeats=rep, axis=1)
            v = F.repeat(v, repeats=rep, axis=1)

        from .. import autograd as _ag
        attn_dropout = self._dropout and _ag.is_training()
        if self._ring_active():
            if mask is not None:
                raise NotImplementedError(
                    "ring attention (sp>1) does not support attention "
                    "masks yet — pad-free packing or causal only; run with "
                    "sp=1 for masked attention")
            out = F.ring_attention(q, k, v, causal=self._causal,
                                   _mesh=self._mesh)
        elif self._use_flash and mask is None and not attn_dropout:
            # Pallas streaming kernel: O(T·D) HBM traffic
            out = F.flash_attention(q, k, v, causal=self._causal)
        else:
            scores = F.batch_dot_attn(q, k) / math.sqrt(D)  # (B,H,T,T)
            if self._causal:
                scores = F.causal_mask_fill(scores)
            attn = F.masked_softmax(scores, mask=mask, axis=-1)
            if self._dropout:
                attn = self.drop(attn)
            out = F.attn_value(attn, v)  # (B,H,T,D)
        out = out.transpose((0, 2, 1, 3)).reshape(B, T, H * D)
        return self.out_proj(out)

    # -- KV-cache incremental decode -----------------------------------
    def init_cache(self, batch_size, max_length, dtype="float32"):
        """Static-size KV cache: (B, KV_heads, T_max, D) per tensor.  The
        fixed shape is deliberate — every decode step reuses one compiled
        program instead of recompiling per sequence length.

        ``dtype="int8"`` returns the QUANTIZED layout instead: each leaf
        is an (int8 payload, float32 (B, KV, T_max) scales) pair — half
        the cache bytes plus one scale per head per position (docs/
        inference.md "Quantized serving")."""
        KV, D = self._kv_heads, self._head_dim
        shape = (batch_size, KV, max_length, D)
        if str(dtype) == "int8":
            def leaf():
                return (nd.zeros(shape, dtype="int8"),
                        nd.zeros(shape[:-1], dtype="float32"))
            return (leaf(), leaf())
        return (nd.zeros(shape, dtype=dtype), nd.zeros(shape, dtype=dtype))

    def step(self, x, cache_k, cache_v, pos):
        """One-token decode: x (B, 1, C) → (out (B, 1, C), new_k, new_v).

        Attends the single query against the full static cache with a
        position-validity mask, so kernels see fixed shapes at every step.
        """
        B = x.shape[0]
        H, KV, D = self._heads, self._kv_heads, self._head_dim
        Tmax = _payload(cache_k).shape[2]
        qkv = self.qkv(x)  # (B, 1, (H+2KV)*D)
        q = qkv[:, :, :H * D].reshape(B, 1, H, D).transpose((0, 2, 1, 3))
        k = qkv[:, :, H * D:(H + KV) * D].reshape(
            B, 1, KV, D).transpose((0, 2, 1, 3))
        v = qkv[:, :, (H + KV) * D:].reshape(
            B, 1, KV, D).transpose((0, 2, 1, 3))
        if self._rotary:
            q = nd.rope(q, offset=pos)
            k = nd.rope(k, offset=pos)
        # dynamic_update_slice write: pos may be a python int (eager
        # generate) or a traced scalar (ShardedDecoder's single compiled
        # step for every position)
        cache_k = _cache_write(cache_k, k, pos)
        cache_v = _cache_write(cache_v, v, pos)
        # GQA without materializing repeated caches: fold the rep axis
        # into the query rows and contract against the UNrepeated cache
        # (decode is bandwidth-bound; nd.repeat would copy the whole
        # cache 4x per token for the 32/8-head geometry).  q head
        # h = kv*rep + r matches hybrid_forward's nd.repeat(axis=1)
        # interleaving.
        rep = H // KV
        q_r = q.reshape(B * KV, rep, D)            # (B*KV, rep, D)
        keys = _cache_fp(cache_k).reshape(B * KV, Tmax, D)
        values = _cache_fp(cache_v).reshape(B * KV, Tmax, D)
        scores = nd.batch_dot(q_r, keys,
                              transpose_b=True) / math.sqrt(D)
        valid = nd.arange(0, Tmax) <= pos  # causal+occupancy in one mask
        attn = nd.masked_softmax(
            scores, mask=valid.reshape((1, 1, Tmax)).astype("bool"))
        out = nd.batch_dot(attn, values)           # (B*KV, rep, D)
        out = out.reshape(B, 1, H * D)
        return self.out_proj(out), cache_k, cache_v

    def step_slots(self, x, cache_k, cache_v, pos):
        """One-token decode with PER-ROW positions: x (B, 1, C), pos
        (B,) int vector — row b writes its K/V at position pos[b] and
        attends under its own causal/occupancy mask.  This is the
        continuous-batching form of step(): every pool slot sits at its
        own sequence depth, yet the program keeps fixed shapes so ONE
        compiled step serves every position combination."""
        B = x.shape[0]
        H, KV, D = self._heads, self._kv_heads, self._head_dim
        Tmax = _payload(cache_k).shape[2]
        qkv = self.qkv(x)  # (B, 1, (H+2KV)*D)
        q = qkv[:, :, :H * D].reshape(B, 1, H, D).transpose((0, 2, 1, 3))
        k = qkv[:, :, H * D:(H + KV) * D].reshape(
            B, 1, KV, D).transpose((0, 2, 1, 3))
        v = qkv[:, :, (H + KV) * D:].reshape(
            B, 1, KV, D).transpose((0, 2, 1, 3))
        if self._rotary:
            q = nd.rope(q, offset=pos)  # (B,) offset: per-row rotation
            k = nd.rope(k, offset=pos)
        cache_k = _cache_write_rows(cache_k, k, pos)
        cache_v = _cache_write_rows(cache_v, v, pos)
        # same GQA fold as step(); the validity mask is per-ROW here
        rep = H // KV
        q_r = q.reshape(B * KV, rep, D)            # (B*KV, rep, D)
        keys = _cache_fp(cache_k).reshape(B * KV, Tmax, D)
        values = _cache_fp(cache_v).reshape(B * KV, Tmax, D)
        scores = nd.batch_dot(q_r, keys,
                              transpose_b=True) / math.sqrt(D)
        valid = (nd.arange(0, Tmax).reshape((1, Tmax))
                 <= pos.reshape((B, 1)))           # (B, Tmax)
        attn = nd.masked_softmax(
            scores.reshape(B, KV, rep, Tmax),
            mask=valid.reshape((B, 1, 1, Tmax)).astype("bool"))
        out = nd.batch_dot(attn.reshape(B * KV, rep, Tmax), values)
        out = out.reshape(B, 1, H * D)
        return self.out_proj(out), cache_k, cache_v

    def verify_slots(self, x, cache_k, cache_v, pos, valid_len,
                     tree=None):
        """Batched speculative verification: x (B, W, C) is a window of
        W candidate tokens per row — the last sampled token followed by
        W-1 drafts — with row b's window starting at its own cache
        position ``pos[b]``.  All W positions' K/V are written in one
        scatter (first ``valid_len[b]`` lanes; the rest drop — see
        _internal_cache_write_span) and all W queries attend the cache
        in ONE read: query w of row b sees positions <= pos[b]+w.  By
        construction this is step_slots() run W times with the loop
        folded into the batch axis — same projections, same masked
        softmax extent per query, same GQA fold — so the logits at
        window index w are bit-identical to the sequential step's
        (probe-verified on this XLA build; asserted stream-level in
        tests/test_speculative.py).  Rejected lanes simply roll the
        host position back: their writes sit beyond every validity
        mask until sequential re-writes overtake them.

        ``tree=(perm, depth)`` generalizes the window from a chain to a
        draft TREE (TreeDrafter): lane w sits at tree depth
        ``depth[b, w]`` with ancestor-lane chain ``perm[b, w, :]`` (pad
        = w), its K/V still lands at cache position pos[b]+w (lane
        order) but ropes at pos[b]+depth[b, w], and the attention read
        permutes each lane's window columns into its own path order so
        the masked softmax + contraction see exactly the sequential
        step's arrangement — a per-lane ANCESTOR mask in one pooled
        cache read (see _internal_tree_verify_attn).  A linear chain
        (perm[b, w, i] = min(i, w), depth = arange) reproduces this
        method's chain form exactly."""
        B, W, _ = x.shape
        H, KV, D = self._heads, self._kv_heads, self._head_dim
        Tmax = _payload(cache_k).shape[2]
        qkv = self.qkv(x)  # (B, W, (H+2KV)*D)
        q = qkv[:, :, :H * D].reshape(B, W, H, D).transpose((0, 2, 1, 3))
        k = qkv[:, :, H * D:(H + KV) * D].reshape(
            B, W, KV, D).transpose((0, 2, 1, 3))
        v = qkv[:, :, (H + KV) * D:].reshape(
            B, W, KV, D).transpose((0, 2, 1, 3))
        if self._rotary:
            if tree is not None:
                # absolute per-lane positions: lane w rotates at its
                # TREE depth, not its window index
                off = pos.reshape((B, 1)) + tree[1]          # (B, W)
                q = nd.rope(q, offset=off)
                k = nd.rope(k, offset=off)
            else:
                q = nd.rope(q, offset=pos)  # (B,) offset + window arange
                k = nd.rope(k, offset=pos)
        cache_k = _cache_write_span(cache_k, k, pos, valid_len)
        cache_v = _cache_write_span(cache_v, v, pos, valid_len)
        # the step_slots GQA fold with W queries; validity is per-row
        # AND per-window-index: query w sees keys <= pos[b]+w
        rep = H // KV
        q_r = q.reshape(B * KV, rep * W, D)
        keys = _cache_fp(cache_k).reshape(B * KV, Tmax, D)
        values = _cache_fp(cache_v).reshape(B * KV, Tmax, D)
        scores = nd.batch_dot(q_r, keys,
                              transpose_b=True) / math.sqrt(D)
        if tree is not None:
            out = nd._internal_tree_verify_attn(
                scores, values, pos, tree[0], tree[1], rep=rep)
            return self.out_proj(out), cache_k, cache_v
        valid = (nd.arange(0, Tmax).reshape((1, 1, Tmax))
                 <= (pos.reshape((B, 1)) + nd.arange(0, W).reshape(
                     (1, W))).reshape((B, W, 1)))  # (B, W, Tmax)
        attn = nd.masked_softmax(
            scores.reshape(B, KV, rep, W, Tmax),
            mask=valid.reshape((B, 1, 1, W, Tmax)).astype("bool"))
        out = nd.batch_dot(attn.reshape(B * KV, rep * W, Tmax), values)
        out = out.reshape(B, KV, rep, W, D).transpose(
            (0, 3, 1, 2, 4)).reshape(B, W, H * D)
        return self.out_proj(out), cache_k, cache_v

    def _fused_q8_epilogue_on(self, pool_v):
        """int8-weights × int8-KV fused-epilogue eligibility: an int8
        QuantizedDense qkv projection feeding an int8 paged cache with
        the Pallas read on.  When eligible, the V projection emits
        quantized rows directly (wq_matmul_i8_q8) and the kernel
        dequantizes them in VMEM — neither a float weight copy nor a
        dequantized cache row materializes between projection and
        attention."""
        if not _q8cache(pool_v) or not _paged_attn_on(pool_v):
            return False
        try:
            from ..contrib.quantization import QuantizedDense
        except ImportError:  # pragma: no cover - contrib always ships
            return False
        return (isinstance(self.qkv, QuantizedDense)
                and getattr(self.qkv, "_bits", 0) == 8)

    def _project_qkv_fused_q8(self, x):
        """Split the fused int8 qkv projection at the V boundary: q/k
        rows come out float (rope still applies to them), V rows come
        out as an (int8 payload, scales) pair straight from the matmul
        epilogue.  Bit-identical to the unfused wq_matmul_i8 +
        quantize-on-write path because each output row's contraction
        and _q8_quantize math are unchanged by the row split."""
        H, KV, D = self._heads, self._kv_heads, self._head_dim
        cut = (H + KV) * D
        w = self.qkv.weight.data()
        s = self.qkv.wscale.data()
        b = None if self.qkv.bias is None else self.qkv.bias.data()
        qk = nd.wq_matmul_i8(x, w[:cut], s[:cut],
                             None if b is None else b[:cut],
                             flatten=self.qkv._flatten,
                             no_bias=b is None)
        vq, vs = nd.wq_matmul_i8_q8(x, w[cut:], s[cut:],
                                    None if b is None else b[cut:],
                                    head_dim=D,
                                    flatten=self.qkv._flatten,
                                    no_bias=b is None)
        return qk, vq, vs

    def verify_pages(self, x, pool_k, pool_v, tables, pos, valid_len,
                     tree=None):
        """Batched speculative verification over the BLOCK-PAGED pool —
        verify_slots() with the cache read/write routed through the
        per-row block tables (gather into sequence order, then exactly
        the same math on the same shapes).  Invalid window lanes write
        the null page; rejected lanes need only a host position
        roll-back, never a page operation (every page the window can
        touch was allocated at admission).

        ``tree=(perm, depth, anc)`` is the draft-TREE window (see
        verify_slots): the XLA path permutes window columns per lane
        through ``perm``/``depth``; the Pallas kernel path instead
        consumes ``anc`` (B, W) int32 — bit j of anc[b, w] marks window
        lane j an ancestor-or-self of lane w — via scalar prefetch,
        swapping its triangular W-window mask for the ancestor bitmask
        while the block-table walk (and its O(valid pages) HBM
        traffic) stays untouched."""
        B, W, _ = x.shape
        H, KV, D = self._heads, self._kv_heads, self._head_dim
        Tmax = tables.shape[1] * _payload(pool_k).shape[2]
        fused = self._fused_q8_epilogue_on(pool_v)
        if fused:
            qk, vq, vs = self._project_qkv_fused_q8(x)
            q = qk[:, :, :H * D].reshape(
                B, W, H, D).transpose((0, 2, 1, 3))
            k = qk[:, :, H * D:].reshape(
                B, W, KV, D).transpose((0, 2, 1, 3))
        else:
            qkv = self.qkv(x)
            q = qkv[:, :, :H * D].reshape(
                B, W, H, D).transpose((0, 2, 1, 3))
            k = qkv[:, :, H * D:(H + KV) * D].reshape(
                B, W, KV, D).transpose((0, 2, 1, 3))
            v = qkv[:, :, (H + KV) * D:].reshape(
                B, W, KV, D).transpose((0, 2, 1, 3))
        if self._rotary:
            if tree is not None:
                off = pos.reshape((B, 1)) + tree[1]          # (B, W)
                q = nd.rope(q, offset=off)
                k = nd.rope(k, offset=off)
            else:
                q = nd.rope(q, offset=pos)
                k = nd.rope(k, offset=pos)
        pool_k = _paged_write_span(pool_k, k, tables, pos, valid_len)
        if fused:
            # V rows land pre-quantized — no float V tensor exists
            pool_v = tuple(nd._paged_cache_write_span_pre_q8(
                pool_v[0], pool_v[1],
                vq.reshape(B, W, KV, D).transpose((0, 2, 1, 3)),
                vs.transpose((0, 2, 1)), tables, pos, valid_len))
        else:
            pool_v = _paged_write_span(pool_v, v, tables, pos, valid_len)
        if _paged_attn_on(pool_k):
            # ragged Pallas kernel: walk each row's block table, read
            # only valid rows; per-lane causal extent pos[b]+w, or the
            # ancestor bitmask for tree windows
            out = _paged_kernel_attention(
                q, pool_k, pool_v, tables, pos,
                anc=None if tree is None else tree[2])        # (B,H,W,D)
            out = out.transpose((0, 2, 1, 3)).reshape(B, W, H * D)
            return self.out_proj(out), pool_k, pool_v
        keys = _paged_gather(pool_k, tables).reshape(
            B * KV, Tmax, D)
        values = _paged_gather(pool_v, tables).reshape(
            B * KV, Tmax, D)
        rep = H // KV
        q_r = q.reshape(B * KV, rep * W, D)
        scores = nd.batch_dot(q_r, keys,
                              transpose_b=True) / math.sqrt(D)
        if tree is not None:
            out = nd._internal_tree_verify_attn(
                scores, values, pos, tree[0], tree[1], rep=rep)
            return self.out_proj(out), pool_k, pool_v
        valid = (nd.arange(0, Tmax).reshape((1, 1, Tmax))
                 <= (pos.reshape((B, 1)) + nd.arange(0, W).reshape(
                     (1, W))).reshape((B, W, 1)))  # (B, W, Tmax)
        attn = nd.masked_softmax(
            scores.reshape(B, KV, rep, W, Tmax),
            mask=valid.reshape((B, 1, 1, W, Tmax)).astype("bool"))
        out = nd.batch_dot(attn.reshape(B * KV, rep * W, Tmax), values)
        out = out.reshape(B, KV, rep, W, D).transpose(
            (0, 3, 1, 2, 4)).reshape(B, W, H * D)
        return self.out_proj(out), pool_k, pool_v

    def init_block_pool(self, num_blocks, block_size, dtype="float32"):
        """Block-paged KV cache: (num_blocks, KV_heads, block_size, D)
        per tensor — the pool the continuous-batching engine's block
        tables index into.  Like init_cache, the fixed shape is the
        point: one compiled program serves every table content.

        ``dtype="int8"`` stores each pool as an (int8 payload, float32
        (num_blocks, KV, block_size) scales) pair — the paged form of
        the quantized cache (scales live page-aligned beside their
        payload pages, so allocation/sharing/COW stay page-granular)."""
        KV, D = self._kv_heads, self._head_dim
        shape = (num_blocks, KV, block_size, D)
        if str(dtype) == "int8":
            def leaf():
                return (nd.zeros(shape, dtype="int8"),
                        nd.zeros(shape[:-1], dtype="float32"))
            return (leaf(), leaf())
        return (nd.zeros(shape, dtype=dtype), nd.zeros(shape, dtype=dtype))

    def step_pages(self, x, pool_k, pool_v, tables, pos):
        """One-token decode over the BLOCK-PAGED pool: x (B, 1, C),
        ``tables`` (B, M) int32 block tables, ``pos`` (B,) per-row
        positions.  Row b writes its K/V at logical position pos[b]
        through its table and attends its own gathered [0, pos[b]]
        prefix — the paged form of step_slots(): the gather reproduces
        the contiguous cache bit-for-bit, so everything downstream is
        the same math on the same shapes."""
        B = x.shape[0]
        H, KV, D = self._heads, self._kv_heads, self._head_dim
        Tmax = tables.shape[1] * _payload(pool_k).shape[2]
        fused = self._fused_q8_epilogue_on(pool_v)
        if fused:
            qk, vq, vs = self._project_qkv_fused_q8(x)
            q = qk[:, :, :H * D].reshape(
                B, 1, H, D).transpose((0, 2, 1, 3))
            k = qk[:, :, H * D:].reshape(
                B, 1, KV, D).transpose((0, 2, 1, 3))
        else:
            qkv = self.qkv(x)  # (B, 1, (H+2KV)*D)
            q = qkv[:, :, :H * D].reshape(
                B, 1, H, D).transpose((0, 2, 1, 3))
            k = qkv[:, :, H * D:(H + KV) * D].reshape(
                B, 1, KV, D).transpose((0, 2, 1, 3))
            v = qkv[:, :, (H + KV) * D:].reshape(
                B, 1, KV, D).transpose((0, 2, 1, 3))
        if self._rotary:
            q = nd.rope(q, offset=pos)  # (B,) offset: per-row rotation
            k = nd.rope(k, offset=pos)
        pool_k = _paged_write_rows(pool_k, k, tables, pos)
        if fused:
            # V rows land pre-quantized — no float V tensor exists
            pool_v = tuple(nd._paged_cache_write_rows_pre_q8(
                pool_v[0], pool_v[1],
                vq.reshape(B, 1, KV, D).transpose((0, 2, 1, 3)),
                vs.transpose((0, 2, 1)), tables, pos))
        else:
            pool_v = _paged_write_rows(pool_v, v, tables, pos)
        if _paged_attn_on(pool_k):
            # ragged Pallas kernel replaces the gather+softmax read:
            # each (slot, kv-head) walks its own block-table chain and
            # touches only rows <= pos[b] (docs/inference.md)
            out = _paged_kernel_attention(q, pool_k, pool_v, tables,
                                          pos)                # (B,H,1,D)
            out = out.transpose((0, 2, 1, 3)).reshape(B, 1, H * D)
            return self.out_proj(out), pool_k, pool_v
        # gather the pages into sequence order, then the step_slots math
        keys = _paged_gather(pool_k, tables).reshape(
            B * KV, Tmax, D)
        values = _paged_gather(pool_v, tables).reshape(
            B * KV, Tmax, D)
        rep = H // KV
        q_r = q.reshape(B * KV, rep, D)            # (B*KV, rep, D)
        scores = nd.batch_dot(q_r, keys,
                              transpose_b=True) / math.sqrt(D)
        valid = (nd.arange(0, Tmax).reshape((1, Tmax))
                 <= pos.reshape((B, 1)))           # (B, Tmax)
        attn = nd.masked_softmax(
            scores.reshape(B, KV, rep, Tmax),
            mask=valid.reshape((B, 1, 1, Tmax)).astype("bool"))
        out = nd.batch_dot(attn.reshape(B * KV, rep, Tmax), values)
        out = out.reshape(B, 1, H * D)
        return self.out_proj(out), pool_k, pool_v

    def prefill_pages(self, x, pool_k, pool_v, table, start_pos=0):
        """Chunked prompt ingestion through the paged pool: x (1, T, C)
        is ONE chunk at logical positions [start_pos, start_pos+T); its
        K/V scatter through ``table`` (M,) and the chunk's queries
        attend the gathered table extent (shared prefix pages, earlier
        chunks, and the chunk itself) under the same causal mask as
        prefill() — bit-identical to a contiguous single-pass prefill,
        which is what lets prefix sharing SKIP the shared tokens
        entirely."""
        B, T, _ = x.shape
        H, KV, D = self._heads, self._kv_heads, self._head_dim
        Tmax = table.shape[-1] * _payload(pool_k).shape[2]
        qkv = self.qkv(x)
        q = qkv[:, :, :H * D].reshape(B, T, H, D).transpose((0, 2, 1, 3))
        k = qkv[:, :, H * D:(H + KV) * D].reshape(
            B, T, KV, D).transpose((0, 2, 1, 3))
        v = qkv[:, :, (H + KV) * D:].reshape(
            B, T, KV, D).transpose((0, 2, 1, 3))
        if self._rotary:
            q = nd.rope(q, offset=start_pos)
            k = nd.rope(k, offset=start_pos)
        pool_k = _paged_write(pool_k, k, table, start_pos=start_pos)
        pool_v = _paged_write(pool_v, v, table, start_pos=start_pos)
        rep = H // KV
        if _paged_prefill_on(pool_k, T, rep, q.dtype):
            # Pallas chunked-prefill kernel: scalar-prefetched block-
            # table walk with online-softmax carry across chunk tiles —
            # the full (Tmax, D) K/V rows are never materialized
            out = _paged_prefill_kernel(q, pool_k, pool_v, table,
                                        start_pos)          # (B,H,T,D)
            out = out.transpose((0, 2, 1, 3)).reshape(B, T, H * D)
            return self.out_proj(out), pool_k, pool_v
        keys = _paged_gather(pool_k, table).reshape(
            B * KV, Tmax, D)
        values = _paged_gather(pool_v, table).reshape(
            B * KV, Tmax, D)
        q_r = q.reshape(B * KV, rep * T, D)
        scores = nd.batch_dot(q_r, keys,
                              transpose_b=True) / math.sqrt(D)
        # query at sequence position start_pos+t sees keys <= its own
        valid = (nd.arange(0, Tmax).reshape((1, Tmax))
                 <= (nd.arange(0, T) + start_pos).reshape((T, 1)))
        mask = valid.reshape((1, 1, T, Tmax)).astype("bool")
        attn = nd.masked_softmax(
            scores.reshape(B * KV, rep, T, Tmax), mask=mask)
        out = nd.batch_dot(attn.reshape(B * KV, rep * T, Tmax), values)
        out = out.reshape(B, KV, rep, T, D).transpose(
            (0, 3, 1, 2, 4)).reshape(B, T, H * D)
        return self.out_proj(out), pool_k, pool_v

    def prefill(self, x, cache_k, cache_v, start_pos=0):
        """Process T tokens in ONE batched pass (vs T serial step()
        calls): computes their K/V, writes the cache block at
        [start_pos, start_pos+T), and returns causal attention outputs.
        This is the standard chunked-prefill split — prompt ingestion is
        compute-bound and belongs on the MXU as big matmuls; the serial
        step() is only for the bandwidth-bound token-by-token phase.

        x (B, T, C) -> (out (B, T, C), new_k, new_v).  Like step(),
        functional: thread the returned caches forward."""
        B, T, _ = x.shape
        H, KV, D = self._heads, self._kv_heads, self._head_dim
        Tmax = _payload(cache_k).shape[2]
        qkv = self.qkv(x)
        q = qkv[:, :, :H * D].reshape(B, T, H, D).transpose((0, 2, 1, 3))
        k = qkv[:, :, H * D:(H + KV) * D].reshape(
            B, T, KV, D).transpose((0, 2, 1, 3))
        v = qkv[:, :, (H + KV) * D:].reshape(
            B, T, KV, D).transpose((0, 2, 1, 3))
        if self._rotary:
            q = nd.rope(q, offset=start_pos)
            k = nd.rope(k, offset=start_pos)
        cache_k = _cache_write(cache_k, k, start_pos)
        cache_v = _cache_write(cache_v, v, start_pos)
        # GQA over the UNrepeated cache (same fold as step(): q head
        # h = kv*rep + r, kv-major — matches hybrid_forward's repeat)
        rep = H // KV
        q_r = q.reshape(B * KV, rep * T, D)
        keys = _cache_fp(cache_k).reshape(B * KV, Tmax, D)
        values = _cache_fp(cache_v).reshape(B * KV, Tmax, D)
        scores = nd.batch_dot(q_r, keys,
                              transpose_b=True) / math.sqrt(D)
        # query at sequence position start_pos+t sees keys <= its own
        valid = (nd.arange(0, Tmax).reshape((1, Tmax))
                 <= (nd.arange(0, T) + start_pos).reshape((T, 1)))
        mask = valid.reshape((1, 1, T, Tmax)).astype("bool")
        attn = nd.masked_softmax(
            scores.reshape(B * KV, rep, T, Tmax), mask=mask)
        out = nd.batch_dot(attn.reshape(B * KV, rep * T, Tmax), values)
        out = out.reshape(B, KV, rep, T, D).transpose(
            (0, 3, 1, 2, 4)).reshape(B, T, H * D)
        return self.out_proj(out), cache_k, cache_v


class TransformerEncoderLayer(HybridBlock):
    """Pre-LN encoder block (BERT uses post-LN originally; pre-LN is the
    numerically stable modern default — `post_ln=True` restores parity)."""

    remat_unit = True       # SPMDTrainer(remat=True): gluon/block.py

    def __init__(self, units, hidden_size, num_heads, dropout=0.1,
                 activation="gelu", post_ln=True, mesh=None, **kwargs):
        super().__init__(**kwargs)
        self._post_ln = post_ln
        with self.name_scope():
            self.attn = MultiHeadAttention(units, num_heads, dropout=dropout,
                                           mesh=mesh, prefix="attn_")
            self.ln1 = nn.LayerNorm(in_channels=units)
            self.ffn1 = nn.Dense(hidden_size, flatten=False,
                                 activation=None, prefix="ffn1_")
            self.ffn2 = nn.Dense(units, flatten=False, in_units=hidden_size,
                                 prefix="ffn2_")
            self.ln2 = nn.LayerNorm(in_channels=units)
            self.drop = nn.Dropout(dropout) if dropout else None
            self._act = activation

    def hybrid_forward(self, F, x, mask=None):
        if self._post_ln:
            h = self.attn(x, mask)
            if self.drop:
                h = self.drop(h)
            x = self.ln1(x + h)
            h = self.ffn2(F.gelu_tanh(self.ffn1(x)))
            if self.drop:
                h = self.drop(h)
            return self.ln2(x + h)
        h = self.attn(self.ln1(x), mask)
        if self.drop:
            h = self.drop(h)
        x = x + h
        h = self.ffn2(F.gelu_tanh(self.ffn1(self.ln2(x))))
        if self.drop:
            h = self.drop(h)
        return x + h


class TransformerEncoder(HybridBlock):
    def __init__(self, num_layers, units, hidden_size, num_heads,
                 dropout=0.1, mesh=None, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.layers = nn.HybridSequential(prefix="layers_")
            for i in range(num_layers):
                self.layers.add(TransformerEncoderLayer(
                    units, hidden_size, num_heads, dropout, mesh=mesh,
                    prefix="layer%d_" % i))

    def hybrid_forward(self, F, x, mask=None):
        for layer in self.layers:
            x = layer(x, mask)
        return x


class BERTModel(HybridBlock):
    """BERT encoder with token/segment/position embeddings, pooler and MLM
    head (parity: GluonNLP BERTModel over the reference's fused MHA ops;
    north-star config 3)."""

    def __init__(self, vocab_size=30522, units=768, hidden_size=3072,
                 num_layers=12, num_heads=12, max_length=512,
                 num_segments=2, dropout=0.1, mesh=None, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        with self.name_scope():
            self.word_embed = nn.Embedding(vocab_size, units,
                                           prefix="word_embed_")
            self.segment_embed = nn.Embedding(num_segments, units,
                                              prefix="segment_embed_")
            self.position_embed = nn.Embedding(max_length, units,
                                               prefix="position_embed_")
            self.embed_ln = nn.LayerNorm(in_channels=units)
            self.embed_drop = nn.Dropout(dropout) if dropout else None
            self.encoder = TransformerEncoder(num_layers, units, hidden_size,
                                              num_heads, dropout, mesh=mesh,
                                              prefix="encoder_")
            self.pooler = nn.Dense(units, activation="tanh", in_units=units,
                                   prefix="pooler_")
            self.mlm_decoder = nn.Dense(vocab_size, flatten=False,
                                        in_units=units, prefix="mlm_")

    def hybrid_forward(self, F, token_ids, segment_ids=None, mask=None):
        B, T = token_ids.shape
        emb = self.word_embed(token_ids)
        if segment_ids is not None:
            emb = emb + self.segment_embed(segment_ids)
        pos = F.arange_like(token_ids, axis=1).astype("int32")
        emb = emb + self.position_embed(pos).reshape((1, T, self._units))
        emb = self.embed_ln(emb)
        if self.embed_drop:
            emb = self.embed_drop(emb)
        seq = self.encoder(emb, mask)
        pooled = self.pooler(seq[:, 0])
        mlm = self.mlm_decoder(seq)
        return seq, pooled, mlm


def bert_base(**kwargs):
    return BERTModel(units=768, hidden_size=3072, num_layers=12,
                     num_heads=12, **kwargs)


# ------------------------------------------------------------- decoder side

class LlamaDecoderLayer(HybridBlock):
    """Pre-RMSNorm decoder block: GQA attention with rotary + SwiGLU FFN."""

    remat_unit = True       # SPMDTrainer(remat=True): gluon/block.py

    def __init__(self, units, hidden_size, num_heads, num_kv_heads,
                 mesh=None, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.attn_norm = RMSNorm(units, prefix="attn_norm_")
            self.attn = MultiHeadAttention(
                units, num_heads, num_kv_heads, use_rotary=True, causal=True,
                mesh=mesh, use_bias=False, prefix="attn_")
            self.ffn_norm = RMSNorm(units, prefix="ffn_norm_")
            self.gate_proj = nn.Dense(hidden_size, use_bias=False,
                                      flatten=False, prefix="gate_")
            self.up_proj = nn.Dense(hidden_size, use_bias=False,
                                    flatten=False, prefix="up_")
            self.down_proj = nn.Dense(units, use_bias=False, flatten=False,
                                      in_units=hidden_size, prefix="down_")

    def hybrid_forward(self, F, x):
        x = x + self.attn(self.attn_norm(x))
        h = self.ffn_norm(x)
        h = self.down_proj(F.swish(self.gate_proj(h)) * self.up_proj(h))
        return x + h

    def cached_forward(self, form, x, cache, *address, total_len=None,
                       **kw):
        """This layer over a cache — hybrid_forward's residual structure
        with the sequence mixer reading and writing ``cache`` (its own
        leaves) through ``form``, one of CACHE_FORMS, at ``address``
        (what that form takes after the leaves).  Returns (x, new
        leaves).  The SwiGLU FFN is per-token: no form changes it, and
        ``total_len`` (routed layers' capacity, see MoEDecoderLayer) is
        accepted and ignored so a model threads it uniformly."""
        h, *cache = getattr(self.attn, form)(self.attn_norm(x), *cache,
                                             *address, **kw)
        x = x + h
        h = self.ffn_norm(x)
        h = self.down_proj(nd.swish(self.gate_proj(h)) * self.up_proj(h))
        return x + h, tuple(cache)


#: The cache forms: each is one way of ADDRESSING a sequence mixer's
#: cache, and the mixer (MultiHeadAttention) is the only class that
#: implements them — ``form(x, *cache_leaves, *address, **kw) -> (out,
#: *new_leaves)``.  Layers, TransformerLM and ShardedDecoder carry the
#: form's NAME and its address through (``cached_forward``).  The table
#: of forms and addresses, and what a new mixer with a cache provides:
#: docs/inference.md "What a sequence mixer provides".
CACHE_FORMS = ("step", "step_slots", "verify_slots", "prefill",
               "step_pages", "verify_pages", "prefill_pages")
#: The forms that ingest prompt tokens: a routed channel mixer budgets
#: training capacity there and runs capacity-unbounded in the others.
PREFILL_FORMS = ("prefill", "prefill_pages")


class TransformerLM(HybridBlock):
    """Causal decoder LM (Llama architecture).

    Logits head ties to the embedding when tie_weights (memory win on TPU).
    """

    def __init__(self, vocab_size, units, hidden_size, num_layers, num_heads,
                 num_kv_heads=None, mesh=None, tie_weights=False,
                 num_experts=None, capacity_factor=1.25,
                 return_moe_aux=False, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._tie = tie_weights
        self._return_moe_aux = bool(return_moe_aux and num_experts)
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.layers = nn.HybridSequential(prefix="layers_")
            for i in range(num_layers):
                if num_experts:
                    from .moe import MoEDecoderLayer
                    self.layers.add(MoEDecoderLayer(
                        units, hidden_size, num_heads,
                        num_kv_heads or num_heads, num_experts,
                        capacity_factor, mesh=mesh,
                        return_aux=self._return_moe_aux,
                        prefix="layer%d_" % i))
                else:
                    self.layers.add(LlamaDecoderLayer(
                        units, hidden_size, num_heads,
                        num_kv_heads or num_heads, mesh=mesh,
                        prefix="layer%d_" % i))
            self.norm = RMSNorm(units, prefix="norm_")
            if not tie_weights:
                self.lm_head = nn.Dense(vocab_size, use_bias=False,
                                        flatten=False, in_units=units,
                                        prefix="lm_head_")

    def hybrid_forward(self, F, token_ids):
        x = self.embed(token_ids)
        aux_total = None
        for layer in self.layers:
            if self._return_moe_aux:
                x, aux = layer(x)
                aux_total = aux if aux_total is None else aux_total + aux
            else:
                x = layer(x)
        x = self.norm(x)
        if self._tie:
            w = self.embed.weight.data(x.context)
            logits = F.dot(x, w, transpose_b=True)
        else:
            logits = self.lm_head(x)
        if self._return_moe_aux:
            # mean over layers: the Switch load-balancing term, for the
            # caller's loss (jit-safe — threaded through outputs)
            return logits, aux_total / len(self.layers)
        return logits

    # -- incremental decode --------------------------------------------
    def init_cache(self, batch_size, max_length, dtype="float32"):
        """Per-layer (k, v) static-size caches."""
        return [layer.attn.init_cache(batch_size, max_length, dtype)
                for layer in self.layers]

    def _logits(self, x):
        x = self.norm(x)
        if self._tie:
            w = self.embed.weight.data(x.context)
            return nd.dot(x, w, transpose_b=True)
        return self.lm_head(x)

    def cached_forward(self, form, token_ids, caches, *address, **kw):
        """Every cache form is this one pass: embed → each layer over
        its own cache leaves → logits.  ``form`` is one of CACHE_FORMS
        and ``address`` what it addresses the cache with — the table
        there; each form's contract is the mixer's docstring (e.g.
        MultiHeadAttention.verify_slots: window logits bit-identical to
        sequential steps, ``tree=`` the draft-tree form).  token_ids
        (B, T) → (logits (B, T, V), new_caches).  Caches are
        FUNCTIONAL: the passed-in list is not mutated — always thread
        the returned new_caches into the next call (this is what lets
        ShardedDecoder trace a form with dynamic positions and
        tables).  ``total_len=`` (prefill forms): the FULL prompt
        length, for routed layers' expert capacity."""
        x = self.embed(token_ids)
        new_caches = []
        for layer, cache in zip(self.layers, caches):
            x, cache = layer.cached_forward(form, x, cache, *address, **kw)
            new_caches.append(cache)
        return self._logits(x), new_caches

    def step(self, token_ids, caches, pos):
        """Decode ONE token per sequence: token_ids (B, 1) → (logits
        (B, 1, V), new_caches)."""
        return self.cached_forward("step", token_ids, caches, pos)

    def permute_cache_span(self, caches, pos, src_lane):
        """Post-acceptance tree fix-up over every layer's static cache:
        row b's window entry at position pos[b]+src_lane[b, j] moves to
        pos[b]+j, landing the accepted root-to-leaf path in depth order
        — exactly where sequential decode would have written it (see
        _internal_cache_permute_span; lanes marked -1 stay untouched).
        Functional like write_cache_slot; the serving engines skip the
        dispatch entirely when every row is the identity."""
        def _permute(leaf):
            if _q8cache(leaf):
                return tuple(nd._internal_cache_permute_span_q8(
                    leaf[0], leaf[1], pos, src_lane))
            return nd._internal_cache_permute_span(leaf, pos, src_lane)
        return [(_permute(ck), _permute(cv)) for ck, cv in caches]

    def permute_pool_span(self, pools, tables, pos, src_lane):
        """Paged twin of permute_cache_span: the accepted path moves
        through the block tables — rollback and fix-up stay position
        bookkeeping, never an allocator op."""
        def _permute(leaf):
            if _q8cache(leaf):
                return tuple(nd._paged_cache_permute_span_q8(
                    leaf[0], leaf[1], tables, pos, src_lane))
            return nd._paged_cache_permute_span(leaf, tables, pos,
                                                src_lane)
        return [(_permute(pk), _permute(pv)) for pk, pv in pools]

    def prefill(self, token_ids, caches, start_pos=0, total_len=None):
        """Ingest the whole prompt in ONE forward: token_ids (B, T) →
        (logits (B, T, V), new_caches) with every layer's K/V cached at
        [start_pos, start_pos+T).  One MXU-sized pass replaces T serial
        step() calls — the standard prefill/decode split.  For routed
        (MoE) layers ``total_len`` declares the FULL prompt length so
        expert capacity budgets from the whole prompt even when this
        call ingests only a chunk (defaults to start_pos + T)."""
        return self.cached_forward("prefill", token_ids, caches, start_pos,
                                   total_len=total_len)

    def write_cache_slot(self, caches, slot_caches, slot, pos=0):
        """Copy one sequence's per-layer (k, v) caches (batch 1, length
        T) into row ``slot`` of the pool caches at column ``pos`` — the
        compiled slot-prefill write of the continuous-batching engine.
        ``slot`` may be a traced scalar; returns new pool caches
        (functional, like step/prefill)."""
        return [
            (_cache_write_slot(ck, sk, slot, pos=pos),
             _cache_write_slot(cv, sv, slot, pos=pos))
            for (ck, cv), (sk, sv) in zip(caches, slot_caches)]

    # -- block-paged decode (PagedContinuousBatchingEngine) ------------
    def init_block_pool(self, num_blocks, block_size, dtype="float32"):
        """Per-layer (k, v) page pools — see Attention.init_block_pool."""
        return [layer.attn.init_block_pool(num_blocks, block_size, dtype)
                for layer in self.layers]

    def copy_block(self, pools, src, dst):
        """Copy page ``src`` onto page ``dst`` in every layer's pool —
        the admission-time copy-on-write of prefix sharing.  ``src`` /
        ``dst`` may be traced scalars; ``src == dst`` is a bit-exact
        no-op (how the fused prefill program skips COW)."""
        return [(_page_copy(pk, src, dst), _page_copy(pv, src, dst))
                for pk, pv in pools]

    def generate(self, prompt_ids, max_new_tokens, max_length=None,
                 temperature=0.0, top_k=0, top_p=0.0,
                 repetition_penalty=1.0, seed=None):
        """Greedy (temperature=0) or sampled autoregressive decode with a
        KV cache (parity target: gluonnlp SequenceSampler / the
        reference's example inference loops — new capability here).

        prompt_ids: (B, T_prompt) int NDArray.  Returns (B, T_prompt +
        max_new_tokens) ids.  The prompt is ingested in ONE chunked
        prefill forward (compute-bound, MXU-sized matmuls); the serial
        fixed-shape step() only runs the bandwidth-bound decode phase.

        Sampling: temperature=0 (default) decodes greedily and IGNORES
        top_k/top_p; with temperature > 0, draws go through
        sampler.sample_next_token with optional top-k truncation and
        nucleus (top_p) filtering.  repetition_penalty != 1 applies in
        BOTH modes (greedy penalizes already-emitted tokens, then
        argmaxes) via a fixed-shape seen-token mask.

        Decode expects REPLICATED parameters.  After sharded training,
        gather first (``p.set_data(nd.array(p.data().asnumpy()))`` per
        param — see examples/parallel/llama_train.py); eager decode over
        mesh-sharded weights would launch a collective per token.
        """
        B, Tp = prompt_ids.shape
        total = Tp + max_new_tokens
        max_length = max_length or total
        if max_length < total:
            raise ValueError("max_length %d < prompt+new %d"
                             % (max_length, total))
        caches = self.init_cache(B, max_length)
        tokens = [prompt_ids]
        # chunked prefill: the whole prompt in ONE forward (round-5);
        # the serial step() loop below only runs the decode phase
        logits, caches = self.prefill(prompt_ids, caches)
        if seed is not None and temperature and temperature > 0.0:
            # reproducible sampling: seeds the GLOBAL mxtpu key stream
            # (mx.random.seed semantics).  Seed AFTER the prefill — a
            # first-ever forward finishes deferred parameter init, which
            # draws ring keys and would shift the sampling stream
            from .. import random as _rnd
            _rnd.seed(seed)
        import jax.numpy as jnp
        from .sampler import sample_next_token
        from .. import random as _rnd

        sampled = bool(temperature and temperature > 0.0)
        penalized = bool(repetition_penalty
                         and repetition_penalty != 1.0)
        seen = None
        if penalized:
            # fixed-shape (B, V) mask — one scatter per emitted token,
            # never a growing prev tensor (per-step recompiles)
            V = logits.shape[-1]
            seen = jnp.zeros((B, V), bool).at[
                jnp.arange(B)[:, None],
                prompt_ids._data.astype(jnp.int32)].set(True)
        for pos in range(Tp, total):
            if sampled or penalized:
                # greedy-with-penalty also routes here: temperature=0
                # penalizes then argmaxes (no ring key consumed)
                nxt = NDArray(sample_next_token(
                    logits[:, -1]._data,
                    _rnd.next_key() if sampled else None,
                    temperature if sampled else 0.0, top_k, top_p,
                    repetition_penalty, seen_mask=seen)).reshape((B, 1))
            else:
                nxt = logits[:, -1].argmax(axis=-1).reshape(
                    (B, 1))
            nxt = nxt.astype(prompt_ids.dtype)
            tokens.append(nxt)
            if penalized:
                seen = seen.at[jnp.arange(B),
                               nxt._data.astype(jnp.int32)[:, 0]].set(
                    True)
            if pos < total - 1:
                logits, caches = self.step(nxt, caches, pos)
        return nd.concat(*tokens, dim=1)


def llama_tiny(vocab_size=256, mesh=None, **kwargs):
    """Tiny decoder for tests/dryruns."""
    return TransformerLM(vocab_size, units=64, hidden_size=172,
                         num_layers=2, num_heads=4, num_kv_heads=2,
                         mesh=mesh, **kwargs)


def llama_3_8b(vocab_size=128256, mesh=None, width_factor=1.0,
               depth_factor=1.0, **kwargs):
    """Llama-3-8B geometry (meta-llama/Meta-Llama-3-8B config.json).

    width_factor/depth_factor scale the architecture down while keeping
    its shape invariants (4:1 GQA ratio, SwiGLU hidden ratio, rotary,
    head_dim 128) — the reduced-width configs train the REAL architecture
    end-to-end on small meshes (examples/parallel/llama_train.py).
    """
    heads = max(4, int(32 * width_factor) // 4 * 4)
    units = 128 * heads          # keep head_dim 128 — the MXU-native tile
    hidden = int(14336 * width_factor) // 128 * 128 or 128
    layers = max(1, int(32 * depth_factor))
    return TransformerLM(vocab_size, units=units, hidden_size=hidden,
                         num_layers=layers, num_heads=heads,
                         num_kv_heads=max(1, heads // 4),
                         mesh=mesh, **kwargs)


def bert_sharding_rules():
    """Megatron TP layout for the encoder (mxtpu Dense keeps weights
    (out, in), so column-parallel = shard dim 0)."""
    return ShardingRules([
        (r"qkv_weight$", P("tp", None)),
        (r"qkv_bias$", P("tp")),
        (r"attn_out_weight$", P(None, "tp")),
        (r"ffn1_weight$", P("tp", None)),
        (r"ffn1_bias$", P("tp")),
        (r"ffn2_weight$", P(None, "tp")),
        (r"(word|position)_embed_weight$", P(None, "tp")),
        (r"mlm_weight$", P("tp", None)),
    ])


def transformer_lm_sharding_rules():
    """TP layout for the decoder family."""
    return ShardingRules([
        (r"qkv_weight$", P("tp", None)),
        (r"attn_out_weight$", P(None, "tp")),
        (r"(gate|up)_weight$", P("tp", None)),
        (r"down_weight$", P(None, "tp")),
        (r"embed_weight$", P(None, "tp")),
        (r"lm_head_weight$", P("tp", None)),
    ])
