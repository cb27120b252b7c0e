"""Sharded incremental decode (VERDICT r4 item 5 / SURVEY §7 stage 10).

``ShardedDecoder`` compiles a TransformerLM's one-token decode step as a
single SPMD program over the device mesh: parameters stay tp-sharded
exactly as training left them, the KV caches live on-mesh sharded over
the kv-head axis, and the decode position is a *traced* scalar — one
compiled program serves every position (no per-step recompiles, no
host gather of the weights).

This removes the consolidated-inference workaround in
examples/parallel/llama_train.py (gather-all-params-to-host before
``generate()``): decode now launches exactly the collectives XLA plans
for the sharded matmuls (all-gather on the tp axis), amortized inside
one program per token instead of one per op.

The reference has no analogue (MXNet 1.x predates tensor-parallel
inference); the API mirrors ``TransformerLM.generate`` so the two paths
are drop-in interchangeable and testable against each other.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import autograd
from .. import random as _random
from ..ndarray import NDArray, array as nd_array
from .mesh import DeviceMesh
from .sharding import ShardingRules

__all__ = ["ShardedDecoder"]


def _strip_instance_prefix(name: str) -> str:
    """Drop the outermost ``<block><N>_`` instance prefix from a
    parameter name (``transformerlm1_embed_weight`` ->
    ``embed_weight``): the per-process block-instance counter that
    makes the same architecture's names differ across processes."""
    return re.sub(r"^[a-z][a-z0-9]*?\d+_", "", name)


def _bucket(n, base=8):
    """Smallest power-of-two >= n (floor `base`)."""
    b = base
    while b < n:
        b *= 2
    return b


# -- quantized-cache leaves -------------------------------------------------
# With cache_dtype="int8" every cache leaf is a (payload, scales) PAIR
# (models.transformer docstring).  The helpers below keep the decoder's
# jit plumbing shape-generic: leaves wrap/unwrap structurally, sharding
# trees map the payload to cache_spec and the (D-less) scale tensors to
# cache_spec minus its trailing axis, and jit-cache keys read the
# payload's shape/dtype so int8 programs key separately from float ones.

def _leaf_q8(leaf):
    return isinstance(leaf, tuple)


def _leaf_payload(leaf):
    return leaf[0] if _leaf_q8(leaf) else leaf


def _wrap_leaf(leaf):
    if _leaf_q8(leaf):
        return (NDArray(leaf[0]), NDArray(leaf[1]))
    return NDArray(leaf)


def _unwrap_leaf(leaf):
    if _leaf_q8(leaf):
        return (leaf[0]._data, leaf[1]._data)
    return leaf._data


def _cache_shapes(cache_leaves):
    return tuple(tuple(_leaf_payload(ck).shape)
                 for ck, _ in cache_leaves)


def _cache_dt(cache_leaves):
    ck = cache_leaves[0][0]
    return "int8" if _leaf_q8(ck) else str(ck.dtype)


def _paged_attn_gate():
    """MXTPU_PALLAS_PAGED_ATTN read for the paged jit-cache keys: the
    kernel choice is baked at trace time, so flipping the env mid-
    process must key a distinct program, not silently reuse one."""
    from ..ops.pallas.paged_attention import paged_attention_enabled
    return bool(paged_attention_enabled())


def _paged_prefill_gate():
    """Prefill twin of _paged_attn_gate: the chunked-prefill kernel
    choice is likewise baked into the compiled program, so the
    page_prefill jit key carries the resolved tri-state verdict."""
    from ..ops.pallas.prefill_attention import paged_prefill_enabled
    return bool(paged_prefill_enabled())


def resolve_cache_dtype(cache_dtype):
    """None → the ambient default: MXTPU_CACHE_DTYPE (e.g. "int8" to
    run every engine/generate quantized without touching call sites),
    falling back to float32."""
    import os

    if cache_dtype is not None:
        return cache_dtype
    return os.environ.get("MXTPU_CACHE_DTYPE", "float32")


class ShardedDecoder:
    """Jitted KV-cache decode over a mesh with tp-sharded parameters.

    Parameters
    ----------
    block : TransformerLM-like block with ``init_cache``/``step``.
    mesh : DeviceMesh (axes dp/tp/...).
    rules : ShardingRules — the SAME rules used for training, so the
        sharded training weights are consumed in place.
    cache_spec : PartitionSpec for the (B, KV_heads, T_max, D) caches;
        default shards the kv-head axis over "tp" (each tp shard holds
        the heads whose q/k/v projections it owns — no cross-shard
        traffic in the attention itself).
    ledger_tag : optional label appended to this decoder's compile-
        ledger site names (``serving.step@TAG``) so a multi-replica
        pool's per-replica program families stay separable in
        ``check_compiles``/``compile_budget`` — each replica owns its
        own jit cache, so without the tag N replicas look like N×
        churn at one site.  Prefix queries (``serving.*``) still match.
    """

    def __init__(self, block, mesh: DeviceMesh,
                 rules: Optional[ShardingRules] = None,
                 cache_spec: P = P(None, "tp", None, None),
                 bucket_prefill: bool = True,
                 ledger_tag: Optional[str] = None):
        self._block = block
        self._mesh = mesh
        self._rules = rules or ShardingRules()
        self._cache_spec = cache_spec
        self._bucket_prefill = bucket_prefill
        self._ledger_tag = ledger_tag
        self._has_moe = None  # computed once on first generate()
        self._params = sorted(block.collect_params().values(),
                              key=lambda p: p.name)
        self._staged = False
        self._jit_cache: Dict[Any, Any] = {}
        #: "kernel[geometry]" -> "pallas|xla: why" for every attention
        #: gate resolved while one of THIS decoder's programs was traced
        self.attention_paths: Dict[str, str] = {}
        # live weight hot-swap (docs/serving.md "Elastic serving"):
        # when set, every compiled call runs with THESE placed leaves
        # instead of the parameters' own data — the serving engines
        # install a new generation here at an iteration boundary
        self._adopted: Optional[tuple] = None
        self._validate_kv_sharding()

    def _iter_blocks(self):
        """DFS over the block tree (shared by every construction-time
        inspection: MoE detection, kv-head validation)."""
        stack = [self._block]
        while stack:
            b = stack.pop()
            yield b
            children = getattr(b, "_children", None)
            if children:
                stack.extend(children.values()
                             if hasattr(children, "values") else children)

    def _validate_kv_sharding(self):
        """The default cache_spec shards the kv-head axis over "tp"; a
        head count not divisible by the shard count would surface as an
        opaque GSPMD partitioning failure deep inside the first compiled
        step (ADVICE r5).  Catch it at construction with the actual
        constraint spelled out."""
        spec = self._cache_spec
        axes = ()
        if len(spec) > 1 and spec[1] is not None:
            axes = spec[1] if isinstance(spec[1], tuple) else (spec[1],)
        shards = 1
        for a in axes:
            shards *= self._mesh.axis_sizes.get(a, 1)
        if shards <= 1:
            return
        for b in self._iter_blocks():
            kv = getattr(b, "_kv_heads", None)
            if kv is not None and kv % shards != 0:
                raise ValueError(
                    "KV cache sharding %r splits the %d kv heads of "
                    "block %r over %d shards, which does not divide "
                    "evenly — this would fail inside GSPMD at the first "
                    "decode step.  Use a model whose num_kv_heads is "
                    "divisible by the tp axis, or pass "
                    "cache_spec=PartitionSpec() to replicate the caches."
                    % (tuple(spec), kv, getattr(b, "name", b), shards))

    def _block_has_moe(self):
        """Bucketed prefill is disabled for MoE blocks: padded tokens
        would participate in capacity-limited expert routing and could
        evict REAL tokens (attention masks pads out; routing does not).
        The tree walk runs once; the block is fixed at construction.
        """
        if self._has_moe is not None:
            return self._has_moe
        from ..models.moe import SwitchMoE

        self._has_moe = any(isinstance(b, SwitchMoE)
                            for b in self._iter_blocks())
        return self._has_moe

    # -- staging ---------------------------------------------------------
    def _stage(self):
        for p in self._params:
            holder = p.data()
            sh = self._rules.sharding_for(p.name, holder.ndim, self._mesh)
            holder._rebind(jax.device_put(holder._data, sh))
        self._staged = True

    # -- live weight hot-swap (docs/serving.md "Elastic serving") --------
    def _live_param_leaves(self):
        """The param leaves every compiled call runs with: the adopted
        generation when one is installed, else the parameters' own
        staged data.  Swapping leaves costs zero recompiles — the jit
        cache keys on shapes/dtypes, which adoption preserves."""
        if self._adopted is not None:
            return self._adopted
        return tuple(p.data()._data for p in self._params)

    def prepare_adoption(self, named):
        """Validate a ``name -> host array`` map against this block's
        parameter tree and place each array on the mesh by the SAME
        sharding rules as :meth:`_stage` — returned as a leaves tuple
        ready for :meth:`install_leaves`, WITHOUT installing anything.
        Split from install so the serving engines can stage a verified
        checkpoint while streams are in flight and install only at an
        empty iteration boundary.  Extra names are ignored (a broader
        checkpoint may feed a narrower block).

        Names match exactly first; on a miss the lookup retries with
        the outermost instance prefix stripped (``transformerlm1_`` vs
        ``transformerlm0_``): the same architecture built in another
        process numbers its root block differently, and a checkpoint
        written there must still adopt here.  An ambiguous stripped
        name stays a mismatch."""
        stripped = None
        for k in named:
            key = _strip_instance_prefix(k)
            if stripped is None:
                stripped = {}
            if key in stripped:
                stripped[key] = None      # ambiguous: refuse to guess
            else:
                stripped[key] = k
        leaves = []
        for p in self._params:
            src = p.name
            if src not in named:
                alt = (stripped or {}).get(_strip_instance_prefix(src))
                if alt is None:
                    raise ValueError(
                        "checkpoint is missing parameter %r — "
                        "architecture mismatch" % p.name)
                src = alt
            holder = p.data()
            arr = jnp.asarray(named[src], dtype=holder.dtype)
            if tuple(arr.shape) != tuple(holder.shape):
                raise ValueError(
                    "checkpoint parameter %r has shape %r, block "
                    "expects %r — architecture mismatch"
                    % (p.name, tuple(arr.shape), tuple(holder.shape)))
            sh = self._rules.sharding_for(p.name, holder.ndim, self._mesh)
            leaves.append(jax.device_put(arr, sh))
        return tuple(leaves)

    def install_leaves(self, leaves):
        """Point every subsequent compiled call at ``leaves`` (from
        :meth:`prepare_adoption`, or a previously captured
        :meth:`_live_param_leaves` for rollback).  ``None`` reverts to
        the parameters' own data."""
        self._adopted = None if leaves is None else tuple(leaves)

    # -- the compiled programs -------------------------------------------
    def _scale_spec(self):
        """PartitionSpec of an int8 cache's scale tensors: the payload
        spec minus its trailing head-dim axis (a (B, KV, T, D) spec
        prices/shards its (B, KV, T) scales identically head-wise)."""
        return P(*tuple(self._cache_spec)[:-1])

    def _leaf_sharding(self, leaf):
        jm = self._mesh.jax_mesh
        if _leaf_q8(leaf):
            return (NamedSharding(jm, self._cache_spec),
                    NamedSharding(jm, self._scale_spec()))
        return NamedSharding(jm, self._cache_spec)

    def _cache_sharding_tree(self, cache_template):
        return tuple((self._leaf_sharding(ck), self._leaf_sharding(cv))
                     for ck, cv in cache_template)

    def _place_cache(self, nd_caches):
        """device_put a freshly-built NDArray cache tree onto the mesh
        (payload by cache_spec; int8 scales by the derived scale spec).
        Shared by generate() and both serving engines' pools."""
        def put(leaf):
            if isinstance(leaf, tuple):
                sh = self._leaf_sharding((leaf[0]._data, leaf[1]._data))
                return (jax.device_put(leaf[0]._data, sh[0]),
                        jax.device_put(leaf[1]._data, sh[1]))
            return jax.device_put(
                leaf._data, self._leaf_sharding(leaf._data))
        return tuple((put(ck), put(cv)) for ck, cv in nd_caches)

    def _build_program(self, body, cache_template, n_extra_inputs):
        """Shared jit scaffolding for the decode programs: the param
        holder swap/restore protocol, sharding trees (params by rules,
        caches by cache_spec — int8 (payload, scales) pairs map
        structurally, scales on the derived scale spec — everything
        else replicated) and cache donation live HERE once — both the
        one-token step and the chunked prefill specialize only the
        traced ``body``.

        body(block, caches, *extra) -> (logits NDArray, new_caches).
        Specialization happens through the _jit_cache key + jax.jit's
        own shape cache; only the cache TREE (count + leaf form) shapes
        the sharding trees.
        """
        block = self._block
        params = self._params
        mesh = self._mesh
        spec = tuple(self._cache_spec)
        heads_axes = ()
        if len(spec) > 1 and spec[1] is not None:
            heads_axes = (spec[1] if isinstance(spec[1], tuple)
                          else (spec[1],))

        def program(param_leaves, cache_leaves, *extra):
            # the cache_spec heads axes scope the trace: any Pallas
            # paged-attention call inside body() shard_maps itself over
            # them, so tp>1 configurations ride the kernel per-shard
            # instead of falling back (ops/pallas/partition.py)
            from ..ops.pallas.paged_attention import recording_paths
            from ..ops.pallas.partition import head_sharding_scope
            saved = []
            for p, leaf in zip(params, param_leaves):
                holder = p.data()
                saved.append((holder, holder._data))
                holder._data = leaf
            try:
                with autograd.pause(train_mode=False), \
                        head_sharding_scope(mesh, heads_axes), \
                        recording_paths(self.attention_paths):
                    caches = [(_wrap_leaf(ck), _wrap_leaf(cv))
                              for ck, cv in cache_leaves]
                    logits, new_caches = body(block, caches, *extra)
            finally:
                for holder, data in saved:
                    holder._data = data
            return logits._data, tuple(
                (_unwrap_leaf(ck), _unwrap_leaf(cv))
                for ck, cv in new_caches)

        jm = self._mesh.jax_mesh
        rep = NamedSharding(jm, P())
        param_sh = tuple(
            self._rules.sharding_for(p.name, p.data().ndim, self._mesh)
            for p in params)
        cache_sh = self._cache_sharding_tree(cache_template)
        in_sh = (param_sh, cache_sh) + (rep,) * n_extra_inputs
        # donate the caches: each write supersedes the old buffer
        return jax.jit(program, in_shardings=in_sh,
                       out_shardings=(rep, cache_sh), donate_argnums=(1,))

    @staticmethod
    def _step_body(block, caches, token, pos):
        return block.step(NDArray(token), caches, NDArray(pos))

    @staticmethod
    def _prefill_body(block, caches, tokens):
        return block.prefill(NDArray(tokens), caches)

    @staticmethod
    def _step_slots_body(block, caches, token, pos):
        """Pool decode step: pos is a (B,) vector — every slot at its
        own position, one compiled program for all combinations."""
        return block.step_slots(NDArray(token), caches, NDArray(pos))

    @staticmethod
    def _slot_prefill_body(block, caches, tokens, slot):
        """Compiled slot prefill: run the (1, Tb) prompt through the
        block's chunked prefill against a FRESH batch-1 scratch cache
        of length Tb, then write the scratch K/V into pool row ``slot``
        (a traced scalar — one program per bucket serves every slot).
        The scratch cache is an in-program constant; XLA fuses the
        zero-init away."""
        tokens = NDArray(tokens)
        ck0 = caches[0][0]
        dt = "int8" if isinstance(ck0, tuple) else str(ck0.dtype)
        scratch = block.init_cache(1, tokens.shape[1], dt)
        logits, scratch = block.prefill(tokens, scratch)
        return logits, block.write_cache_slot(caches, scratch,
                                              NDArray(slot))

    @staticmethod
    def _verify_slots_body(block, caches, tokens, pos, valid_len):
        """Pooled speculative verification: ``tokens`` (B, W) is each
        row's candidate window (last sampled token + drafts) at traced
        per-row start positions — ONE compiled program per window-size
        bucket scores every draft position against the cache in one
        read (see TransformerLM.verify_slots)."""
        return block.verify_slots(NDArray(tokens), caches, NDArray(pos),
                                  NDArray(valid_len))

    @staticmethod
    def _verify_pages_body(block, caches, tokens, tables, pos,
                           valid_len):
        """Block-paged speculative verification (traced tables +
        per-row positions; see TransformerLM.verify_pages)."""
        return block.verify_pages(NDArray(tokens), caches,
                                  NDArray(tables), NDArray(pos),
                                  NDArray(valid_len))

    @staticmethod
    def _verify_tree_slots_body(block, caches, tokens, pos, valid_len,
                                perm, depth):
        """Tree-speculative verification over the slot pool: ``tokens``
        (B, W) holds a draft TREE in window-lane order (lane 0 = root)
        and ``perm``/``depth`` carry each lane's root-to-self ancestor
        chain — one pooled cache read scores every branch (see
        MultiHeadAttention.verify_slots).  A degenerate chain
        (perm[b, w, i] = min(i, w), depth[b, w] = w) reproduces the
        linear verify bit for bit, which is how mixed linear/tree pools
        share this program."""
        return block.verify_slots(NDArray(tokens), caches, NDArray(pos),
                                  NDArray(valid_len),
                                  tree=(NDArray(perm), NDArray(depth)))

    @staticmethod
    def _verify_tree_pages_body(block, caches, tokens, tables, pos,
                                valid_len, perm, depth, anc):
        """Block-paged tree verification: ``anc`` additionally carries
        the (B, W) int32 strict-ancestor bitmask the Pallas kernel's
        tree mask reads via scalar prefetch (see
        ops/pallas/paged_attention.py)."""
        return block.verify_pages(NDArray(tokens), caches,
                                  NDArray(tables), NDArray(pos),
                                  NDArray(valid_len),
                                  tree=(NDArray(perm), NDArray(depth),
                                        NDArray(anc)))

    @staticmethod
    def _fixup_slots_body(block, caches, pos, src_lane):
        """Post-acceptance cache fix-up: rewrite rows pos[b]+j from the
        accepted path's window lanes (``src_lane`` (B, W), -1 beyond
        the accepted count) so the surviving K/V land in SEQUENTIAL
        arrangement — a host position fix-up expressed as one in-place
        gather/scatter, never an allocator op.  src_lane[b, j] >= j
        always (parents precede children in lane order), so the
        gather-before-scatter inside the op reads pre-permute rows."""
        return NDArray(pos), block.permute_cache_span(
            caches, NDArray(pos), NDArray(src_lane))

    @staticmethod
    def _fixup_pages_body(block, caches, tables, pos, src_lane):
        """Paged twin of _fixup_slots_body: the same span permute
        routed through the block tables (out-of-range destinations fall
        on the reserved null page 0)."""
        return NDArray(pos), block.permute_pool_span(
            caches, NDArray(tables), NDArray(pos), NDArray(src_lane))

    @staticmethod
    def _step_pages_body(block, caches, token, tables, pos):
        """Block-paged pool decode step: ``tables`` (B, M) block tables
        and ``pos`` (B,) positions are both traced — ONE compiled
        program serves every table content and position combination."""
        return block.step_pages(NDArray(token), caches, NDArray(tables),
                                NDArray(pos))

    @staticmethod
    def _page_prefill_body(total_len, block, caches, tokens, table,
                           start_pos, cow_src, cow_dst):
        """Compiled paged chunk-prefill: an optional copy-on-write of
        one page (``cow_src`` → ``cow_dst``; equal scalars are a
        bit-exact no-op, so the COW and no-COW admissions share ONE
        program), then one (1, Tb) chunk scattered/attended through the
        traced block ``table`` at traced ``start_pos``.  ``total_len``
        is STATIC (None for dense blocks; the full prompt length for
        MoE expert-capacity budgeting — capacity is a shape)."""
        caches = block.copy_block(caches, NDArray(cow_src),
                                  NDArray(cow_dst))
        return block.prefill_pages(NDArray(tokens), caches,
                                   NDArray(table), NDArray(start_pos),
                                   total_len=total_len)

    def _build_swap_program(self, cache_template):
        """ONE bounded copy program for the hierarchical cache's
        device↔host page moves (docs/inference.md): reads page ``bid``
        of every pool leaf (replicated out, so the host copy sees the
        full page) and — under the traced ``write`` flag — overwrites
        that page with ``content``.  Swap-out passes write=0 (the
        content arg is an ignored zero template), swap-in passes
        write=1 and discards the read; both directions therefore share
        a SINGLE compiled program per pool shape, the only program the
        swap tier ever adds (site ``serving.swap``)."""
        jm = self._mesh.jax_mesh
        rep = NamedSharding(jm, P())
        cache_sh = self._cache_sharding_tree(cache_template)
        rep_tree = jax.tree_util.tree_map(lambda _: rep, cache_sh)

        def program(cache_leaves, content, bid, write):
            read = jax.tree_util.tree_map(
                lambda l: jax.lax.dynamic_index_in_dim(
                    l, bid, 0, keepdims=False), cache_leaves)

            def wr(leaf, c):
                return jax.lax.cond(
                    write > 0,
                    lambda a: jax.lax.dynamic_update_slice_in_dim(
                        a, c[None].astype(a.dtype), bid, 0),
                    lambda a: a, leaf)

            new = jax.tree_util.tree_map(wr, cache_leaves, content)
            return read, new

        return jax.jit(program,
                       in_shardings=(cache_sh, rep_tree, rep, rep),
                       out_shardings=(rep_tree, cache_sh),
                       donate_argnums=(0,))

    def _swap_page_jitted(self, cache_leaves, content, bid, write):
        """The hierarchical cache's page copy (see
        :meth:`_build_swap_program`); returns ``(page_content,
        new_cache_leaves)``."""
        key = ("swap", _cache_shapes(cache_leaves),
               _cache_dt(cache_leaves))
        hit = key in self._jit_cache
        self._ledger_report("swap", cache_leaves, (), hit)
        if not hit:
            self._jit_cache[key] = self._build_swap_program(cache_leaves)
        return self._jit_cache[key](cache_leaves, content,
                                    jnp.int32(bid), jnp.int32(write))

    def _ledger_report(self, kind, cache_leaves, extras, hit):
        """Report one program-cache lookup into the process compile
        ledger (docs/analysis.md): the bucketed prefill and pooled decode
        step are THE sites the O(log T) discipline bounds, and
        compile_budget / compile_check read this record.  Gated before
        the signature build — this runs once per decode token."""
        from ..analysis.compile_ledger import (Signature, ledger_enabled,
                                               record)
        if not ledger_enabled():
            return
        site = "serving.%s" % kind
        if self._ledger_tag:
            site = "%s@%s" % (site, self._ledger_tag)
        record(site, Signature(
            shapes=_cache_shapes(cache_leaves)
            + tuple(tuple(e.shape) for e in extras),
            dtypes=(_cache_dt(cache_leaves),)
            + tuple(str(e.dtype) for e in extras),
            weak=(),
            static=(kind,)), hit=hit)

    def _step_jitted(self, cache_leaves, token, pos):
        key = ("step", _cache_shapes(cache_leaves),
               _cache_dt(cache_leaves), token.shape, token.dtype)
        hit = key in self._jit_cache
        self._ledger_report("step", cache_leaves, (token,), hit)
        if not hit:
            self._jit_cache[key] = self._build_program(
                self._step_body, cache_leaves, n_extra_inputs=2)
        param_leaves = self._live_param_leaves()
        return self._jit_cache[key](param_leaves, cache_leaves, token, pos)

    def _prefill_jitted(self, cache_leaves, tokens):
        key = ("prefill", _cache_shapes(cache_leaves),
               _cache_dt(cache_leaves), tokens.shape, tokens.dtype)
        hit = key in self._jit_cache
        self._ledger_report("prefill", cache_leaves, (tokens,), hit)
        if not hit:
            self._jit_cache[key] = self._build_program(
                self._prefill_body, cache_leaves, n_extra_inputs=1)
        param_leaves = self._live_param_leaves()
        return self._jit_cache[key](param_leaves, cache_leaves, tokens)

    def _step_slots_jitted(self, cache_leaves, token, pos):
        key = ("step_slots", _cache_shapes(cache_leaves),
               _cache_dt(cache_leaves), token.shape, token.dtype)
        hit = key in self._jit_cache
        self._ledger_report("step_slots", cache_leaves, (token,), hit)
        if not hit:
            self._jit_cache[key] = self._build_program(
                self._step_slots_body, cache_leaves,
                n_extra_inputs=2)
        param_leaves = self._live_param_leaves()
        return self._jit_cache[key](param_leaves, cache_leaves, token, pos)

    def _slot_prefill_jitted(self, cache_leaves, tokens, slot):
        key = ("slot_prefill",
               _cache_shapes(cache_leaves),
               _cache_dt(cache_leaves), tokens.shape, tokens.dtype)
        hit = key in self._jit_cache
        self._ledger_report("slot_prefill", cache_leaves, (tokens,), hit)
        if not hit:
            self._jit_cache[key] = self._build_program(
                self._slot_prefill_body, cache_leaves,
                n_extra_inputs=2)
        param_leaves = self._live_param_leaves()
        return self._jit_cache[key](param_leaves, cache_leaves, tokens,
                                    slot)

    def _verify_slots_jitted(self, cache_leaves, tokens, pos, valid_len):
        """Speculative verify step over the slot pool: the window width
        W in ``tokens`` (B, W) comes from the engine's power-of-two
        ladder, so this site compiles at most |ladder| programs — the
        bounded family the compile discipline allows (C004, never
        C001)."""
        key = ("verify_slots",
               _cache_shapes(cache_leaves),
               _cache_dt(cache_leaves), tokens.shape, tokens.dtype)
        hit = key in self._jit_cache
        self._ledger_report("verify_slots", cache_leaves, (tokens,), hit)
        if not hit:
            self._jit_cache[key] = self._build_program(
                self._verify_slots_body, cache_leaves,
                n_extra_inputs=3)
        param_leaves = self._live_param_leaves()
        return self._jit_cache[key](param_leaves, cache_leaves, tokens,
                                    pos, valid_len)

    def _verify_pages_jitted(self, cache_leaves, tokens, tables, pos,
                             valid_len):
        """Block-paged speculative verify step (same bounded
        window-ladder family as _verify_slots_jitted)."""
        key = ("verify_pages",
               _cache_shapes(cache_leaves),
               _cache_dt(cache_leaves), tokens.shape, tokens.dtype,
               tables.shape, _paged_attn_gate())
        hit = key in self._jit_cache
        self._ledger_report("verify_pages", cache_leaves, (tokens,), hit)
        if not hit:
            self._jit_cache[key] = self._build_program(
                self._verify_pages_body, cache_leaves,
                n_extra_inputs=4)
        param_leaves = self._live_param_leaves()
        return self._jit_cache[key](param_leaves, cache_leaves, tokens,
                                    tables, pos, valid_len)

    def _verify_tree_slots_jitted(self, cache_leaves, tokens, pos,
                                  valid_len, perm, depth):
        """Tree verify over the slot pool: W rides the same power-of-two
        node ladder as the linear verify, and perm/depth shapes are
        functions of (B, W) — so this site compiles at most |ladder|
        programs (the compile_budget bound), shared by every tree SHAPE
        in the bucket including degenerate linear chains."""
        key = ("verify_tree_slots",
               _cache_shapes(cache_leaves),
               _cache_dt(cache_leaves), tokens.shape, tokens.dtype)
        hit = key in self._jit_cache
        self._ledger_report("verify_tree_slots", cache_leaves, (tokens,),
                            hit)
        if not hit:
            self._jit_cache[key] = self._build_program(
                self._verify_tree_slots_body, cache_leaves,
                n_extra_inputs=5)
        param_leaves = self._live_param_leaves()
        return self._jit_cache[key](param_leaves, cache_leaves, tokens,
                                    pos, valid_len, perm, depth)

    def _verify_tree_pages_jitted(self, cache_leaves, tokens, tables,
                                  pos, valid_len, perm, depth, anc):
        """Block-paged tree verify (same bounded window-ladder family
        as _verify_tree_slots_jitted)."""
        key = ("verify_tree_pages",
               _cache_shapes(cache_leaves),
               _cache_dt(cache_leaves), tokens.shape, tokens.dtype,
               tables.shape, _paged_attn_gate())
        hit = key in self._jit_cache
        self._ledger_report("verify_tree_pages", cache_leaves, (tokens,),
                            hit)
        if not hit:
            self._jit_cache[key] = self._build_program(
                self._verify_tree_pages_body, cache_leaves,
                n_extra_inputs=7)
        param_leaves = self._live_param_leaves()
        return self._jit_cache[key](param_leaves, cache_leaves, tokens,
                                    tables, pos, valid_len, perm, depth,
                                    anc)

    def _fixup_slots_jitted(self, cache_leaves, pos, src_lane):
        """Accepted-path cache permute over the slot pool (tree verify
        rollback; one program per (pool shape, W) pair)."""
        key = ("fixup_slots", _cache_shapes(cache_leaves),
               _cache_dt(cache_leaves), src_lane.shape)
        hit = key in self._jit_cache
        self._ledger_report("fixup_slots", cache_leaves, (src_lane,),
                            hit)
        if not hit:
            self._jit_cache[key] = self._build_program(
                self._fixup_slots_body, cache_leaves, n_extra_inputs=2)
        param_leaves = self._live_param_leaves()
        _, caches = self._jit_cache[key](param_leaves, cache_leaves,
                                         pos, src_lane)
        return caches

    def _fixup_pages_jitted(self, cache_leaves, tables, pos, src_lane):
        """Paged accepted-path cache permute (see _fixup_slots_jitted)."""
        key = ("fixup_pages", _cache_shapes(cache_leaves),
               _cache_dt(cache_leaves), src_lane.shape, tables.shape)
        hit = key in self._jit_cache
        self._ledger_report("fixup_pages", cache_leaves, (src_lane,),
                            hit)
        if not hit:
            self._jit_cache[key] = self._build_program(
                self._fixup_pages_body, cache_leaves, n_extra_inputs=3)
        param_leaves = self._live_param_leaves()
        _, caches = self._jit_cache[key](param_leaves, cache_leaves,
                                         tables, pos, src_lane)
        return caches

    def _step_pages_jitted(self, cache_leaves, token, tables, pos):
        key = ("step_pages", _cache_shapes(cache_leaves),
               _cache_dt(cache_leaves), token.shape, token.dtype,
               tables.shape, _paged_attn_gate())
        hit = key in self._jit_cache
        self._ledger_report("step_pages", cache_leaves, (token,), hit)
        if not hit:
            self._jit_cache[key] = self._build_program(
                self._step_pages_body, cache_leaves,
                n_extra_inputs=3)
        param_leaves = self._live_param_leaves()
        return self._jit_cache[key](param_leaves, cache_leaves, token,
                                    tables, pos)

    def _page_prefill_jitted(self, cache_leaves, tokens, table,
                             start_pos, cow_src, cow_dst,
                             total_len=None):
        import functools

        key = ("page_prefill",
               _cache_shapes(cache_leaves),
               _cache_dt(cache_leaves), tokens.shape, tokens.dtype,
               table.shape, total_len, _paged_prefill_gate())
        hit = key in self._jit_cache
        self._ledger_report("page_prefill", cache_leaves, (tokens,), hit)
        if not hit:
            self._jit_cache[key] = self._build_program(
                functools.partial(self._page_prefill_body, total_len),
                cache_leaves, n_extra_inputs=5)
        param_leaves = self._live_param_leaves()
        return self._jit_cache[key](param_leaves, cache_leaves, tokens,
                                    table, start_pos, cow_src, cow_dst)

    def _ensure_staged(self, sample_ids):
        """Resolve deferred parameter shapes (one imperative forward if
        needed — same bootstrap as SPMDTrainer.step) and stage the
        params onto the mesh.  Shared by generate() and the
        continuous-batching engine."""
        if self._staged:
            return
        from ..gluon.parameter import DeferredInitializationError
        try:
            for p in self._params:
                p.data()
        except DeferredInitializationError:
            with autograd.pause(train_mode=False):
                self._block(sample_ids)
        self._stage()

    # -- public API ------------------------------------------------------
    def generate(self, prompt_ids, max_new_tokens, max_length=None,
                 temperature=0.0, top_k=0, top_p=0.0,
                 repetition_penalty=1.0, seed=None,
                 cache_dtype=None):
        """Same contract as ``TransformerLM.generate`` but sharded: the
        params keep their mesh shardings; returns (B, T_prompt +
        max_new_tokens) ids as a host NDArray.  temperature=0 decodes
        greedily and ignores top_k/top_p (same gating as generate).
        ``cache_dtype``: the KV-cache dtype ("int8" = quantized cache
        with per-head scales, docs/inference.md); None reads the
        MXTPU_CACHE_DTYPE default (float32)."""
        cache_dtype = resolve_cache_dtype(cache_dtype)
        prompt_ids = prompt_ids if isinstance(prompt_ids, NDArray) \
            else nd_array(prompt_ids)
        self._ensure_staged(prompt_ids)
        B, Tp = prompt_ids.shape
        total = Tp + max_new_tokens
        bucketing = self._bucket_prefill and not self._block_has_moe()
        if max_length is None:
            # bucket the CACHE length too: the jit-cache key includes
            # the (B, KV, max_length, D) cache shapes, so without this a
            # varying default max_length would recompile per request
            # and defeat the prefill bucketing entirely
            max_length = _bucket(total) if bucketing else total
        if max_length < total:
            raise ValueError("max_length %d < prompt+new %d"
                             % (max_length, total))

        cache_leaves = self._place_cache(
            self._block.init_cache(B, max_length, cache_dtype))

        tokens = [prompt_ids]
        # chunked prefill: one compiled forward ingests the whole
        # prompt.  With bucket_prefill, the prompt is right-padded to a
        # power-of-two bucket so serving traffic with varied prompt
        # lengths reuses a handful of compiled prefills instead of one
        # per length.  Right padding is safe by construction: padded
        # QUERIES' logits are ignored (we read position Tp-1), padded
        # KEYS sit at positions > Tp-1 which the causal masks of both
        # prefill and decode exclude until the decode step's own
        # dynamic-slice write overwrites them with the real token.
        raw = prompt_ids._data.astype(jnp.int32)
        if bucketing:
            Tb = min(_bucket(Tp), max_length)
            if Tb > Tp:
                raw = jnp.pad(raw, ((0, 0), (0, Tb - Tp)))
        logits, cache_leaves = self._prefill_jitted(cache_leaves, raw)
        logits = logits[:, :Tp]  # padded-query logits are garbage
        if seed is not None and temperature and temperature > 0.0:
            # after prefill: deferred init / staging must not shift the
            # sampling stream (same ordering as TransformerLM.generate)
            _random.seed(seed)
        from ..models.sampler import sample_next_token

        sampled = bool(temperature and temperature > 0.0)
        penalized = bool(repetition_penalty
                         and repetition_penalty != 1.0)
        seen = None
        if penalized:
            # fixed-shape (B, V) mask (same discipline as generate():
            # no growing prev tensor, no per-step recompiles)
            V = logits.shape[-1]
            seen = jnp.zeros((B, V), bool).at[
                jnp.arange(B)[:, None],
                prompt_ids._data.astype(jnp.int32)].set(True)
        for pos in range(Tp, total):
            last = logits[:, -1]
            if sampled or penalized:
                nxt = sample_next_token(
                    last, _random.next_key() if sampled else None,
                    temperature if sampled else 0.0, top_k, top_p,
                    repetition_penalty, seen_mask=seen)
            else:
                nxt = jnp.argmax(last, axis=-1)
            nxt = nxt.reshape(B, 1).astype(jnp.int32)
            tokens.append(NDArray(nxt.astype(prompt_ids.dtype)))
            if penalized:
                seen = seen.at[jnp.arange(B), nxt[:, 0]].set(True)
            if pos < total - 1:
                logits, cache_leaves = self._step_jitted(
                    cache_leaves, nxt, jnp.int32(pos))
        out = jnp.concatenate([t._data for t in tokens], axis=1)
        return NDArray(out)
