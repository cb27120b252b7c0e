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

import functools
import re
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

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


# -- the program kinds ------------------------------------------------------
# Every compiled serving program is one row of PROGRAMS: what it traces
# (``body(block, caches, *extras) -> (logits, new_caches)``) and what of
# its call enters the jit-cache key.  ShardedDecoder._run is the one
# path from a kind's name to its program; the engines call it by name.

def _form_body(form, n_tree=0):
    """Body of a kind that IS one cache form of the block (models.
    transformer.CACHE_FORMS): the inputs are the tokens, then the
    form's address, every one traced — ONE compiled program serves
    every position, table content and window.  The last ``n_tree``
    inputs travel as the form's ``tree=`` (a draft TREE in window-lane
    order, lane 0 = root: each lane's root-to-self ancestor chain perm/
    depth, and for the paged kernel the (B, W) int32 strict-ancestor
    bitmask anc it reads via scalar prefetch).  A degenerate chain
    (perm[b, w, i] = min(i, w), depth[b, w] = w) reproduces the linear
    verify bit for bit, which is how mixed linear/tree pools share the
    tree program."""
    def body(block, caches, tokens, *address):
        address = [NDArray(a) for a in address]
        kw = {}
        if n_tree:
            kw["tree"] = tuple(address[-n_tree:])
            del address[-n_tree:]
        return block.cached_forward(form, NDArray(tokens), caches,
                                    *address, **kw)
    return body


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


def _page_prefill_body(block, caches, tokens, table, start_pos, cow_src,
                       cow_dst, total_len=None):
    """Compiled paged chunk-prefill: an optional copy-on-write of
    one page (``cow_src`` → ``cow_dst``; equal scalars are a
    bit-exact no-op, so the COW and no-COW admissions share ONE
    program), then one (1, Tb) chunk scattered/attended through the
    traced block ``table`` at traced ``start_pos``.  ``total_len``
    is STATIC (None for dense blocks; the full prompt length for
    MoE expert-capacity budgeting — capacity is a shape)."""
    caches = block.copy_block(caches, NDArray(cow_src),
                              NDArray(cow_dst))
    return block.cached_forward("prefill_pages", NDArray(tokens), caches,
                                NDArray(table), NDArray(start_pos),
                                total_len=total_len)


def _fixup_slots_body(block, caches, pos, src_lane):
    """Post-acceptance cache fix-up (tree verify rollback): rewrite
    rows pos[b]+j from the accepted path's window lanes (``src_lane``
    (B, W), -1 beyond the accepted count) so the surviving K/V land in
    SEQUENTIAL arrangement — a host position fix-up expressed as one
    in-place gather/scatter, never an allocator op.  src_lane[b, j] >= j
    always (parents precede children in lane order), so the
    gather-before-scatter inside the op reads pre-permute rows."""
    return NDArray(pos), block.permute_cache_span(
        caches, NDArray(pos), NDArray(src_lane))


def _fixup_pages_body(block, caches, tables, pos, src_lane):
    """Paged twin of _fixup_slots_body: the same span permute
    routed through the block tables (out-of-range destinations fall
    on the reserved null page 0)."""
    return NDArray(pos), block.permute_pool_span(
        caches, NDArray(tables), NDArray(pos), NDArray(src_lane))


class _Program(NamedTuple):
    """One row of PROGRAMS."""
    body: Callable
    #: the mixer's cache form the body drives; None for the fix-ups,
    #: which are operations on cache leaves, not forms
    form: Optional[str] = None
    #: index (among the extras) of the block tables, whose shape enters
    #: the key
    tables: Optional[int] = None
    #: names of keyword inputs that are closed over, not traced, and
    #: enter the key by value
    static: Tuple[str, ...] = ()
    #: a kernel choice baked at trace time: its verdict enters the key
    gate: Optional[Callable[[], bool]] = None
    #: a fix-up: no tokens come in (the LAST input, src_lane, leads
    #: the key in their place) and no logits come out (_run returns the
    #: caches alone)
    caches_only: bool = False


#: kind -> row.  The verify kinds' window width W (tokens (B, W)) comes
#: from the engines' power-of-two ladder, and a tree's perm/depth/anc
#: shapes are functions of (B, W): each verify site compiles at most
#: |ladder| programs — the bounded family the compile discipline allows
#: (C004, never C001) — shared by every tree SHAPE in a bucket.  The
#: fix-ups compile one program per (pool shape, W).
PROGRAMS = {
    "step": _Program(_form_body("step"), "step"),
    "prefill": _Program(_form_body("prefill"), "prefill"),
    "step_slots": _Program(_form_body("step_slots"), "step_slots"),
    "slot_prefill": _Program(_slot_prefill_body, "prefill"),
    "verify_slots": _Program(_form_body("verify_slots"), "verify_slots"),
    "verify_tree_slots": _Program(_form_body("verify_slots", n_tree=2),
                                  "verify_slots"),
    "fixup_slots": _Program(_fixup_slots_body, caches_only=True),
    "step_pages": _Program(_form_body("step_pages"), "step_pages",
                           tables=1, gate=_paged_attn_gate),
    "page_prefill": _Program(_page_prefill_body, "prefill_pages",
                             tables=1, static=("total_len",),
                             gate=_paged_prefill_gate),
    "verify_pages": _Program(_form_body("verify_pages"), "verify_pages",
                             tables=1, gate=_paged_attn_gate),
    "verify_tree_pages": _Program(_form_body("verify_pages", n_tree=3),
                                  "verify_pages", tables=1,
                                  gate=_paged_attn_gate),
    "fixup_pages": _Program(_fixup_pages_body, tables=0,
                            caches_only=True),
}


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

    def _run(self, kind, cache_leaves, *extras, **static):
        """Run one program of PROGRAMS (by name) over ``cache_leaves``:
        key → compile-ledger report → build on a miss → call with the
        live parameter leaves.  ``extras`` are the kind's replicated
        inputs in its body's order; ``static`` (``total_len``) is
        closed over and keyed, not traced.  Returns (logits,
        new_cache_leaves), or the new leaves alone for a kind that
        computes no logits."""
        row = PROGRAMS[kind]
        # the input the ledger reports and whose shape — and dtype, for
        # tokens — leads the key
        lead = extras[-1] if row.caches_only else extras[0]
        key = (kind, _cache_shapes(cache_leaves), _cache_dt(cache_leaves),
               lead.shape)
        if not row.caches_only:
            key += (lead.dtype,)
        if row.tables is not None:
            key += (extras[row.tables].shape,)
        key += tuple(static.get(name) for name in row.static)
        if row.gate is not None:
            key += (row.gate(),)
        hit = key in self._jit_cache
        self._ledger_report(kind, cache_leaves, (lead,), hit)
        if not hit:
            self._jit_cache[key] = self._build_program(
                functools.partial(row.body, **static), cache_leaves,
                n_extra_inputs=len(extras))
        out = self._jit_cache[key](self._live_param_leaves(), cache_leaves,
                                   *extras)
        return out[1] if row.caches_only else out

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
        logits, cache_leaves = self._run("prefill", cache_leaves, raw)
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
                logits, cache_leaves = self._run(
                    "step", cache_leaves, nxt, jnp.int32(pos))
        out = jnp.concatenate([t._data for t in tokens], axis=1)
        return NDArray(out)
