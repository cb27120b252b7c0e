"""memory_estimate: sharding-aware per-device HBM cost model.

The reference ran nnvm's PlanMemory pass — static buffer assignment over
the graph before execution; on TPU the analogous question is "does this
program fit in HBM per device, under this PartitionSpec/mesh?" and the
answer usually arrives as an opaque RESOURCE_EXHAUSTED deep inside the
first compile.  This pass answers it statically:

- **Symbol graphs** (:func:`estimate_graph_memory`): reuses
  ``Symbol._propagate`` — the same shape/dtype propagation walk
  ``verify_graph`` uses — then runs a liveness scan over the topological
  schedule: params + inputs resident throughout, each op output live
  from its def to its last consumer, graph outputs live to the end.
- **Jittable callables** (:func:`estimate_jit_memory`): the same
  liveness scan over the ``jax.make_jaxpr`` equation list (call-like
  sub-jaxprs — pjit, remat, custom_vjp — contribute their inner peak
  while executing, and free an argument nothing reads after them at
  its last reader inside), which covers CachedOp-style compiled programs,
  decode steps with KV caches, and trainer steps.
- **KV caches** (:func:`kv_cache_residency`): persistent cache bytes for
  a block's ``init_cache`` under a cache PartitionSpec, abstractly
  evaluated (no allocation).  :func:`paged_kv_cache_residency` prices
  the BLOCK-PAGED layout (PagedContinuousBatchingEngine): bytes per
  page, pages resident vs free, and the bytes cross-request prefix
  sharing is saving — refcounted pages are priced ONCE, not
  per-request, which is what a ``check_memory`` budget over the paged
  pool inherits for free (the pool is one allocation whatever the
  sharing degree).

Per-device accounting: a tensor matched to a PartitionSpec divides by
the product of the mesh-axis sizes it is sharded over (ceil per dim —
GSPMD's padding rule).  Intermediates are counted replicated unless the
caller provides specs — an upper bound, which is the safe direction for
a fit check.  The estimator is cross-checked against
``jax.jit(...).lower().compile().memory_analysis()`` on CPU in
tests/test_memory_estimate.py (within 10% on the reference graphs).

Diagnostics (pass name ``memory_estimate``; M0xx):

==========  ========  =====================================================
code        severity  meaning
==========  ========  =====================================================
M001        ERROR     estimated per-device bytes exceed the budget
M002        WARNING   estimate within budget but above the headroom
                      fraction (default 90%) — one growth step from OOM
M003        INFO      accounting breakdown (params / inputs / activations
                      peak / kv cache / outputs), always emitted
M004        INFO      top liveness contributors (largest intermediates)
M005        WARNING   nodes whose shapes could not be inferred — the
                      estimate is a LOWER bound
M006        ERROR     host-RAM KV tier exceeds its host budget (the
                      hierarchical cache's spilled chains live in host
                      memory, never HBM — they are budgeted separately)
M007        INFO      per-grid-step VMEM pricing of a Pallas kernel call
                      (emitted by the ``kernel_check`` pass from
                      :func:`kernel_vmem_estimate` — the on-chip sibling
                      of the M003 HBM breakdown)
==========  ========  =====================================================

Beside the HBM model this module also prices **VMEM** — the ~16 MiB
on-chip budget every Pallas grid step must fit in
(:func:`kernel_vmem_estimate`, consumed by
:mod:`mxtpu.analysis.kernel_check` for its K003 verdict).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .diagnostics import Diagnostic, Report, Severity, register_pass

__all__ = ["MemoryEstimate", "estimate_graph_memory", "estimate_jit_memory",
           "kv_cache_residency", "paged_kv_cache_residency", "check_memory",
           "xla_memory_stats", "parse_bytes", "format_bytes",
           "LANE", "sublane_tile", "kernel_vmem_estimate",
           "kernel_hbm_traffic"]

_PASS = "memory_estimate"

# variables with these suffixes are parameters (resident weights), the
# rest are data inputs — accounting split only; both are resident
_PARAM_SUFFIXES = ("weight", "bias", "gamma", "beta", "moving_mean",
                   "moving_var", "running_mean", "running_var")


class MemoryEstimate:
    """Per-device byte accounting for one program/graph."""

    __slots__ = ("param_bytes", "input_bytes", "activation_peak_bytes",
                 "output_bytes", "kv_cache_bytes", "contributors",
                 "unknown_nodes", "n_values")

    def __init__(self):
        self.param_bytes = 0
        self.input_bytes = 0
        self.activation_peak_bytes = 0   # peak live intermediates+outputs
        self.output_bytes = 0
        self.kv_cache_bytes = 0
        self.contributors: List[Tuple[str, int]] = []
        self.unknown_nodes: List[str] = []
        self.n_values = 0

    @property
    def total_bytes(self) -> int:
        """Peak per-device residency: resident tensors (params, inputs,
        KV caches) plus the activation-liveness peak (which includes the
        outputs at schedule end)."""
        return (self.param_bytes + self.input_bytes + self.kv_cache_bytes
                + self.activation_peak_bytes)

    def breakdown(self) -> Dict[str, int]:
        return {"params": self.param_bytes, "inputs": self.input_bytes,
                "kv_cache": self.kv_cache_bytes,
                "activation_peak": self.activation_peak_bytes,
                "outputs": self.output_bytes,
                "total": self.total_bytes}

    def __repr__(self):
        return "<MemoryEstimate %s>" % ", ".join(
            "%s=%s" % (k, format_bytes(v))
            for k, v in self.breakdown().items())


# -- byte helpers ---------------------------------------------------------

def format_bytes(n: int) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return ("%d%s" % (n, unit) if unit == "B"
                    else "%.2f%s" % (n, unit))
        n = n / 1024
    return str(n)


def parse_bytes(text) -> int:
    """'8GB' / '512MiB' / '1e9' → bytes (decimal suffixes are power-of-
    1024 too: HBM budgets are conventionally binary)."""
    if isinstance(text, (int, float)):
        return int(text)
    s = str(text).strip().lower()
    mult = 1
    for suf, m in (("tib", 1024 ** 4), ("tb", 1024 ** 4),
                   ("gib", 1024 ** 3), ("gb", 1024 ** 3),
                   ("mib", 1024 ** 2), ("mb", 1024 ** 2),
                   ("kib", 1024), ("kb", 1024), ("b", 1)):
        if s.endswith(suf):
            mult = m
            s = s[:-len(suf)].strip()
            break
    return int(float(s) * mult)


def _axis_sizes(mesh) -> Dict[str, int]:
    if mesh is None:
        return {}
    sizes = getattr(mesh, "axis_sizes", None)
    if sizes is not None:
        return dict(sizes)
    if isinstance(mesh, dict):
        return dict(mesh)
    names = getattr(mesh, "axis_names", None)
    devs = getattr(mesh, "devices", None)
    if names is not None and devs is not None:
        return dict(zip(names, devs.shape))
    return {}


def _itemsize(dtype) -> int:
    import jax.numpy as jnp
    try:
        return jnp.dtype(dtype).itemsize
    except TypeError:
        return 4


def _sharded_nbytes(shape, dtype, spec, axis_sizes) -> int:
    """Per-device bytes of a tensor under a PartitionSpec (ceil per
    sharded dim — GSPMD pads uneven shards)."""
    n = _itemsize(dtype)
    for i, dim in enumerate(shape):
        shards = 1
        if spec is not None and i < len(spec) and spec[i] is not None:
            axes = spec[i] if isinstance(spec[i], tuple) else (spec[i],)
            for a in axes:
                shards *= axis_sizes.get(a, 1)
        n *= math.ceil(dim / shards) if shards > 1 else dim
    return n


# -- Symbol-graph path ----------------------------------------------------

def estimate_graph_memory(sym, known_shapes: Optional[dict] = None,
                          rules=None, mesh=None,
                          kv_caches: Sequence[Tuple[tuple, Any]] = (),
                          params: Optional[set] = None,
                          **shape_kwargs) -> MemoryEstimate:
    """Estimate per-device memory of a Symbol graph.

    known_shapes/**shape_kwargs: input shapes (``infer_shape``
    convention).  rules: a ShardingRules mapping variable names to
    PartitionSpecs (params divide by their shard count); mesh: DeviceMesh
    / jax Mesh / ``{axis: size}`` dict.  kv_caches: extra persistent
    (shape, dtype) residents (use :func:`kv_cache_residency` to derive
    them from a block).  params: explicit set of variable names to count
    as parameters; default is the ``_weight``/``_bias``/... suffix
    heuristic (classification only affects the breakdown, not the
    total).
    """
    est = MemoryEstimate()
    known = dict(known_shapes or {})
    known.update(shape_kwargs)
    axis_sizes = _axis_sizes(mesh)

    res = sym._propagate(known)
    topo = sym._topo()

    # resident graph inputs
    for node in topo:
        if node.op is not None:
            continue
        shape = res.var_shapes.get(node.name)
        if shape is None:
            est.unknown_nodes.append(node.name)
            continue
        dt = res.dtypes.get((id(node), 0), "float32")
        spec = None
        if rules is not None:
            try:
                spec = rules.spec_for(node.name, len(shape))
            except ValueError:
                spec = None
        nbytes = _sharded_nbytes(shape, dt, spec, axis_sizes)
        is_param = (node.name in params if params is not None
                    else node.name.endswith(_PARAM_SUFFIXES))
        if is_param:
            est.param_bytes += nbytes
        else:
            est.input_bytes += nbytes

    for shape, dt in kv_caches:
        est.kv_cache_bytes += _sharded_nbytes(tuple(shape), dt, None,
                                              axis_sizes)

    # liveness over the op schedule
    schedule = [n for n in topo if n.op is not None]
    order = {id(n): i for i, n in enumerate(schedule)}
    last_use: Dict[Tuple[int, int], int] = {}
    for n in schedule:
        for s in n.inputs:
            if s._node.op is None:
                continue  # inputs are resident, not liveness-tracked
            key = (id(s._node), s._index)
            last_use[key] = max(last_use.get(key, -1), order[id(n)])
    out_entries = set()
    for n, i in sym._output_entries():
        if n.op is not None:
            out_entries.add((id(n), i))
            last_use[(id(n), i)] = len(schedule)  # live to the end

    sizes: Dict[Tuple[int, int], int] = {}
    names: Dict[Tuple[int, int], str] = {}
    for n in schedule:
        for i in range(n.num_outputs):
            key = (id(n), i)
            shape = res.shapes.get(key)
            if shape is None:
                if n.name not in est.unknown_nodes:
                    est.unknown_nodes.append(n.name)
                continue
            dt = res.dtypes.get(key, "float32")
            sizes[key] = _sharded_nbytes(shape, dt, None, axis_sizes)
            names[key] = n.name if n.num_outputs == 1 \
                else "%s[%d]" % (n.name, i)

    live: Dict[Tuple[int, int], int] = {}
    running = 0
    peak = 0
    peak_set: List[Tuple[str, int]] = []
    for step, n in enumerate(schedule):
        for i in range(n.num_outputs):
            key = (id(n), i)
            if key in sizes and key not in live and \
                    last_use.get(key, -1) >= step:
                live[key] = sizes[key]
                running += sizes[key]
        if running > peak:
            peak = running
            peak_set = sorted(((names[k], v) for k, v in live.items()),
                              key=lambda kv: -kv[1])[:8]
        for key in [k for k, lu in last_use.items()
                    if lu == step and k in live]:
            running -= live.pop(key)

    est.activation_peak_bytes = peak
    est.output_bytes = sum(sizes.get(k, 0) for k in out_entries)
    est.contributors = peak_set
    est.n_values = len(sizes)
    return est


# -- jaxpr path -----------------------------------------------------------

# primitives that carry an inner jaxpr, by the names JAX 0.9 gives them
# (a nested ``jax.jit`` is "jit" there; it was "pjit")
_CALL_PRIMITIVES = {"jit", "closed_call", "call", "custom_jvp_call",
                    "custom_vjp_call", "remat2", "custom_lin"}


def _inner_jaxpr(eqn):
    p = eqn.params
    for k in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        j = p.get(k)
        if j is not None:
            return getattr(j, "jaxpr", j)
    return None


def _aval_nbytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    n = _itemsize(getattr(aval, "dtype", "float32"))
    for d in shape:
        n *= int(d)
    return n


# pure-layout primitives: same-bytes views XLA fuses into the consumer
# (or bitcasts) instead of materializing — their outputs alias the input
_LAYOUT_PRIMS = {"transpose", "reshape", "squeeze", "expand_dims",
                 "rev", "bitcast_convert_type", "copy"}


def _jaxpr_liveness_peak(jaxpr, dying=()) -> int:
    """Peak live intermediate bytes over a jaxpr's equation schedule
    (outvars live to the end; invars/constvars excluded — the caller
    accounts them as resident).  Layout ops (transpose/reshape/...)
    alias their input: they add no bytes, and extend the aliased
    value's liveness instead.  ``dying`` are the invars that the caller
    holds for nothing after this call: they are counted here, live from
    the start to their last consumer, as XLA frees them once the call is
    inlined."""
    from jax.extend.core import Literal

    eqns = jaxpr.eqns
    defined = set(dying)
    for eqn in eqns:
        for v in eqn.outvars:
            defined.add(v)

    # alias classes: out -> canonical root (resolved transitively since
    # eqns are processed in def order)
    root: Dict[Any, Any] = {}
    for eqn in eqns:
        if eqn.primitive.name in _LAYOUT_PRIMS and len(eqn.outvars) == 1:
            srcs = [v for v in eqn.invars
                    if not isinstance(v, Literal)]
            out = eqn.outvars[0]
            if len(srcs) == 1 and _aval_nbytes(
                    getattr(out, "aval", None)) == _aval_nbytes(
                    srcs[0].aval):
                root[out] = root.get(srcs[0], srcs[0])

    def canon(v):
        return root.get(v, v)

    last_use: Dict[Any, int] = {}
    for n, eqn in enumerate(eqns):
        for v in eqn.invars:
            if isinstance(v, Literal):
                continue
            c = canon(v)
            if c in defined:
                last_use[c] = n
    for v in jaxpr.outvars:
        if not isinstance(v, Literal):
            c = canon(v)
            if c in defined:
                last_use[c] = len(eqns)

    live: Dict[Any, int] = {v: _aval_nbytes(v.aval) for v in dying
                            if v in last_use}
    running = sum(live.values())
    peak = running
    for n, eqn in enumerate(eqns):
        inner = (_inner_jaxpr(eqn)
                 if eqn.primitive.name in _CALL_PRIMITIVES else None)
        transient = 0
        if inner is not None:
            # a value whose last consumer is this call is the call's to
            # free: it leaves the count here and joins the inner one
            passed = [canon(v) for v in eqn.invars
                      if not isinstance(v, Literal)]
            handed = [(canon(v), w)
                      for v, w in zip(eqn.invars, inner.invars)
                      if not isinstance(v, Literal) and canon(v) in live
                      and last_use[canon(v)] == n
                      and passed.count(canon(v)) == 1
                      ] if len(inner.invars) == len(eqn.invars) else []
            # the inner peak excludes the inner invars (resident at the
            # outer level) but INCLUDES the inner outputs (live to the
            # inner end); the outer level counts this eqn's outvars
            # again below, so subtract exactly that overlap
            out_bytes = sum(_aval_nbytes(getattr(v, "aval", None))
                            for v in inner.outvars
                            if not isinstance(v, Literal))
            transient = max(0, _jaxpr_liveness_peak(
                inner, [w for _, w in handed]) - out_bytes) - sum(
                live[c] for c, _ in handed)
        elif eqn.primitive.name == "scan":
            body = _inner_jaxpr(eqn)
            if body is not None:
                transient = _jaxpr_liveness_peak(body)
        elif eqn.primitive.name == "cond":
            branches = eqn.params.get("branches", ())
            transient = max((_jaxpr_liveness_peak(
                getattr(b, "jaxpr", b)) for b in branches), default=0)
        for v in eqn.outvars:
            c = canon(v)
            if c is not v:
                continue  # layout alias: no new allocation
            nb = _aval_nbytes(getattr(v, "aval", None))
            if last_use.get(c, -1) >= n:
                if c not in live:
                    live[c] = nb
                    running += nb
            else:
                transient += nb  # dead-on-arrival (DropVar) output
        peak = max(peak, running + transient)
        for v in [v for v, lu in last_use.items() if lu == n and v in live]:
            running -= live.pop(v)
    return peak


def estimate_jit_memory(fn, *sample_args,
                        arg_specs: Optional[Sequence] = None,
                        mesh=None, param_argnums: Sequence[int] = (),
                        kv_caches: Sequence[Tuple[tuple, Any]] = (),
                        static_argnums: Sequence[int] = (),
                        activation_shards: int = 1) -> MemoryEstimate:
    """Estimate per-device memory of a jittable callable on abstract
    inputs (``jax.ShapeDtypeStruct`` or concrete arrays; never executes).

    arg_specs: optional PartitionSpecs aligned with the FLATTENED leaves
    of sample_args (None = replicated); mesh supplies axis sizes.
    param_argnums: top-level argument positions counted as parameters in
    the breakdown (default: everything is ``inputs``).
    activation_shards: divisor for intermediate liveness when GSPMD
    shards the program's activations (e.g. the tp degree of a
    Megatron-sharded block, whose matmul intermediates are tp-sharded);
    the default 1 counts intermediates replicated — the safe upper
    bound for a fit check.
    """
    import jax
    from jax.extend.core import Literal

    closed = jax.make_jaxpr(
        fn, static_argnums=tuple(static_argnums))(*sample_args)
    jaxpr = closed.jaxpr
    est = MemoryEstimate()
    axis_sizes = _axis_sizes(mesh)

    # resident: flattened args + closed-over consts
    leaves_per_arg = [
        (i, jax.tree_util.tree_leaves(a)) for i, a in
        enumerate(sample_args) if i not in set(static_argnums)]
    flat: List[Tuple[int, Any]] = [(i, leaf) for i, ls in leaves_per_arg
                                   for leaf in ls]
    specs = list(arg_specs or [])
    for k, (argnum, leaf) in enumerate(flat):
        spec = specs[k] if k < len(specs) else None
        nbytes = _sharded_nbytes(tuple(leaf.shape), leaf.dtype, spec,
                                 axis_sizes)
        if argnum in set(param_argnums):
            est.param_bytes += nbytes
        else:
            est.input_bytes += nbytes
    for c in closed.consts:
        est.input_bytes += _aval_nbytes(
            jax.api_util.shaped_abstractify(c))

    for shape, dt in kv_caches:
        est.kv_cache_bytes += _sharded_nbytes(tuple(shape), dt, None,
                                              axis_sizes)

    est.activation_peak_bytes = _jaxpr_liveness_peak(jaxpr) // max(
        int(activation_shards), 1)
    est.output_bytes = sum(
        _aval_nbytes(getattr(v, "aval", None)) for v in jaxpr.outvars
        if not isinstance(v, Literal))
    est.n_values = sum(len(e.outvars) for e in jaxpr.eqns)
    return est


def _flat_cache_pair(pair):
    """Flatten one layer's (k, v) cache entry to raw arrays — an int8
    cache leaf is a (payload, scales) pair (models.transformer), a float
    leaf one array."""
    out = []
    for leaf in pair:
        if isinstance(leaf, tuple):
            out.extend(part._data for part in leaf)
        else:
            out.append(leaf._data)
    return tuple(out)


def kv_cache_residency(block, batch: int, max_length: int,
                       dtype: str = "float32", cache_spec=None,
                       mesh=None) -> Tuple[int, List[Tuple[tuple, str]]]:
    """Per-device bytes (and the (shape, dtype) list) of a block's KV
    cache at ``(batch, max_length)`` under ``cache_spec`` — abstractly
    evaluated via ``jax.eval_shape``, no allocation."""
    import jax

    def _mk():
        return tuple(_flat_cache_pair(pair)
                     for pair in block.init_cache(batch, max_length,
                                                  dtype))

    try:
        leaves = jax.eval_shape(_mk)
    except Exception:
        leaves = _mk()  # tiny blocks: concrete fallback
    axis_sizes = _axis_sizes(mesh)
    shapes: List[Tuple[tuple, str]] = []
    total = 0
    for pair in leaves:
        for leaf in pair:
            # an int8 cache's (B, KV, T) scale tensors drop only the
            # trailing head-dim, so the payload spec prices them too
            # (_sharded_nbytes ignores spec axes past the leaf's ndim)
            shapes.append((tuple(leaf.shape), str(leaf.dtype)))
            total += _sharded_nbytes(tuple(leaf.shape), leaf.dtype,
                                     cache_spec, axis_sizes)
    return total, shapes


def paged_kv_cache_residency(block, num_blocks: int, block_size: int,
                             dtype: str = "float32", cache_spec=None,
                             mesh=None, blocks_in_use: Optional[int] = None,
                             shared_extra_refs: int = 0,
                             pinned_blocks: int = 0,
                             spilled_blocks: int = 0,
                             engine=None) -> Dict[str, Any]:
    """Per-device byte accounting of a BLOCK-PAGED KV cache
    (:class:`~mxtpu.parallel.PagedContinuousBatchingEngine`):
    abstractly evaluated like :func:`kv_cache_residency`, plus the
    paged split the slot layout cannot express —

    - ``bytes_per_block``: per-device bytes one page costs across every
      layer's (k, v) pools (the granularity admission allocates at);
    - ``resident_bytes`` / ``free_bytes``: the pool split at
      ``blocks_in_use`` allocated pages (the +1 null page is counted in
      ``total_bytes`` — it is real HBM — but never in the free pool);
    - ``shared_savings_bytes``: ``shared_extra_refs`` — the sum of
      (refcount − 1) over shared pages — times ``bytes_per_block``:
      what an unshared layout would ADDITIONALLY hold resident right
      now.  Refcounted pages are deliberately priced ONCE in
      ``resident_bytes`` — a page shared by N requests is one page.
    - the HIERARCHICAL tiers (docs/inference.md), priced SEPARATELY:
      ``pinned_bytes`` = ``pinned_blocks`` × bytes_per_block is the
      slice of ``resident_bytes`` the cache is holding past its last
      table reference — it counts against the HBM budget like any
      resident page; ``spilled_bytes_host`` = ``spilled_blocks`` ×
      ``bytes_per_block_host`` prices the host-RAM tier at UNSHARDED
      page bytes (host copies are full replicated pages) and belongs
      to a HOST budget, never the HBM one (:func:`check_memory`'s
      ``host_budget_bytes``).

    Pass a live engine (``engine=``) to read ``num_blocks`` /
    ``block_size`` / occupancy / sharing / tier counters — and the
    pool's actual cache dtype, sharding spec and mesh — from it
    instead of spelling them out."""
    import jax

    if engine is not None:
        st = engine.stats
        num_blocks = st["num_blocks"]
        block_size = st["block_size"]
        blocks_in_use = st["blocks_in_use"]
        shared_extra_refs = st["shared_extra_refs"]
        pinned_blocks = st.get("pinned_blocks", 0)
        spilled_blocks = st.get("spilled_blocks", 0)
        dtype = engine._cache_dtype
        cache_spec = engine._dec._cache_spec
        mesh = engine._mesh

    def _mk():
        return tuple(_flat_cache_pair(pair)
                     for pair in block.init_block_pool(
                         num_blocks + 1, block_size, dtype))

    try:
        leaves = jax.eval_shape(_mk)
    except Exception:
        leaves = _mk()  # tiny blocks: concrete fallback
    axis_sizes = _axis_sizes(mesh)
    shapes: List[Tuple[tuple, str]] = []
    total = 0
    per_block = 0
    per_block_host = 0
    for pair in leaves:
        for leaf in pair:
            # int8 pools carry (N, KV, bs) scale tensors page-aligned
            # beside their payload pages: same axis-0 page granularity,
            # same spec truncation as kv_cache_residency — so
            # bytes_per_block prices a page's payload PLUS its scales
            shapes.append((tuple(leaf.shape), str(leaf.dtype)))
            nbytes = _sharded_nbytes(tuple(leaf.shape), leaf.dtype,
                                     cache_spec, axis_sizes)
            total += nbytes
            per_block += nbytes // leaf.shape[0]
            # host copies are unsharded full pages (the swap program
            # replicates its read)
            per_block_host += _sharded_nbytes(
                tuple(leaf.shape), leaf.dtype, None,
                axis_sizes) // leaf.shape[0]
    out = {
        "total_bytes": total,
        "bytes_per_block": per_block,
        "bytes_per_block_host": per_block_host,
        "num_blocks": int(num_blocks),
        "block_size": int(block_size),
        "shapes": shapes,
    }
    if blocks_in_use is not None:
        out["blocks_in_use"] = int(blocks_in_use)
        out["resident_bytes"] = int(blocks_in_use) * per_block
        out["free_bytes"] = (int(num_blocks)
                             - int(blocks_in_use)) * per_block
    out["shared_extra_refs"] = int(shared_extra_refs)
    out["shared_savings_bytes"] = int(shared_extra_refs) * per_block
    out["pinned_blocks"] = int(pinned_blocks)
    out["pinned_bytes"] = int(pinned_blocks) * per_block
    out["spilled_blocks"] = int(spilled_blocks)
    out["spilled_bytes_host"] = int(spilled_blocks) * per_block_host
    return out


# -- the XLA cross-check --------------------------------------------------

def xla_memory_stats(fn, *sample_args, in_shardings=None,
                     out_shardings=None, donate_argnums=(),
                     static_argnums=()) -> Dict[str, int]:
    """Ground truth: compile ``fn`` (abstract — no execution) and return
    ``compile().memory_analysis()`` totals.  ``total`` sums argument +
    output + temp + alias bytes, the figure :class:`MemoryEstimate`
    ``total_bytes`` models (tests assert agreement within tolerance on
    the CPU reference graphs)."""
    import jax

    kw = {}
    if in_shardings is not None:
        kw["in_shardings"] = in_shardings
    if out_shardings is not None:
        kw["out_shardings"] = out_shardings
    jitted = jax.jit(fn, donate_argnums=tuple(donate_argnums),
                     static_argnums=tuple(static_argnums), **kw)
    ma = jitted.lower(*sample_args).compile().memory_analysis()
    out = {"argument": int(ma.argument_size_in_bytes),
           "output": int(ma.output_size_in_bytes),
           "temp": int(ma.temp_size_in_bytes),
           "alias": int(ma.alias_size_in_bytes)}
    out["total"] = sum(out.values())
    return out


# -- the VMEM model (Pallas kernel calls) ---------------------------------
# The HBM model above answers "does the program fit per device"; this
# answers the on-chip sibling: "does ONE GRID STEP of a Pallas kernel fit
# in VMEM".  mxtpu.analysis.kernel_check turns the estimate into its
# K003/M007 diagnostics; the descriptors it consumes are duck-typed (any
# object with .operands/.scratch/.prefetch of the KernelSpec shape).

#: TPU lane width: the last dim of every VMEM tile is 128 wide.
LANE = 128

# minimum sublane tile (second-to-last dim) per element width: fp32/int32
# tile (8, 128), bf16/fp16 (16, 128), int8/fp8 (32, 128)
_SUBLANE_BY_ITEMSIZE = {1: 32, 2: 16, 4: 8}


def sublane_tile(dtype) -> int:
    """Minimum second-to-last tile dim for ``dtype`` on TPU (8 fp32,
    16 bf16, 32 int8 — the dtype packing rule kernel_check's K002
    enforces)."""
    return _SUBLANE_BY_ITEMSIZE.get(_itemsize(dtype), 8)


def _tile_padded_bytes(shape, dtype) -> int:
    """Bytes one block/scratch buffer occupies in VMEM: the last dim
    pads to the 128-lane tile, the second-to-last to the dtype's sublane
    tile (partial tiles are allocated whole); leading dims multiply."""
    shape = tuple(int(d) for d in shape)
    if not shape:
        return _itemsize(dtype)
    dims = list(shape)
    dims[-1] = math.ceil(dims[-1] / LANE) * LANE
    if len(dims) >= 2:
        sub = sublane_tile(dtype)
        dims[-2] = math.ceil(dims[-2] / sub) * sub
    n = _itemsize(dtype)
    for d in dims:
        n *= d
    return n


def kernel_vmem_estimate(spec, buffering: int = 2) -> Dict[str, Any]:
    """Per-grid-step VMEM bytes of one Pallas kernel call described by a
    :class:`~mxtpu.analysis.kernel_check.KernelSpec`.

    The model: every in/out block is resident tile-padded and — because
    the Pallas TPU pipeline double-buffers blocks across grid steps —
    counted ``buffering`` times (default 2; pass 1 for the unpipelined
    lower bound); scratch buffers are single-resident; scalar-prefetch
    operands live in SMEM and are priced separately
    (``smem_prefetch_bytes``), never against the VMEM total.

    Returns a dict: ``in_bytes`` / ``out_bytes`` (single-copy block
    sums), ``scratch_bytes``, ``smem_prefetch_bytes``, ``buffering``,
    ``total_bytes`` = buffering × (in + out) + scratch, and
    ``per_operand`` — (name, kind, block_shape, dtype, padded bytes)
    tuples for the breakdown diagnostics.
    """
    in_bytes = 0
    out_bytes = 0
    per_operand = []
    for op in spec.operands:
        nbytes = _tile_padded_bytes(op.block_shape, op.dtype)
        if op.kind == "out":
            out_bytes += nbytes
        else:
            in_bytes += nbytes
        per_operand.append((op.name, op.kind, tuple(op.block_shape),
                            str(op.dtype), nbytes))
    scratch_bytes = 0
    for sc in spec.scratch:
        nbytes = _tile_padded_bytes(sc.shape, sc.dtype)
        scratch_bytes += nbytes
        per_operand.append((sc.name, "scratch", tuple(sc.shape),
                            str(sc.dtype), nbytes))
    import numpy as np

    smem = 0
    for pf in spec.prefetch:
        vals = np.asarray(pf.values)
        smem += int(vals.size) * _itemsize(vals.dtype)
    buffering = max(int(buffering), 1)
    return {
        "in_bytes": in_bytes,
        "out_bytes": out_bytes,
        "scratch_bytes": scratch_bytes,
        "smem_prefetch_bytes": smem,
        "buffering": buffering,
        "total_bytes": buffering * (in_bytes + out_bytes) + scratch_bytes,
        "per_operand": per_operand,
    }


def kernel_hbm_traffic(spec, workload=None) -> Dict[str, Any]:
    """Deterministic per-invocation HBM traffic of one Pallas kernel
    call described by a :class:`~mxtpu.analysis.kernel_check.KernelSpec`
    — the DMA-count sibling of :func:`kernel_vmem_estimate` (which
    answers residency, not traffic).

    The model mirrors the Pallas TPU pipeline: one block DMA per grid
    step per operand, ELIDED when the operand's index map returns the
    same block index as the previous step (the pipeline skips the copy
    for an unchanged window — this is what makes the paged kernels'
    null-page-0 routing a no-op read: every padded step lands on the
    same page).  Each operand's index map is evaluated over the FULL
    grid in execution order (last axis innermost) with the spec's
    scalar-prefetch values, so ragged block-table walks are priced
    against the real tables: the decode kernel's pool traffic comes out
    O(valid pages), not O(table width), and the claim is a numeric
    assertion, not prose.

    ``workload``: optional dict — ``max_grid_points`` (default 1<<22)
    caps the sweep; a grid past the cap raises instead of sampling,
    because a *deterministic* cost model must not silently verdict a
    partial walk.

    Returns per-operand ``fetches`` (elided-DMA count), ``unique_blocks``
    (distinct windows touched), ``block_bytes`` (payload bytes, not
    tile-padded — traffic counts bytes moved, not VMEM allocated) and
    ``bytes``; plus ``in_bytes`` / ``out_bytes`` / ``total_bytes`` and
    ``grid_points``.
    """
    import numpy as np

    workload = dict(workload or {})
    cap = int(workload.get("max_grid_points", 1 << 22))
    grid = tuple(max(int(g), 1) for g in spec.grid)
    total = 1
    for g in grid:
        total *= g
    if total > cap:
        raise ValueError(
            "kernel_hbm_traffic: grid %r has %d points, past the %d "
            "cap — this model sweeps the FULL grid (deterministic "
            "traffic, no sampling); raise workload['max_grid_points']"
            % (grid, total, cap))

    # lazy import: kernel_check imports this module at load time
    from .kernel_check import _as_index_arrays, _prefetch_values

    axes = [np.arange(g) for g in grid]
    mesh = np.meshgrid(*axes, indexing="ij") if axes else []
    coords = [m.reshape(-1) for m in mesh]
    npoints = len(coords[0]) if coords else 1
    pf_vals = _prefetch_values(spec)

    per_operand: Dict[str, Dict[str, Any]] = {}
    in_bytes = 0
    out_bytes = 0
    for op in spec.operands:
        block_bytes = _itemsize(op.dtype)
        for d in op.block_shape:
            block_bytes *= int(d)
        if op.index_map is None:
            fetches = unique = 1
        else:
            idx = _as_index_arrays(
                op.index_map(*coords, *pf_vals), len(op.block_shape),
                npoints)
            stack = np.stack(idx, axis=1)        # (npoints, ndim)
            changes = int(np.any(stack[1:] != stack[:-1],
                                 axis=1).sum()) if npoints > 1 else 0
            fetches = changes + 1
            unique = int(len(np.unique(stack, axis=0)))
        nbytes = fetches * block_bytes
        per_operand[op.name] = {
            "kind": op.kind, "fetches": fetches,
            "unique_blocks": unique, "block_bytes": block_bytes,
            "bytes": nbytes}
        if op.kind == "out":
            out_bytes += nbytes
        else:
            in_bytes += nbytes
    return {
        "per_operand": per_operand,
        "in_bytes": in_bytes,
        "out_bytes": out_bytes,
        "total_bytes": in_bytes + out_bytes,
        "grid_points": npoints,
    }


# -- the registered pass --------------------------------------------------

def check_memory(target, budget_bytes=None, known_shapes=None, rules=None,
                 mesh=None, kv_caches=(), sample_args=None,
                 headroom: float = 0.9, top_k: int = 3,
                 host_budget_bytes=None, host_kv_bytes: int = 0,
                 **shape_kwargs) -> Report:
    """Budget check over a Symbol graph (or a jittable callable when
    ``sample_args`` is given); returns a Report of M0xx diagnostics.

    budget_bytes: int or a string like ``"16GiB"``; None checks nothing
    but still reports the M003 breakdown.

    The hierarchical cache's tiers are priced SEPARATELY
    (docs/inference.md "Hierarchical prefix cache"): pinned pages are
    part of the device pool — whatever ``kv_caches`` shapes carry them
    already counts against ``budget_bytes`` — while spilled chains
    live in HOST RAM and must not inflate the HBM verdict.  Pass their
    bytes (``paged_kv_cache_residency(...)["spilled_bytes_host"]``) as
    ``host_kv_bytes`` with a ``host_budget_bytes`` cap to get an M006
    ERROR when the host tier outgrows its budget."""
    report = Report()
    if callable(target) and not hasattr(target, "_topo"):
        if sample_args is None:
            raise ValueError(
                "check_memory on a callable needs sample_args "
                "(ShapeDtypeStructs or arrays)")
        est = estimate_jit_memory(target, *sample_args, mesh=mesh,
                                  kv_caches=kv_caches)
        subject = getattr(target, "__name__", repr(target))
    else:
        est = estimate_graph_memory(target, known_shapes=known_shapes,
                                    rules=rules, mesh=mesh,
                                    kv_caches=kv_caches, **shape_kwargs)
        subject = getattr(target, "name", "graph")

    bd = est.breakdown()
    # host tier reported beside the device breakdown but NEVER summed
    # into it — spilled chains are host RAM, not HBM
    bd3 = dict(bd, host_kv_cache=int(host_kv_bytes)) if host_kv_bytes \
        else bd
    report.add(Diagnostic(
        _PASS, "M003", Severity.INFO, subject,
        "per-device estimate: %s" % ", ".join(
            "%s=%s" % (k, format_bytes(v)) for k, v in bd3.items()),
        details=bd3))
    for name, nbytes in est.contributors[:top_k]:
        report.add(Diagnostic(
            _PASS, "M004", Severity.INFO, name,
            "largest liveness contributor at the activation peak: "
            "%s = %s" % (name, format_bytes(nbytes)),
            details={"bytes": nbytes}))
    if est.unknown_nodes:
        report.add(Diagnostic(
            _PASS, "M005", Severity.WARNING,
            est.unknown_nodes[0],
            "%d node(s) have unknown shapes (%s%s) — the estimate is a "
            "LOWER bound; provide input shapes" % (
                len(est.unknown_nodes),
                ", ".join(est.unknown_nodes[:5]),
                ", …" if len(est.unknown_nodes) > 5 else ""),
            details={"nodes": est.unknown_nodes[:32]}))
    if budget_bytes is not None:
        budget = parse_bytes(budget_bytes)
        total = est.total_bytes
        if total > budget:
            report.add(Diagnostic(
                _PASS, "M001", Severity.ERROR, subject,
                "estimated per-device memory %s exceeds the %s budget "
                "by %s (%s)" % (
                    format_bytes(total), format_bytes(budget),
                    format_bytes(total - budget),
                    ", ".join("%s=%s" % (k, format_bytes(v))
                              for k, v in bd.items()
                              if k != "total" and v)),
                details=bd))
        elif total > headroom * budget:
            report.add(Diagnostic(
                _PASS, "M002", Severity.WARNING, subject,
                "estimated per-device memory %s is within the %s budget "
                "but above %d%% headroom — one growth step from OOM" % (
                    format_bytes(total), format_bytes(budget),
                    int(headroom * 100)),
                details=bd))
    if host_budget_bytes is not None:
        host_budget = parse_bytes(host_budget_bytes)
        if int(host_kv_bytes) > host_budget:
            report.add(Diagnostic(
                _PASS, "M006", Severity.ERROR, subject,
                "host-RAM KV tier %s exceeds the %s host budget by %s "
                "— shrink host_cache_bytes or let the LRU evict "
                "(spilled chains are host memory, priced separately "
                "from the HBM budget)" % (
                    format_bytes(int(host_kv_bytes)),
                    format_bytes(host_budget),
                    format_bytes(int(host_kv_bytes) - host_budget)),
                details={"host_kv_bytes": int(host_kv_bytes),
                         "host_budget_bytes": host_budget}))
    return report


register_pass(_PASS)(check_memory)
