"""kernel_check: static TPU tile-geometry, VMEM-budget, and grid-safety
analysis for Pallas kernels.

The serving/training stack's Pallas kernels (``mxtpu.ops.pallas``:
flash_attention, kda, paged_attention) compile against TPU lowering
constraints — lane-aligned last dims, dtype-dependent sublane tiling,
the ~16 MiB VMEM ceiling per grid step — that until this pass lived only
in docstrings, and whose violation surfaces as an opaque Mosaic lowering
error *on hardware*.  In the NNVM-pass framing the rest of this package
adopts (InferShape/PlanMemory fail loudly per node before execution),
this is the pre-compile pass for kernel *call geometry*: every kernel
module exposes a small :class:`KernelSpec` descriptor — grid, per-operand
block shapes + index maps, scratch shapes, dtypes, scalar-prefetch
operands, as a function of the workload geometry — and the pass verdicts
it entirely on the host, so CPU-only CI can assert TPU-readiness.

Diagnostics (pass name ``kernel_check``; K0xx, plus the M007 VMEM
pricing INFO from :func:`~.memory_estimate.kernel_vmem_estimate`):

==========  ========  =====================================================
code        severity  meaning
==========  ========  =====================================================
K001        ERROR     last block dim splits an axis into chunks that are
                      not a multiple of the 128-lane tile (a block
                      covering the FULL axis is exempt — partial lane
                      tiles pad — unless the dim is a ``strict_dims``
                      tile parameter like head_dim)
K002        ERROR     second-to-last block dim not a multiple of the
                      dtype's sublane tile (8 fp32 / 16 bf16 / 32 int8 —
                      the "block_size ≥ 32 for int8" rule, enforced via
                      ``strict_dims``); a full-axis dim is otherwise
                      exempt (padded partial tile) — a size-1 window
                      into a wider axis is NOT: Mosaic refuses it
K003        ERROR     per-grid-step VMEM estimate (double-buffered in/out
                      blocks + scratch) exceeds the budget (default
                      16 MiB)
K004        ERROR     an index_map can address past the backing array's
                      extent for some in-range grid index (block-table
                      contents are modeled via the spec's scalar-prefetch
                      values — the null-page-0 convention is part of the
                      model, not special-cased)
K005        WARNING   scalar-prefetch table operand not int32, or its
                      value range unvalidated against the page-pool
                      extent (no ``valid_range`` declared)
K006        WARNING   grid ordering revisits a written output block — the
                      output's index map varies in a grid axis that runs
                      INSIDE an axis the output is reduced over (reduced
                      axes must be the innermost suffix)
K007        INFO      geometry is interpret-mode-only: the spec was
                      declared ``interpret=True`` and carries violations
                      that are legal on CPU tests but illegal on TPU — a
                      CPU-green suite must not claim TPU-readiness
K008        INFO      the K004 index-map sweep SAMPLED an oversized grid
                      (small axes full, large axes at edges+midpoint) —
                      the clean verdict is partial, never silent
K009        ERROR     mesh-axis/cache_spec mismatch: the spec declares a
                      shard_map partitioning (``mesh_axis``) whose shard
                      count does not divide the global sharded-axis
                      extent — GSPMD would pad or gather around the
                      kernel instead of running it per-device
M007        INFO      per-grid-step VMEM pricing breakdown (always
                      emitted per spec; PER-SHARD when the spec carries
                      a ``mesh_axis``)
==========  ========  =====================================================

Severity contract: K001–K004 are definite Mosaic-lowering/correctness
defects (ERROR); on a spec declared ``interpret=True`` the
TPU-lowering-only rules (K001/K002/K003) downgrade into one K007 INFO —
out-of-extent indexing (K004) stays an ERROR everywhere, interpret mode
included.  "Passes clean" means zero ERROR, same as every other pass.

Self-application: :func:`default_kernel_specs` builds the three shipped
kernels' descriptors at their real TPU serving/training geometries (fp32
and int8, decode and W-wide verify) and ``check_kernels()`` with no
arguments verdicts them — the merge gate every ROADMAP-item-2 kernel
lands behind (``python -m mxtpu.analysis kernel``, tier-1
``tests/test_kernel_check.py``).
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

from .diagnostics import Diagnostic, Report, Severity, register_pass
from .memory_estimate import (LANE, format_bytes, kernel_vmem_estimate,
                              parse_bytes, sublane_tile)

__all__ = ["BlockOperand", "ScratchOperand", "ScalarPrefetch",
           "KernelSpec", "check_kernels", "default_kernel_specs"]

_PASS = "kernel_check"

#: default per-grid-step budget: the ~16 MiB VMEM per TensorCore
DEFAULT_VMEM_BUDGET = 16 * (1 << 20)
#: what a call may ask for at most (``KernelSpec.vmem_limit``): a v5e
#: TensorCore's physical VMEM
PHYSICAL_VMEM = 128 * (1 << 20)


class BlockOperand:
    """One windowed in/out operand of a pallas_call: the BlockSpec's
    block shape and index map plus the backing array's shape/dtype.

    ``index_map`` mirrors the real BlockSpec's: called with the grid
    indices followed by the spec's scalar-prefetch VALUES (numpy arrays
    — the same positional convention as PrefetchScalarGridSpec), it
    returns per-dim BLOCK indices (element offset = index × block dim).
    The checker evaluates it vectorized over the whole grid, so maps
    written with jnp/np ``where`` and fancy indexing — the real kernel
    maps — evaluate in a handful of dispatches.
    """

    __slots__ = ("name", "kind", "block_shape", "array_shape", "dtype",
                 "index_map", "strict_dims")

    def __init__(self, name: str, kind: str, block_shape: Sequence[int],
                 array_shape: Sequence[int], dtype,
                 index_map: Optional[Callable] = None,
                 strict_dims: Sequence[int] = ()):
        if kind not in ("in", "out"):
            raise ValueError("BlockOperand kind must be 'in' or 'out', "
                             "got %r" % (kind,))
        if len(tuple(block_shape)) != len(tuple(array_shape)):
            # the geometry and extent rules both align block dims with
            # array dims positionally; a rank mismatch would make them
            # disagree (and fail open on the unchecked trailing axes)
            raise ValueError(
                "BlockOperand %r: block_shape %r (rank %d) must have "
                "the same rank as array_shape %r (rank %d)"
                % (name, tuple(block_shape), len(tuple(block_shape)),
                   tuple(array_shape), len(tuple(array_shape))))
        self.name = name
        self.kind = kind
        self.block_shape = tuple(int(d) for d in block_shape)
        self.array_shape = tuple(int(d) for d in array_shape)
        self.dtype = dtype
        self.index_map = index_map
        # negative dim indices whose extent is an engine-CHOSEN tile
        # parameter (head_dim, block_size, q_block): the full-axis
        # exemption never applies there — a sub-tile choice is a real
        # defect the caller can fix, not workload-determined padding
        self.strict_dims = tuple(int(d) for d in strict_dims)

    def __repr__(self):
        return ("<BlockOperand %s %s block=%r array=%r %s>"
                % (self.kind, self.name, self.block_shape,
                   self.array_shape, self.dtype))


class ScratchOperand:
    """One VMEM scratch allocation (pltpu.VMEM(shape, dtype))."""

    __slots__ = ("name", "shape", "dtype")

    def __init__(self, name: str, shape: Sequence[int], dtype):
        self.name = name
        self.shape = tuple(int(d) for d in shape)
        self.dtype = dtype


class ScalarPrefetch:
    """One scalar-prefetch operand (SMEM), with representative VALUES —
    e.g. a model block table using the null-page-0 convention — and the
    extent its values must stay within (``valid_range=(lo, hi)``,
    half-open; None = undeclared, which K005 flags)."""

    __slots__ = ("name", "values", "valid_range")

    def __init__(self, name: str, values,
                 valid_range: Optional[Tuple[int, int]] = None):
        import numpy as np
        self.name = name
        self.values = np.asarray(values)
        self.valid_range = (tuple(int(v) for v in valid_range)
                            if valid_range is not None else None)


class KernelSpec:
    """Statically-checkable descriptor of ONE pallas_call: grid,
    windowed operands, VMEM scratch, scalar-prefetch operands, and
    whether the call is interpret-mode-only (CPU tests).

    ``mesh_axis`` describes a shard_map-partitioned call (the serving
    kernels under a tp-sharded cache): a
    ``(axis_name, shards, global_extent)`` triple — mesh axis name, its
    shard count, and the GLOBAL extent of the sharded operand axis the
    per-shard geometry was derived from (kv heads for the paged
    kernels).  The spec's grid/operands then describe ONE shard, so
    K003 prices the per-device VMEM; a shard count that does not divide
    the global extent is a K009 ERROR.

    ``vmem_limit`` is what the call itself asks of the compiler
    (``CompilerParams(vmem_limit_bytes=...)``): K003 then holds the
    estimate to that, under the chip's physical VMEM."""

    __slots__ = ("name", "grid", "operands", "scratch", "prefetch",
                 "interpret", "mesh_axis", "vmem_limit")

    def __init__(self, name: str, grid: Sequence[int],
                 operands: Sequence[BlockOperand],
                 scratch: Sequence[ScratchOperand] = (),
                 prefetch: Sequence[ScalarPrefetch] = (),
                 interpret: bool = False,
                 mesh_axis: Optional[Tuple] = None,
                 vmem_limit: Optional[int] = None):
        self.name = name
        self.vmem_limit = None if vmem_limit is None else int(vmem_limit)
        self.grid = tuple(int(g) for g in grid)
        self.operands = list(operands)
        self.scratch = list(scratch)
        self.prefetch = list(prefetch)
        self.interpret = bool(interpret)
        if mesh_axis is not None:
            axis, shards = mesh_axis[0], int(mesh_axis[1])
            extent = int(mesh_axis[2]) if len(mesh_axis) > 2 else None
            mesh_axis = (str(axis), shards, extent)
        self.mesh_axis = mesh_axis

    def __repr__(self):
        return ("<KernelSpec %s grid=%r %d operand(s) %d scratch "
                "%d prefetch%s%s>"
                % (self.name, self.grid, len(self.operands),
                   len(self.scratch), len(self.prefetch),
                   " interpret" if self.interpret else "",
                   " mesh_axis=%r" % (self.mesh_axis,)
                   if self.mesh_axis else ""))


# -- geometry rules (K001/K002) -------------------------------------------

def _geometry_violations(spec: KernelSpec) -> List[Tuple[str, str, str]]:
    """(code, operand name, message) for every tile-geometry violation.

    The lane/sublane rules flag tilings that split an axis into
    non-tile-aligned chunks — misaligned strided windows Mosaic cannot
    lower.  One exemption, not applying to an operand's
    ``strict_dims``: a block dim equal to the FULL array extent (no
    tiling choice exists; the hardware pads a partial tile — the
    rep*W-lane query block, conv's H+2 rows).  A size-1 second-to-last
    dim is exempt only on that ground (the array's dim is 1 too): the
    chip's compiler refuses a (1, n) window into a taller 2-D array and
    a (1, 1, n) window into a (N, KV, n) one — the pre-PR-22 lse and
    int8 scale specs.  ``strict_dims`` marks
    engine-CHOSEN tile parameters (head_dim, block_size, q_block): a
    sub-tile value there is the fixable defect this pass exists for —
    the ROADMAP "block_size >= 32 for int8" rule."""
    out = []
    for op in spec.operands:
        bs = op.block_shape
        ar = op.array_shape
        if not bs:
            continue
        strict = {d % len(bs) for d in op.strict_dims}
        last = bs[-1]
        strict_last = (len(bs) - 1) in strict
        full_last = len(ar) >= 1 and last == ar[-1] and not strict_last
        if last % LANE != 0 and not full_last:
            out.append((
                "K001", op.name,
                "operand %r block %r: last dim %d is not a multiple of "
                "the %d-lane tile%s"
                % (op.name, bs, last, LANE,
                   " (a chosen tile parameter — pick a lane-aligned "
                   "value)" if strict_last else
                   " and does not cover the full %d-wide axis"
                   % (ar[-1] if ar else -1))))
        if len(bs) >= 2:
            sub = sublane_tile(op.dtype)
            second = bs[-2]
            strict_second = (len(bs) - 2) in strict
            exempt = not strict_second and second == ar[-2]
            if second % sub != 0 and not exempt:
                out.append((
                    "K002", op.name,
                    "operand %r block %r (%s): second-to-last dim %d is "
                    "not a multiple of the %s sublane tile %d (8 fp32 / "
                    "16 bf16 / 32 int8)%s"
                    % (op.name, bs, op.dtype, second, op.dtype, sub,
                       " — a chosen tile parameter; raise it to the "
                       "sublane floor" if strict_second else
                       " and does not cover the full axis")))
    return out


# -- index-map evaluation (K004/K006) -------------------------------------

def _prefetch_values(spec: KernelSpec):
    return tuple(pf.values for pf in spec.prefetch)


def _as_index_arrays(result, ndim: int, npoints: int):
    """Normalize an index_map result (tuple of scalars / numpy / jnp
    values) to per-dim int64 numpy arrays of shape (npoints,)."""
    import numpy as np

    if not isinstance(result, (tuple, list)):
        result = (result,)
    if len(result) != ndim:
        raise ValueError("index_map returned %d indices for a rank-%d "
                         "block" % (len(result), ndim))
    out = []
    for r in result:
        arr = np.asarray(r).astype(np.int64)
        out.append(np.broadcast_to(arr, (npoints,)) if arr.ndim == 0
                   else arr.reshape(npoints))
    return out


def _grid_points(grid: Tuple[int, ...], max_points: int):
    """(coords, sampled): per-axis index arrays covering the full grid
    product, or — past ``max_points`` — a partial sweep that keeps
    small axes (<= 64: slot/head-style table axes) FULL and samples
    only large axes at their edges + midpoint.  ``sampled=True`` means
    the K004 verdict is partial; the caller surfaces that as a K008
    INFO so a clean report never silently claims a full sweep."""
    import numpy as np

    total = 1
    for g in grid:
        total *= max(int(g), 1)
    if total <= max_points:
        axes = [np.arange(max(int(g), 1)) for g in grid]
        sampled = False
    else:
        def edge_pick(g):
            return np.asarray(sorted(
                x for x in {0, 1, g // 2, g - 2, g - 1} if 0 <= x < g))

        axes = []
        for g in grid:
            g = max(int(g), 1)
            axes.append(np.arange(g) if g <= 64 else edge_pick(g))
        kept = 1
        for a in axes:
            kept *= len(a)
        if kept > max_points:
            # many small axes can still blow the cap multiplicatively —
            # the cap is a hard memory bound, so fall back to edge
            # sampling everywhere
            axes = [edge_pick(max(int(g), 1)) for g in grid]
        sampled = True
    mesh = np.meshgrid(*axes, indexing="ij") if axes else []
    coords = [m.reshape(-1) for m in mesh]
    return coords, sampled


def _check_index_extents(spec: KernelSpec, report: Report,
                         max_points: int) -> None:
    import numpy as np

    pf_vals = _prefetch_values(spec)
    coords, sampled = _grid_points(spec.grid, max_points)
    npoints = len(coords[0]) if coords else 1
    if sampled:
        total = 1
        for g in spec.grid:
            total *= max(int(g), 1)
        report.add(Diagnostic(
            _PASS, "K008", Severity.INFO, spec.name,
            "index-map sweep SAMPLED the grid (%d of %d points: small "
            "axes full, large axes at edges+midpoint) — the K004 "
            "verdict is partial; raise max_grid_points for a full "
            "sweep" % (npoints, total),
            details={"points_checked": npoints, "grid_points": total}))
    for op in spec.operands:
        if op.index_map is None:
            continue
        try:
            res = op.index_map(*coords, *pf_vals)
            idx = _as_index_arrays(res, len(op.block_shape), npoints)
        except Exception as exc:
            report.add(Diagnostic(
                _PASS, "K004", Severity.ERROR,
                "%s.%s" % (spec.name, op.name),
                "operand %r index_map failed to evaluate over the grid "
                "(%s: %s) — the map must be a pure function of the grid "
                "indices and scalar-prefetch values"
                % (op.name, type(exc).__name__, exc)))
            continue
        for d, (ix, bdim, ext) in enumerate(
                zip(idx, op.block_shape, op.array_shape)):
            bad = (ix < 0) | (ix * bdim >= ext)
            if not bool(bad.any()):
                continue
            flat = int(np.argmax(bad))
            point = tuple(int(c[flat]) for c in coords)
            report.add(Diagnostic(
                _PASS, "K004", Severity.ERROR,
                "%s.%s" % (spec.name, op.name),
                "operand %r dim %d: index_map addresses block %d "
                "(elements from %d) past the backing array extent %d "
                "at in-range grid index %r — %d of %d checked grid "
                "point(s) out of bounds%s"
                % (op.name, d, int(ix[flat]), int(ix[flat]) * bdim,
                   ext, point, int(bad.sum()), npoints,
                   " (grid sampled at axis extremes)" if sampled
                   else ""),
                details={"dim": d, "grid_index": list(point),
                         "block_index": int(ix[flat]),
                         "extent": int(ext)}))


def _output_grid_dependence(spec: KernelSpec, op: BlockOperand):
    """Grid axes the output's index map depends on, probed per axis at
    1 and size-1 against the origin (affine maps — the real kernels' —
    are exactly captured; anything fancier still lands on the safe
    WARNING side)."""
    import numpy as np

    pf_vals = _prefetch_values(spec)

    def at(point):
        res = op.index_map(*point, *pf_vals)
        if not isinstance(res, (tuple, list)):
            res = (res,)
        return tuple(int(np.asarray(r)) for r in res)

    origin = tuple(0 for _ in spec.grid)
    base = at(origin)
    dependent = set()
    for axis, size in enumerate(spec.grid):
        # probe only IN-GRID points: a size-1 axis has nothing to vary
        # (and a phantom index could fault a table-driven map)
        for probe in {p for p in (1, size - 1) if 0 < p < size}:
            point = list(origin)
            point[axis] = probe
            if at(tuple(point)) != base:
                dependent.add(axis)
                break
    return dependent


def _check_output_revisit(spec: KernelSpec, report: Report) -> None:
    for op in spec.operands:
        if op.kind != "out" or op.index_map is None:
            continue
        try:
            dependent = _output_grid_dependence(spec, op)
        except Exception:
            continue  # un-probeable map: extent check already reported
        reduced = [ax for ax, size in enumerate(spec.grid)
                   if size > 1 and ax not in dependent]
        inner_dep = [ax for ax in dependent
                     if any(r < ax for r in reduced)]
        if not inner_dep:
            continue
        r = min(ax for ax in reduced if ax < max(inner_dep))
        report.add(Diagnostic(
            _PASS, "K006", Severity.WARNING,
            "%s.%s" % (spec.name, op.name),
            "output %r is written per grid axis %d but revisited "
            "across the OUTER reduced axis %d: each block is flushed "
            "and re-fetched once per outer step (and a j==0-style init "
            "re-zeros it) — make the reduced axes the innermost grid "
            "suffix" % (op.name, max(inner_dep), r),
            details={"dependent_axes": sorted(dependent),
                     "reduced_axes": reduced}))


def _check_prefetch(spec: KernelSpec, report: Report) -> None:
    import numpy as np

    for pf in spec.prefetch:
        vals = np.asarray(pf.values)
        if vals.dtype != np.int32:
            report.add(Diagnostic(
                _PASS, "K005", Severity.WARNING,
                "%s.%s" % (spec.name, pf.name),
                "scalar-prefetch operand %r is %s, not int32 — SMEM "
                "table walks index with int32; other widths reconvert "
                "per step or fail to lower" % (pf.name, vals.dtype)))
        if pf.valid_range is None:
            report.add(Diagnostic(
                _PASS, "K005", Severity.WARNING,
                "%s.%s" % (spec.name, pf.name),
                "scalar-prefetch operand %r declares no valid_range — "
                "its values are unvalidated against the page-pool "
                "extent, so a corrupt table walks out of the pool "
                "silently" % (pf.name,)))
        elif vals.size:
            lo, hi = pf.valid_range
            bad = int(((vals < lo) | (vals >= hi)).sum())
            if bad:
                report.add(Diagnostic(
                    _PASS, "K005", Severity.WARNING,
                    "%s.%s" % (spec.name, pf.name),
                    "scalar-prefetch operand %r: %d value(s) outside "
                    "the declared valid range [%d, %d) (min %d, max %d)"
                    % (pf.name, bad, lo, hi, int(vals.min()),
                       int(vals.max()))))


# -- the registered pass --------------------------------------------------

def check_kernels(specs: Optional[Sequence[KernelSpec]] = None,
                  vmem_budget=DEFAULT_VMEM_BUDGET,
                  buffering: int = 2,
                  max_grid_points: int = 1 << 20) -> Report:
    """Statically validate Pallas kernel call geometry; returns a Report
    of K0xx (+ M007) diagnostics.

    specs: KernelSpec descriptors (default: the shipped kernels' real
    TPU serving/training geometries via :func:`default_kernel_specs` —
    the repo self-application).  vmem_budget: per-grid-step ceiling, int
    or ``"16MiB"``-style string.  buffering: in/out block residency
    multiplier (the Pallas pipeline double-buffers; see
    :func:`~.memory_estimate.kernel_vmem_estimate`).  max_grid_points:
    full-product index-map sweep cap, beyond which large grid axes are
    sampled at their extremes (small axes stay fully swept) and a K008
    INFO marks the verdict partial.
    """
    if specs is None:
        specs = default_kernel_specs()
    base_budget = parse_bytes(vmem_budget)
    report = Report()
    for spec in specs:
        deferred: List[Tuple[str, str, str]] = []

        # K009 — mesh-axis/cache_spec divisibility (ERROR everywhere:
        # a partitioning the mesh cannot honor is wrong in interpret
        # mode too — GSPMD would pad or gather around the kernel)
        if spec.mesh_axis is not None:
            axis, shards, extent = spec.mesh_axis
            if shards < 1 or (extent is not None
                              and extent % max(shards, 1) != 0):
                report.add(Diagnostic(
                    _PASS, "K009", Severity.ERROR, spec.name,
                    "mesh-axis mismatch: cache_spec shards axis %r "
                    "over %d device(s) but the global sharded-axis "
                    "extent %s does not divide — shard_map cannot "
                    "place whole kv heads per device; fix the mesh "
                    "size or the cache_spec heads axis"
                    % (axis, shards, extent),
                    details={"axis": axis, "shards": shards,
                             "global_extent": extent}))

        # K001/K002 — tile geometry
        for code, opname, msg in _geometry_violations(spec):
            if spec.interpret:
                deferred.append((code, opname, msg))
            else:
                report.add(Diagnostic(
                    _PASS, code, Severity.ERROR,
                    "%s.%s" % (spec.name, opname), msg))

        # K003 / M007 — VMEM budget + pricing
        budget = base_budget if spec.vmem_limit is None else min(
            max(base_budget, spec.vmem_limit), PHYSICAL_VMEM)
        est = kernel_vmem_estimate(spec, buffering=buffering)
        report.add(Diagnostic(
            _PASS, "M007", Severity.INFO, spec.name,
            "per-grid-step VMEM estimate: total=%s (%dx(in=%s + out=%s)"
            " + scratch=%s), smem prefetch=%s, budget=%s"
            % (format_bytes(est["total_bytes"]), est["buffering"],
               format_bytes(est["in_bytes"]),
               format_bytes(est["out_bytes"]),
               format_bytes(est["scratch_bytes"]),
               format_bytes(est["smem_prefetch_bytes"]),
               format_bytes(budget)),
            details={k: v for k, v in est.items() if k != "per_operand"}))
        if est["total_bytes"] > budget:
            msg = ("per-grid-step VMEM estimate %s exceeds the %s "
                   "budget by %s — shrink the block/scratch shapes or "
                   "stream the oversized operand (largest: %s)"
                   % (format_bytes(est["total_bytes"]),
                      format_bytes(budget),
                      format_bytes(est["total_bytes"] - budget),
                      ", ".join("%s=%s" % (n, format_bytes(b))
                                for n, _k, _s, _d, b in sorted(
                                    est["per_operand"],
                                    key=lambda t: -t[-1])[:3])))
            if spec.interpret:
                deferred.append(("K003", spec.name, msg))
            else:
                report.add(Diagnostic(_PASS, "K003", Severity.ERROR,
                                      spec.name, msg,
                                      details={"total_bytes":
                                               est["total_bytes"],
                                               "budget_bytes": budget}))

        # K004 — index maps stay inside their arrays (ERROR everywhere:
        # out-of-extent reads are wrong in interpret mode too)
        _check_index_extents(spec, report, max_grid_points)

        # K005 — scalar-prefetch hygiene
        _check_prefetch(spec, report)

        # K006 — output-revisit grid ordering
        _check_output_revisit(spec, report)

        # K007 — interpret-only downgrade summary
        if deferred:
            report.add(Diagnostic(
                _PASS, "K007", Severity.INFO, spec.name,
                "geometry is interpret-mode-only: %d TPU-lowering "
                "violation(s) [%s] are legal on CPU tests but would "
                "fail Mosaic on hardware — this suite being green does "
                "NOT claim TPU-readiness for %r"
                % (len(deferred),
                   ", ".join(sorted({c for c, _o, _m in deferred})),
                   spec.name),
                details={"violations": [
                    {"code": c, "operand": o, "message": m}
                    for c, o, m in deferred]}))
    return report


# -- repo self-application ------------------------------------------------

def default_kernel_specs() -> List[KernelSpec]:
    """The shipped kernels' descriptors at their REAL TPU geometries —
    the set ``check_kernels()`` (and ``python -m mxtpu.analysis
    kernel``) verdicts as the merge gate:

    - flash_attention fwd + the one backward kernel, fp32 training
      shape and the bf16 serving-prefill shape (T=2048, D=128, blocks
      of 128, both kernels' 512 x 512 tiles), and the three benchmark
      cells' calls, fp32: BERT's (384 heads x 512 x 64, one tile a
      head), latent attention's (T=8192, keys 192, values 128) and
      rotary latent attention's (T=8192, keys and values 256), whole
      heads in VMEM, asked for through ``vmem_limit``;
    - the Keye-VL cell's indexed attention (T=8192): the flash kernels
      with kept keys at 32 heads of 128, and the indexer's scores
      forward, mean probabilities and one backward pass (16 heads of
      64, a tile's heads a grid step), the query gradient carried over
      the key tiles and the key gradient resident for the call;
    - KDA's kernels (the chunks' operands forward and backward, the
      state pass forward writing states and backward) at 8,192 positions
      and at a toy length, heads and chunks of 128 x 64;
    - paged_attention decode (W=1) and W-wide speculative verify (W=8),
      fp32 cache at block_size 16 and int8 cache at block_size 32 (the
      int8 sublane floor), GQA rep 4, D=128, ragged model tables — plus
      the shard_map-partitioned (``mesh_axis=("tp", 4)``) per-shard
      variants of the decode and int8-verify geometries, the default
      fast path under a tp-sharded cache;
    - paged_attention TREE verify (``tree=True``: ancestor-bitmask
      lane masking over a model binary tree) at W=4 and W=8, fp32 and
      int8 caches, plus the tp=2 per-shard int8 tree geometry — the
      serving engines' spec_tree fast path;
    - paged_prefill chunked-prefill at the serving chunk (T=128, GQA
      rep 4, D=128), fp32 cache at block_size 16 and int8 at 32, plus
      the tp=4 per-shard variant.
    """
    import importlib

    from ..ops.pallas import paged_attention
    kda = importlib.import_module("mxtpu.ops.pallas.kda")
    indexer = importlib.import_module("mxtpu.ops.pallas.indexer")

    # the package re-exports the flash_attention FUNCTION under the
    # module's name; import the module itself for its spec builder
    flash_attention = importlib.import_module(
        "mxtpu.ops.pallas.flash_attention")
    prefill_attention = importlib.import_module(
        "mxtpu.ops.pallas.prefill_attention")

    specs: List[KernelSpec] = []
    for dtype in ("float32", "bfloat16"):
        specs.extend(flash_attention.kernel_specs(
            B=4, H=8, T=2048, D=128, dtype=dtype))
    # the benchmark's cells: BERT's 32 x 12 heads of 64 at 512
    # positions, latent attention of the Kimi-Linear cell (keys 192,
    # values 128, 8,192 positions) and of the GLM-4.7-Flash cell (256 /
    # 256); and KDA's kernels (4 heads a call) at the cell's length and
    # at the toy length of the CPU tests
    specs.extend(flash_attention.kernel_specs(
        B=32, H=12, T=512, D=64, dtype="float32"))
    specs.extend(flash_attention.kernel_specs(
        B=1, H=32, T=8192, D=192, Dv=128, dtype="float32"))
    specs.extend(flash_attention.kernel_specs(
        B=1, H=20, T=8192, D=256, dtype="float32"))
    for T in (8192, 96):
        specs.extend(kda.kernel_specs(B=1, H=4, T=T, K=128))
    # indexed sparse attention of the Keye-VL cell: the flash kernels
    # with a sequence's kept keys (32 heads of 128, a tile's column or a
    # block's row of the (T, T) selection scores beside the head), and
    # the indexer's three kernels (16 heads of 64; 4 key heads)
    specs.extend(flash_attention.kernel_specs(
        B=1, H=32, T=8192, D=128, dtype="float32", kept=True))
    specs.extend(indexer.kernel_specs(B=1, Hi=16, T=8192, d=64, H=32, G=4,
                                      D=128))
    for cache_dtype, block_size in (("float32", 16), ("int8", 32)):
        for W in (1, 8):
            specs.append(paged_attention.kernel_spec(
                B=16, KV=8, rep=4, W=W, D=128, block_size=block_size,
                max_length=512, cache_dtype=cache_dtype))
    # the GSPMD-partitioned serving path: per-shard (tp=4 over 8 global
    # kv heads -> 2 per device) decode and int8-verify geometries
    specs.append(paged_attention.kernel_spec(
        B=16, KV=8, rep=4, W=1, D=128, block_size=16, max_length=512,
        cache_dtype="float32", mesh_axis=("tp", 4)))
    specs.append(paged_attention.kernel_spec(
        B=16, KV=8, rep=4, W=8, D=128, block_size=32, max_length=512,
        cache_dtype="int8", mesh_axis=("tp", 4)))
    # tree-speculative verify: per-lane ancestor bitmasks over a model
    # binary tree (the engines' spec_tree path), fp32 + int8, and the
    # tp-sharded int8 variant
    for cache_dtype, block_size in (("float32", 16), ("int8", 32)):
        for W in (4, 8):
            specs.append(paged_attention.kernel_spec(
                B=16, KV=8, rep=4, W=W, D=128, block_size=block_size,
                max_length=512, cache_dtype=cache_dtype, tree=True))
    specs.append(paged_attention.kernel_spec(
        B=16, KV=8, rep=4, W=8, D=128, block_size=32, max_length=512,
        cache_dtype="int8", tree=True, mesh_axis=("tp", 2)))
    # chunked-prefill kernel at the serving chunk geometry
    for cache_dtype, block_size in (("float32", 16), ("int8", 32)):
        specs.append(prefill_attention.kernel_spec(
            T=128, KV=8, rep=4, D=128, block_size=block_size,
            max_length=2048, start_pos=512, cache_dtype=cache_dtype))
    specs.append(prefill_attention.kernel_spec(
        T=128, KV=8, rep=4, D=128, block_size=16, max_length=2048,
        start_pos=512, cache_dtype="float32", mesh_axis=("tp", 4)))
    return specs


register_pass(_PASS)(check_kernels)
