"""kernel_check (ISSUE 12): static TPU tile-geometry / VMEM-budget /
grid-safety analysis for Pallas kernels.

Three claims pinned here:

1. **Self-application is the merge gate** — the shipped kernels
   (flash_attention fwd+bwd, kda, paged_attention) at their REAL
   TPU serving/training geometries (fp32 and int8, decode and W-wide
   verify) report ZERO ERROR, so every ROADMAP-item-2 kernel lands
   behind an asserted-on-CPU geometry verdict.
2. **Every K code fires exactly where expected** — a red-team fixture
   bank of deliberately broken specs, one per rule.
3. **The VMEM estimator prices the real call** — kernel_vmem_estimate
   agrees with the interpret-mode pallas_call's actual grid/block/
   scratch shapes on the paged-attention kernel (captured from the real
   invocation), and the runtime guard mirrors the static rules.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from mxtpu.analysis import (BlockOperand, KernelSpec, ScalarPrefetch,
                            ScratchOperand, Severity, check_kernels,
                            default_kernel_specs, kernel_vmem_estimate,
                            list_passes, run_pass, sublane_tile)
from mxtpu.ops.pallas import paged_attention as pa


def _codes(rep):
    return sorted({d.code for d in rep})


def _spec(block, array, dtype="float32", kind="in", grid=(4,),
          imap=None, **kw):
    imap = imap if imap is not None else (lambda *a: (0,) * len(block))
    return KernelSpec(
        "fixture", grid,
        [BlockOperand("x", kind, block, array, dtype, imap)], **kw)


# ------------------------------------------------ 1. self-application

def test_shipped_kernels_pass_clean_at_tpu_geometries():
    """The merge gate: flash fwd+bwd (fp32 + bf16) and
    paged_attention (fp32 bs=16 + int8 bs=32, W=1 decode + W=8 verify)
    — zero ERROR, zero WARNING, one M007 pricing INFO per spec."""
    specs = default_kernel_specs()
    names = " ".join(s.name for s in specs)
    assert "flash_attention.fwd" in names
    assert "flash_attention.bwd[" in names
    assert "flash_attention.bwd_dq" not in names
    assert "flash_attention.bwd_dkv" not in names
    assert "paged_attention[int8,W=8" in names
    assert "paged_attention[float32,W=1" in names
    rep = check_kernels(specs)
    assert rep.ok, "TPU geometry regression:\n%s" % rep
    assert not rep.warnings, "unexpected warnings:\n%s" % rep
    assert len(rep.filter(code="M007")) == len(specs)


def test_kernel_check_is_a_registered_pass():
    assert "kernel_check" in list_passes()
    rep = run_pass("kernel_check")
    assert rep.ok


def test_int8_sublane_floor_is_enforced_not_prose():
    """The ROADMAP "block_size >= 32 for int8" rule: the same paged
    geometry that passes at bs=32 fails K002 at bs=16 (int8 sublane
    tile is 32), while fp32 accepts bs=16 (sublane 8)."""
    bad = pa.kernel_spec(B=4, KV=2, rep=4, W=1, D=128, block_size=16,
                         max_length=256, cache_dtype="int8")
    rep = check_kernels([bad])
    hit = rep.filter(code="K002", min_severity=Severity.ERROR)
    assert {d.subject for d in hit} == {
        "%s.pool_k" % bad.name, "%s.pool_v" % bad.name}
    ok = pa.kernel_spec(B=4, KV=2, rep=4, W=1, D=128, block_size=16,
                        max_length=256, cache_dtype="float32")
    assert check_kernels([ok]).ok


# ------------------------------------------- 2. red-team fixture bank

def test_k001_last_dim_not_lane_aligned():
    s = _spec((1, 8, 64), (4, 8, 256), imap=lambda i: (i, 0, 0))
    rep = check_kernels([s])
    hit = rep.filter(code="K001")
    assert len(hit) == 1 and hit.diagnostics[0].severity == Severity.ERROR
    assert hit.diagnostics[0].subject == "fixture.x"
    assert _codes(rep) == ["K001", "M007"]


def test_k001_full_axis_block_is_exempt():
    """A block covering the whole (sub-128) axis pads a partial lane
    tile — legal; only CHOSEN non-aligned tilings are defects."""
    s = _spec((1, 8, 64), (4, 8, 64), imap=lambda i: (i, 0, 0))
    assert check_kernels([s]).ok


def test_k002_sublane_tile_per_dtype():
    for dtype, sub in (("float32", 8), ("bfloat16", 16), ("int8", 32)):
        assert sublane_tile(dtype) == sub
        bad = _spec((1, sub // 2, 128), (4, 4 * sub, 128), dtype=dtype,
                    imap=lambda i: (i, 0, 0))
        rep = check_kernels([bad])
        assert _codes(rep) == ["K002", "M007"], dtype
        ok = _spec((1, sub, 128), (4, 4 * sub, 128), dtype=dtype,
                   imap=lambda i: (i, 0, 0))
        assert check_kernels([ok]).ok, dtype


@pytest.mark.parametrize("block,array,legal", [
    # the pre-PR-22 specs the v5e compiler refused ("last two dimensions
    # of your block shape ... divisible by 8 and 128 ... or equal to the
    # respective dimensions of the overall array"): flash lse rows and
    # the int8 per-position scale plane
    ((1, 128), (384, 128), False),
    ((1, 1, 32), (129, 8, 32), False),
    # their repaired forms: a trailing unit dim / the whole KV plane
    ((1, 128, 1), (384, 128, 1), True),
    ((1, 8, 32), (129, 8, 32), True),
    # a size-1 window is exempt only when the axis itself is size 1
    ((1, 1, 128), (4, 1, 1024), True),
])
def test_k002_size_one_sublane_needs_a_size_one_axis(block, array, legal):
    s = _spec(block, array, imap=lambda b: (b,) + (0,) * (len(block) - 1))
    rep = check_kernels([s])
    assert rep.ok == legal, rep
    if not legal:
        assert _codes(rep) == ["K002", "M007"]


def test_k003_vmem_budget_and_configurability():
    big = _spec((1, 8192, 1024), (2, 8192, 1024), grid=(2,),
                imap=lambda i: (i, 0, 0))
    rep = check_kernels([big])   # 2 x 32MiB > 16MiB default
    hit = rep.filter(code="K003")
    assert len(hit) == 1 and not rep.ok
    assert hit.diagnostics[0].details["budget_bytes"] == 16 * 2**20
    # the same spec passes a raised budget; a small one fails anything
    assert check_kernels([big], vmem_budget="128MiB").ok
    tiny = _spec((1, 8, 128), (2, 8, 128), imap=lambda i: (i, 0, 0))
    assert not check_kernels([tiny], vmem_budget="1KiB").ok


def test_k004_block_table_entry_past_pool_extent():
    """The null-page-0 convention is modeled: a legal ragged table
    passes; corrupting ONE live entry to the pool size fires K004 with
    the offending grid index."""
    ok = pa.kernel_spec(B=3, KV=2, rep=2, W=1, D=128, block_size=8,
                        max_length=64, num_blocks=8)
    assert check_kernels([ok]).ok
    tables, pos = pa._model_tables(3, 8, 8, 8, 1, 64)
    tables[1, 0] = 8                      # == N: one page past the pool
    bad = pa.kernel_spec(B=3, KV=2, rep=2, W=1, D=128, block_size=8,
                         max_length=64, num_blocks=8, tables=tables,
                         pos=pos)
    rep = check_kernels([bad])
    hit = rep.filter(code="K004")
    assert {d.subject for d in hit} == {
        "%s.pool_k" % bad.name, "%s.pool_v" % bad.name}
    for d in hit:
        assert d.details["grid_index"][0] == 1   # slot 1's walk
        assert d.details["extent"] == 8
    # the corrupt value also trips the declared-range validation
    assert len(rep.filter(code="K005")) >= 1
    # overrides apply INDEPENDENTLY: auditing a real engine's corrupt
    # table with pos omitted must still evaluate THAT table, never
    # fall back to clean model tables
    bad2 = pa.kernel_spec(B=3, KV=2, rep=2, W=1, D=128, block_size=8,
                          max_length=64, num_blocks=8, tables=tables)
    assert not check_kernels([bad2]).ok


def test_k004_affine_map_overruns_unpadded_array():
    # grid covers 6 blocks of 128 but the array holds only 512 rows
    s = _spec((128, 128), (512, 128), grid=(6,),
              imap=lambda i: (i, 0))
    rep = check_kernels([s])
    hit = rep.filter(code="K004")
    assert len(hit) == 1
    assert hit.diagnostics[0].details["block_index"] == 4
    assert not rep.ok


def test_k004_fires_on_sampled_oversize_grids():
    """Past max_grid_points the sweep samples large axes at their
    extremes — an overrun at the grid corner is still caught, and the
    partial sweep is announced as a K008 INFO so a clean verdict can
    never silently mean 'mostly unchecked'."""
    s = _spec((8, 128), (1024, 128), grid=(1000, 1000),
              imap=lambda i, j: (i + j, 0))
    rep = check_kernels([s], max_grid_points=1024)
    hit = rep.filter(code="K004")
    assert len(hit) == 1
    assert "sampled" in hit.diagnostics[0].message
    k8 = rep.filter(code="K008")
    assert len(k8) == 1
    assert k8.diagnostics[0].details["grid_points"] == 1000 * 1000
    # small (table-sized) axes stay FULLY swept even when sampling: a
    # corrupt entry on an unsampled-looking slot axis is still caught
    s2 = _spec((8, 128), (1024, 128), grid=(64, 1000),
               imap=lambda b, j: (jnp.where(b == 37, 200, 0), 0))
    rep2 = check_kernels([s2], max_grid_points=1024)
    assert len(rep2.filter(code="K004")) == 1
    # a fully-swept grid never emits K008
    assert not check_kernels(
        [pa.kernel_spec(B=4, KV=2, rep=2, W=1, D=128, block_size=8,
                        max_length=64, num_blocks=8)]).filter(
        code="K008").diagnostics


def test_grid_sampling_enforces_the_point_cap():
    """The sweep cap is a hard memory bound: many small (fully-swept)
    axes whose product still exceeds max_grid_points fall back to edge
    sampling everywhere instead of materializing the product."""
    from mxtpu.analysis.kernel_check import _grid_points

    coords, sampled = _grid_points((64, 64, 64, 64), 1000)
    assert sampled
    assert len(coords[0]) <= 1000
    # a single oversize axis still keeps its neighbours full
    coords, sampled = _grid_points((8, 1000), 1024)
    assert sampled and len(coords[0]) == 8 * 5


def test_block_operand_rejects_rank_mismatch():
    """Geometry and extent rules align block dims with array dims
    positionally — a rank mismatch must be rejected up front, not
    checked against the wrong extents (failing open on the tail)."""
    with pytest.raises(ValueError, match="same rank"):
        BlockOperand("x", "in", (1, 8, 128), (4, 2, 8, 128), "float32")


def test_k004_error_even_in_interpret_mode():
    """Out-of-extent indexing is wrong on CPU too — interpret never
    downgrades K004."""
    s = _spec((128, 128), (512, 128), grid=(6,),
              imap=lambda i: (i, 0), interpret=True)
    rep = check_kernels([s])
    assert len(rep.filter(code="K004", min_severity=Severity.ERROR)) == 1


def test_k005_prefetch_dtype_and_range_hygiene():
    base = dict(block=(1, 8, 128), array=(4, 8, 128), grid=(4,))
    s = KernelSpec("fixture", (4,),
                   [BlockOperand("x", "in", base["block"], base["array"],
                                 "float32", lambda i, t, u: (i, 0, 0))],
                   prefetch=[
                       ScalarPrefetch("t", np.zeros(4, np.int64)),
                       ScalarPrefetch("u", np.array([9], np.int32),
                                      valid_range=(0, 4))])
    rep = check_kernels([s])
    hit = rep.filter(code="K005")
    # t: wrong dtype AND undeclared range; u: value 9 outside [0, 4)
    t_msgs = [d.message for d in hit if d.subject == "fixture.t"]
    assert len(t_msgs) == 2
    assert any("not int32" in m for m in t_msgs)
    assert any("no valid_range" in m for m in t_msgs)
    u_msgs = [d.message for d in hit if d.subject == "fixture.u"]
    assert len(u_msgs) == 1 and "outside" in u_msgs[0]
    assert rep.ok                      # warnings, not errors


def test_k006_output_revisited_across_outer_reduced_axis():
    s = KernelSpec("fixture", (4, 4),
                   [BlockOperand("o", "out", (8, 128), (32, 128),
                                 "float32", lambda i, j: (j, 0))])
    rep = check_kernels([s])
    hit = rep.filter(code="K006")
    assert len(hit) == 1
    assert hit.diagnostics[0].details == {"dependent_axes": [1],
                                          "reduced_axes": [0]}
    # the safe orientations: reduction innermost, or no reduction
    safe = KernelSpec("fixture", (4, 4),
                      [BlockOperand("o", "out", (8, 128), (32, 128),
                                    "float32", lambda i, j: (i, 0))])
    assert not check_kernels([safe]).filter(code="K006").diagnostics
    const = KernelSpec("fixture", (4, 4),
                       [BlockOperand("o", "out", (8, 128), (8, 128),
                                     "float32", lambda i, j: (0, 0))])
    assert not check_kernels([const]).filter(code="K006").diagnostics


def test_k006_size_one_axis_never_probed_or_warned():
    """A degenerate size-1 grid axis has no in-grid point to vary: the
    dependence probe must not evaluate a phantom out-of-grid index —
    a map reading that axis would look 'dependent' on it and draw a
    spurious revisit warning for a grid that writes each block once."""
    s = KernelSpec(
        "fixture", (4, 1),
        [BlockOperand("o", "out", (8, 128), (8, 128), "float32",
                      lambda i, j: (j, 0))])
    rep = check_kernels([s])
    assert not rep.filter(code="K006").diagnostics
    assert not rep.filter(code="K004").diagnostics


def test_k007_interpret_only_downgrade():
    """A CPU-test geometry (the engines' tiny shapes) declared
    interpret=True: the K001/K002 verdicts collapse into one K007 INFO
    — green CPU suites cannot claim TPU-readiness — and nothing errors."""
    s = pa.kernel_spec(B=2, KV=2, rep=2, W=1, D=16, block_size=4,
                       max_length=32, interpret=True)
    rep = check_kernels([s])
    assert rep.ok and not rep.warnings
    hit = rep.filter(code="K007")
    assert len(hit) == 1
    codes = {v["code"] for v in hit.diagnostics[0].details["violations"]}
    assert codes == {"K001", "K002"}    # D=16 lanes, bs=4 sublanes
    # the SAME spec not declared interpret errors on both rules
    hard = pa.kernel_spec(B=2, KV=2, rep=2, W=1, D=16, block_size=4,
                          max_length=32)
    rep = check_kernels([hard])
    assert not rep.ok
    assert {"K001", "K002"} <= set(_codes(rep))
    assert not rep.filter(code="K007").diagnostics


# ------------------------- 3. estimator parity + runtime guard


@pytest.mark.parametrize("cache_dtype", ["float32", "int8"])
def test_vmem_estimate_prices_the_real_call(monkeypatch, cache_dtype):
    """kernel_vmem_estimate's operand model == the pallas_call the
    kernel actually issues: capture the real grid_spec from an
    interpret-mode run and compare grid, per-operand block shapes,
    scratch shapes/dtypes, and scalar-prefetch count."""
    B, KV, rep_, W, D, bs, M, N = 3, 2, 2, 4, 16, 8, 4, 9
    quant = cache_dtype == "int8"
    captured = {}
    real = pa.pl.pallas_call

    def spy(kernel, **kw):
        captured.update(kw)
        return real(kernel, **kw)

    monkeypatch.setattr(pa.pl, "pallas_call", spy)
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, KV * rep_, W, D).astype("float32"))
    tables = jnp.asarray(rng.randint(1, N, (B, M)).astype(np.int32))
    pos = jnp.asarray(rng.randint(0, M * bs - W, B).astype(np.int32))
    kw = {}
    if quant:
        pk = jnp.asarray(rng.randint(-127, 128,
                                     (N, KV, bs, D)).astype(np.int8))
        pv = jnp.asarray(rng.randint(-127, 128,
                                     (N, KV, bs, D)).astype(np.int8))
        kw = dict(k_scales=jnp.ones((N, KV, bs), jnp.float32),
                  v_scales=jnp.ones((N, KV, bs), jnp.float32))
    else:
        pk = jnp.asarray(rng.randn(N, KV, bs, D).astype("float32"))
        pv = jnp.asarray(rng.randn(N, KV, bs, D).astype("float32"))
    pa.paged_decode_attention(q, pk, pv, tables, pos, **kw)

    gs = captured["grid_spec"]
    spec = pa.kernel_spec(B=B, KV=KV, rep=rep_, W=W, D=D, block_size=bs,
                          max_length=M * bs, num_blocks=N,
                          q_dtype="float32", cache_dtype=cache_dtype,
                          tables=np.asarray(tables),
                          pos=np.asarray(pos), interpret=True)
    assert tuple(gs.grid) == spec.grid
    ins = [op for op in spec.operands if op.kind == "in"]
    outs = [op for op in spec.operands if op.kind == "out"]
    assert [tuple(s.block_shape) for s in gs.in_specs] == \
        [op.block_shape for op in ins]
    out_specs = gs.out_specs
    if not isinstance(out_specs, (list, tuple)):
        out_specs = [out_specs]
    assert [tuple(s.block_shape) for s in out_specs] == \
        [op.block_shape for op in outs]
    assert [(tuple(sc.shape), str(jnp.dtype(sc.dtype)))
            for sc in gs.scratch_shapes] == \
        [(sc.shape, str(jnp.dtype(sc.dtype))) for sc in spec.scratch]
    assert gs.num_scalar_prefetch == len(spec.prefetch)
    # byte totals agree when priced from the captured call's shapes
    rebuilt = KernelSpec(
        "captured", tuple(gs.grid),
        [BlockOperand(f"in{i}", "in", tuple(s.block_shape),
                      op.array_shape, op.dtype)
         for i, (s, op) in enumerate(zip(gs.in_specs, ins))]
        + [BlockOperand(f"out{i}", "out", tuple(s.block_shape),
                        op.array_shape, op.dtype)
           for i, (s, op) in enumerate(zip(out_specs, outs))],
        scratch=[ScratchOperand(f"s{i}", tuple(sc.shape), sc.dtype)
                 for i, sc in enumerate(gs.scratch_shapes)],
        prefetch=spec.prefetch)
    assert kernel_vmem_estimate(rebuilt)["total_bytes"] == \
        kernel_vmem_estimate(spec)["total_bytes"]


def _flash_calls(monkeypatch, fa, q, k, v, causal=False, **blocks):
    """The keyword arguments of the pallas_calls one forward and
    backward issue: traced, never run."""
    import jax

    calls = []
    real = fa.pl.pallas_call

    def spy(kernel, **kw):
        calls.append(kw)
        return real(kernel, **kw)

    monkeypatch.setattr(fa.pl, "pallas_call", spy)
    fa._make_flash.cache_clear()
    jax.make_jaxpr(jax.grad(
        lambda q, k, v: fa.flash_attention(
            q, k, v, causal=causal, **blocks).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    return calls


def _assert_specs_are_the_calls(calls, specs):
    assert [c["name"] for c in calls] == \
        ["flash_attention_fwd", "flash_attention_bwd"]
    assert [s.name.split("[")[0] for s in specs] == \
        ["flash_attention.fwd", "flash_attention.bwd"]
    for call, spec in zip(calls, specs):
        assert tuple(call["grid"]) == spec.grid
        for kind, key in (("in", "in_specs"), ("out", "out_specs")):
            assert [tuple(b.block_shape) for b in call[key]] == \
                [op.block_shape for op in spec.operands
                 if op.kind == kind], (spec.name, kind)
        assert [(tuple(sc.shape), str(jnp.dtype(sc.dtype)))
                for sc in call.get("scratch_shapes", ())] == \
            [(sc.shape, sc.dtype) for sc in spec.scratch]
        params = call.get("compiler_params")
        assert (None if params is None else params.vmem_limit_bytes) == \
            spec.vmem_limit, spec.name


@pytest.mark.parametrize("T,blocks", [
    (256, dict(q_block=128, kv_block=128)),     # widened to one tile
    (150, dict(q_block=64, kv_block=128)),      # Tq = 192, Tk = 256
    (640, dict(q_block=128, kv_block=128)),     # five tiles of 128
    (1024, dict(q_block=128, kv_block=128)),    # two tiles of 512
], ids=["T256", "T150_q64_k128", "T640", "T1024"])
def test_flash_specs_describe_the_real_calls(monkeypatch, T, blocks):
    """flash_attention.kernel_specs == the two pallas_calls a forward
    and backward issue: names, grids, block shapes (lse's: the head's
    rows of lanes, a row a tile), dq's scratch, the VMEM asked for.
    Traced only: the same calls run, against the dense reference, in
    tests/test_flash_attention.py (T = 150 at q64 / k128 under
    test_wide_forward_tiles_match_dense, T = 640 and 1,024 padded rows
    under test_the_module_s_own_tiles_in_several_trips)."""
    import importlib

    fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
    B, H, D = 1, 2, 16
    x = jnp.ones((B, H, T, D), jnp.float32)
    calls = _flash_calls(monkeypatch, fa, x, x, x, **blocks)
    _assert_specs_are_the_calls(
        calls, fa.kernel_specs(B, H, T, D, interpret=True, **blocks))


@pytest.mark.parametrize("B,H,T,D,Dv,causal,fwd_grid,lse", [
    (32, 12, 512, 64, 64, False, (384, 1), (1, 1, 512)),
    (1, 32, 8192, 192, 128, True, (32, 16), (1, 16, 512)),
    (1, 20, 8192, 256, 256, True, (20, 16), (1, 16, 512)),
], ids=["bert_base_seq512", "kimi_linear_seq8192", "glm_4_7_flash_seq8192"])
def test_flash_specs_at_the_three_cells_geometries(monkeypatch, B, H, T, D,
                                                   Dv, causal, fwd_grid,
                                                   lse):
    """The benchmark's three cells, float32: the specs in the merge gate
    are the calls the program traces there (tiles of 512 rows forward
    and backward, lse a row of 512 lanes a tile, whole heads in VMEM and
    the VMEM the calls ask for), and they pass the static rules."""
    import importlib

    import jax

    fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
    q = jax.ShapeDtypeStruct((B, H, T, D), jnp.float32)
    v = jax.ShapeDtypeStruct((B, H, T, Dv), jnp.float32)
    monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
    calls = _flash_calls(monkeypatch, fa, q, q, v, causal=causal)
    specs = fa.kernel_specs(B, H, T, D, Dv=Dv)
    _assert_specs_are_the_calls(calls, specs)
    assert specs[0].grid == fwd_grid
    assert [op.block_shape for op in specs[0].operands
            if op.name == "lse"] == [lse]
    names = {s.name for s in default_kernel_specs()}
    assert {s.name for s in specs} <= names
    for spec in specs:
        limit = spec.vmem_limit
        assert limit is None or limit < 128 * 2 ** 20, spec.name
    rep = check_kernels(specs)
    assert rep.ok and not rep.warnings, str(rep)


def test_m007_details_decompose_the_total():
    spec = pa.kernel_spec(B=4, KV=2, rep=4, W=8, D=128, block_size=32,
                          max_length=512, cache_dtype="int8")
    est = kernel_vmem_estimate(spec)
    assert est["total_bytes"] == \
        2 * (est["in_bytes"] + est["out_bytes"]) + est["scratch_bytes"]
    per_op = {n: b for n, _k, _s, _d, b in est["per_operand"]}
    # int8 page block (1, 1, 32, 128): one byte per element, no padding
    assert per_op["pool_k"] == 32 * 128
    # scale block (1, 1, 32) fp32: trailing (1, 32) pads to a whole
    # (8, 128) fp32 tile
    assert per_op["k_scales"] == 8 * 128 * 4
    # fp32 acc scratch (lanes=32, 128)
    assert per_op["acc"] == 32 * 128 * 4
    d = check_kernels([spec]).filter(code="M007").diagnostics[0]
    assert d.details["total_bytes"] == est["total_bytes"]


def test_runtime_guard_mirrors_static_rules(monkeypatch):
    """Satellite: on a non-interpret backend, TPU-illegal geometry
    raises a ValueError NAMING the violated K-rule before any lowering
    — not an opaque Mosaic error."""
    errs = pa.validate_call_geometry(64, 8, "int8")
    assert any(e.startswith("K001") for e in errs)
    assert any(e.startswith("K002") for e in errs)
    assert pa.validate_call_geometry(128, 32, "int8") == []
    assert pa.validate_call_geometry(128, 8, "float32") == []
    assert pa.validate_call_geometry(128, 8, "bfloat16") != []

    monkeypatch.setattr(pa.jax, "default_backend", lambda: "tpu")
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 4, 1, 16).astype("float32"))
    pk = jnp.asarray(rng.randn(5, 2, 4, 16).astype("float32"))
    tables = jnp.asarray(rng.randint(1, 5, (2, 3)).astype(np.int32))
    pos = jnp.asarray(np.array([3, 5], np.int32))
    with pytest.raises(ValueError) as ei:
        pa.paged_decode_attention(q, pk, pk, tables, pos)
    msg = str(ei.value)
    assert "K001" in msg and "K002" in msg
    assert "python -m mxtpu.analysis kernel" in msg


def test_runtime_guard_admits_legal_geometry_interpreted(monkeypatch):
    """The guard never fires in interpret mode (CPU tests run the
    engines' tiny geometries) and a TPU-legal geometry passes the guard
    itself — asserted via the validator the call path uses."""
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 4, 1, 16).astype("float32"))
    pk = jnp.asarray(rng.randn(5, 2, 4, 16).astype("float32"))
    tables = jnp.asarray(rng.randint(1, 5, (2, 3)).astype(np.int32))
    pos = jnp.asarray(np.array([3, 5], np.int32))
    out = pa.paged_decode_attention(q, pk, pk, tables, pos)
    assert out.shape == (2, 4, 1, 16)


# ---------------------------------------------- CLI + gate wiring

def test_cli_kernel_subcommand(capsys):
    from mxtpu.analysis.__main__ import main

    assert main(["kernel"]) == 0
    out = capsys.readouterr().out
    assert "M007" in out and "paged_attention" in out
    # a 1KiB ceiling fails every shipped kernel
    assert main(["kernel", "--vmem-budget", "1KiB"]) == 1
    assert "K003" in capsys.readouterr().out


def test_every_registered_pass_has_a_self_application():
    """The `all` gate cannot silently skip a pass: each registered name
    is wired to a probe, and an unwired name draws a P001 ERROR."""
    from mxtpu.analysis import __main__ as cli

    assert set(list_passes()) <= set(cli._SELF_APPLY)


def test_unwired_pass_fails_the_all_gate(monkeypatch):
    from mxtpu.analysis import __main__ as cli

    monkeypatch.setattr(cli, "list_passes", lambda: ["zz_new_pass"])
    rep = cli._self_apply_all()
    assert not rep.ok
    assert [d.code for d in rep.errors] == ["P001"]
    assert rep.errors[0].subject == "zz_new_pass"


# ------------------------------------- sharded (mesh-axis) geometry


def test_sharded_specs_verdict_per_shard_geometry():
    """A spec carrying ``mesh_axis=(axis, shards)`` prices the
    PER-DEVICE slice: the KV grid axis shrinks to KV/shards and the
    shard count is part of the spec name (so K diagnostics locate the
    sharded variant, not the global one)."""
    g = pa.kernel_spec(B=4, KV=8, rep=2, W=1, D=128, block_size=16,
                      max_length=256, cache_dtype="float32")
    s = pa.kernel_spec(B=4, KV=8, rep=2, W=1, D=128, block_size=16,
                      max_length=256, cache_dtype="float32",
                      mesh_axis=("tp", 4))
    assert s.grid[1] == g.grid[1] // 4
    assert "tp=4" in s.name
    assert check_kernels([s]).ok


def test_k003_per_shard_over_budget_fires_located_error():
    """Red team (ISSUE 16): the K003 budget applies to the PER-SHARD
    geometry — a sharded verify-window spec over a tightened budget
    fires a located ERROR whose subject names the tp-sharded spec."""
    spec = pa.kernel_spec(B=4, KV=8, rep=4, W=8, D=128, block_size=32,
                          max_length=512, cache_dtype="int8",
                          mesh_axis=("tp", 4))
    rep = check_kernels([spec], vmem_budget="64KiB")
    hit = rep.filter(code="K003")
    assert len(hit) == 1 and not rep.ok
    d = hit.diagnostics[0]
    assert d.severity is Severity.ERROR
    assert "tp=4" in d.subject
    assert d.details["budget_bytes"] == 64 * 1024
    # the same per-shard geometry is fine under the real 16MiB budget
    assert check_kernels([spec]).ok


def test_k009_mesh_axis_mismatch_fires_located_error():
    """Red team (ISSUE 16): a shard count that does not divide the
    global KV-head extent is a partitioning error — K009 ERROR locating
    the sharded spec, fired even for interpret-mode specs (it is a
    mesh/cache_spec mismatch, not a TPU tile rule)."""
    for interp in (False, True):
        spec = pa.kernel_spec(B=4, KV=6, rep=2, W=1, D=128,
                              block_size=32, max_length=256,
                              cache_dtype="float32",
                              mesh_axis=("tp", 4), interpret=interp)
        rep = check_kernels([spec])
        hit = rep.filter(code="K009")
        assert len(hit) == 1 and not rep.ok
        d = hit.diagnostics[0]
        assert d.severity is Severity.ERROR
        assert "tp" in d.message and "4" in d.message
        assert d.details["global_extent"] == 6
        assert d.details["shards"] == 4


def test_prefill_specs_in_the_merge_gate():
    """The chunked-prefill kernel lands behind the same gate: its
    specs (fp32 + int8 cache, incl. a tp-sharded variant) are part of
    default_kernel_specs() and verdict clean."""
    names = " ".join(s.name for s in default_kernel_specs())
    assert "paged_prefill[float32" in names
    assert "paged_prefill[int8" in names
    assert "paged_attention[float32,W=1,bs=16,D=128,tp=4" in names \
        or ("paged_attention" in names and "tp=4" in names)
    assert "paged_prefill" in names and "tp=4" in names


def test_indexed_attention_specs_describe_the_real_calls(monkeypatch):
    """The six pallas_calls of one forward and backward of
    ``ops/dsa.py``'s indexed attention — the flash kernels with kept keys
    and the indexer's four — are the ones ``kernel_specs`` describe:
    names, grids, block shapes (the key gradient's resident block among
    the backward's, the thresholds' whole column of keys), the VMEM
    asked for.  Traced only: the same calls
    run against their equations in tests/test_keye_vl.py."""
    import importlib
    import jax

    fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
    ix = importlib.import_module("mxtpu.ops.pallas.indexer")
    dsa = importlib.import_module("mxtpu.ops.dsa")
    B, H, G, D, Hi, d, T = 1, 4, 2, 16, 3, 8, 640
    calls = []
    real = fa.pl.pallas_call

    def spy(kernel, **kw):
        calls.append(kw)
        return real(kernel, **kw)

    monkeypatch.setattr(fa.pl, "pallas_call", spy)     # one pl for both
    fa._make_flash_kept.cache_clear()
    ones = lambda *s: jnp.ones(s, jnp.float32)
    jax.make_jaxpr(jax.grad(
        lambda *a: sum(x.sum() for x in dsa.indexed_attention(
            *a, top_k=64)[:2]), argnums=tuple(range(6))))(
        ones(B, H, T, D), ones(B, G, T, D), ones(B, G, T, D),
        ones(B, Hi, T, d), ones(B, T, d), ones(B, Hi, T))
    specs = {s.name.split("[")[0].replace(".", "_"): s for s in
             fa.kernel_specs(B, H, T, D, interpret=True, kept=True)
             + ix.kernel_specs(B, Hi, T, d, H, G, D, top_k=64,
                               interpret=True)}
    assert sorted(c["name"] for c in calls) == sorted(specs)
    assert len(calls) == 6
    Tp = 640                            # five tiles of 128 a side
    least = specs[ix.THRESHOLD_NAME]
    assert least.grid == (B, 5)
    assert least.operands[0].block_shape == (1, Tp, 128)
    assert [s.shape for s in least.scratch] == [(Tp // 8, 8, 128)]
    bwd = specs[ix.SCORES_BWD_NAME]
    assert bwd.grid == (B, 5, 5)
    assert [op.block_shape for op in bwd.operands if op.kind == "out"] \
        == [(1, Hi, d, 128), (1, Hi, 1, 128), (1, d, Tp)]
    assert specs[ix.PROBS_NAME].operands[0].block_shape == (1, H, 128, D)
    for call in calls:
        spec = specs[call["name"]]
        assert tuple(call["grid"]) == spec.grid, spec.name
        for kind, key in (("in", "in_specs"), ("out", "out_specs")):
            blocks = call[key] if isinstance(call[key], (list, tuple)) \
                else [call[key]]
            assert [tuple(b.block_shape) for b in blocks] == \
                [op.block_shape for op in spec.operands
                 if op.kind == kind], (spec.name, kind)
        params = call.get("compiler_params")
        limit = getattr(params, "vmem_limit_bytes", None)
        assert limit == spec.vmem_limit, spec.name


def test_indexed_attention_specs_in_the_merge_gate():
    names = " ".join(s.name for s in default_kernel_specs())
    for name in ("flash_attention.fwd[float32,T=8192,D=128,kept]",
                 "flash_attention.bwd[float32,T=8192,D=128,kept]",
                 "indexer_scores_fwd[", "indexer_probs[",
                 "indexer_scores_bwd_q_k[",
                 "indexer_threshold[float32,T=8192,top_k=2048]"):
        assert name in names, name
    assert "indexer_scores_bwd_k[" not in names
