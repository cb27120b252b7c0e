"""memory_estimate (ISSUE 6): sharding-aware per-device HBM accounting
over Symbol graphs and jittable callables, the M0xx budget matrix, and
the acceptance cross-check — estimator totals within 10% of
``jax.jit(...).lower().compile().memory_analysis()`` on three CPU
reference graphs (MLP, sharded transformer block, decode step with KV
cache).  Runs on the virtual 8-device CPU mesh from conftest."""

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxtpu as mx  # noqa: F401 — registers ops for the symbol graphs
from mxtpu import symbol as sym
from mxtpu.analysis import (check_memory, estimate_graph_memory,
                            estimate_jit_memory, kv_cache_residency,
                            xla_memory_stats)
from mxtpu.analysis.memory_estimate import format_bytes, parse_bytes
from mxtpu.parallel.sharding import PartitionSpec as P, ShardingRules

F32 = 4  # bytes


def _mlp(batch=32, din=64, hidden=128, dout=10):
    data = sym.Variable("data")
    fc1 = sym.FullyConnected(data, num_hidden=hidden, name="fc1")
    act = sym.Activation(fc1, act_type="relu", name="act")
    return sym.FullyConnected(act, num_hidden=dout, name="fc2"), \
        (batch, din)


# -- byte helpers -------------------------------------------------------

def test_parse_and_format_bytes():
    assert parse_bytes("2MiB") == 2 * 1024 ** 2
    assert parse_bytes("1.5GiB") == int(1.5 * 1024 ** 3)
    assert parse_bytes(4096) == 4096
    assert parse_bytes("100") == 100
    assert format_bytes(1536) == "1.50KiB"


# -- Symbol-graph accounting -------------------------------------------

def test_graph_estimate_exact_accounting():
    net, dshape = _mlp()
    est = estimate_graph_memory(net, data=dshape)
    # params: fc1 (128,64)+(128,), fc2 (10,128)+(10,)
    assert est.param_bytes == F32 * (128 * 64 + 128 + 10 * 128 + 10)
    assert est.input_bytes == F32 * 32 * 64
    # peak liveness: fc1 out (32,128) + act out (32,128) both live while
    # act computes
    assert est.activation_peak_bytes == F32 * 2 * 32 * 128
    assert est.output_bytes == F32 * 32 * 10
    assert est.total_bytes == (est.param_bytes + est.input_bytes
                               + est.activation_peak_bytes)


def test_graph_estimate_shards_params_per_device():
    net, dshape = _mlp()
    rules = ShardingRules([(r"fc1_weight", P("tp", None)),
                           (r"fc2_weight", P(None, "tp"))])
    est = estimate_graph_memory(net, data=dshape, rules=rules,
                                mesh={"tp": 4})
    # fc1_weight (128,64)/4, fc2_weight (10,128) dim1 /4
    assert est.param_bytes == F32 * (128 * 64 // 4 + 128
                                     + 10 * (128 // 4) + 10)


def test_budget_diagnostics_m001_m002_m003():
    net, dshape = _mlp()
    est = estimate_graph_memory(net, data=dshape)
    rep = check_memory(net, budget_bytes=est.total_bytes // 2,
                       data=dshape)
    bad = rep.filter(code="M001")
    assert len(bad) == 1 and not rep.ok
    assert bad.diagnostics[0].details["total"] == est.total_bytes
    # within budget but above 90% headroom -> M002 WARNING
    rep = check_memory(net, budget_bytes=int(est.total_bytes * 1.05),
                       data=dshape)
    assert rep.ok and len(rep.filter(code="M002")) == 1
    # roomy budget: M003 breakdown always present, no findings
    rep = check_memory(net, budget_bytes="1GiB", data=dshape)
    assert rep.ok and not rep.warnings
    assert len(rep.filter(code="M003")) == 1
    assert len(rep.filter(code="M004")) >= 1


def test_unknown_shapes_reported_m005():
    net, _ = _mlp()
    rep = check_memory(net)  # no input shapes at all
    m5 = rep.filter(code="M005")
    assert len(m5) == 1
    assert "data" in m5.diagnostics[0].details["nodes"]


def test_kv_cache_residency_abstract():
    from mxtpu.models.transformer import llama_tiny

    mx.random.seed(0)
    net = llama_tiny(vocab_size=50)  # init_cache needs no param init
    total, shapes = kv_cache_residency(net, batch=4, max_length=32)
    # 2 layers x (k, v) x (4, kv_heads=2, 32, head_dim=16) f32
    assert shapes == [((4, 2, 32, 16), "float32")] * 4
    assert total == F32 * 4 * (4 * 2 * 32 * 16)
    sharded, _ = kv_cache_residency(net, batch=4, max_length=32,
                                    cache_spec=P(None, "tp"),
                                    mesh={"tp": 2})
    assert sharded == total // 2


def test_paged_kv_cache_residency_accounting():
    """ISSUE-7 satellite: the paged layout — bytes per page, resident
    vs free split, shared-page savings, and the refcounted-once rule
    (a page shared by N tables is ONE page; the unshared equivalent
    would hold shared_extra_refs more copies resident)."""
    from mxtpu.analysis import paged_kv_cache_residency
    from mxtpu.models.transformer import llama_tiny

    mx.random.seed(0)
    net = llama_tiny(vocab_size=50)
    out = paged_kv_cache_residency(net, num_blocks=16, block_size=8,
                                   blocks_in_use=10,
                                   shared_extra_refs=3)
    # 2 layers x (k, v) x (17, 2, 8, 16) f32 — the +1 null page is
    # real HBM and priced in the total, never in the free pool
    per_block = F32 * 4 * (2 * 8 * 16)
    assert out["bytes_per_block"] == per_block
    assert out["total_bytes"] == 17 * per_block
    assert out["resident_bytes"] == 10 * per_block
    assert out["free_bytes"] == 6 * per_block
    assert out["shared_savings_bytes"] == 3 * per_block
    assert out["shapes"] == [((17, 2, 8, 16), "float32")] * 4
    # tp-sharded pool: kv-head axis divides, per-device bytes halve
    sharded = paged_kv_cache_residency(
        net, num_blocks=16, block_size=8,
        cache_spec=P(None, "tp"), mesh={"tp": 2})
    assert sharded["total_bytes"] == out["total_bytes"] // 2
    # check_memory budgets the POOL (one allocation, whatever the
    # sharing degree): a budget that fits the pool passes even when
    # the sum of per-request logical caches would blow it
    rep = check_memory(
        sym.Variable("tokens"), budget_bytes=out["total_bytes"] * 2,
        known_shapes={"tokens": (4, 8)},
        kv_caches=[(s, d) for s, d in out["shapes"]])
    assert rep.ok
    m3 = rep.filter(code="M003").diagnostics[0]
    assert m3.details["kv_cache"] == out["total_bytes"]


def test_paged_residency_prices_hierarchical_tiers_separately():
    """ISSUE-11 satellite: pinned pages count against the HBM side
    (per-device bytes, a slice of the resident pool) while host-spilled
    chains price at UNSHARDED full-page bytes against a separate host
    budget — check_memory raises M006 on a host-tier overflow without
    touching the HBM verdict, and a live engine feeds both counters
    through ``engine=``."""
    from mxtpu.analysis import paged_kv_cache_residency
    from mxtpu.models.transformer import llama_tiny

    mx.random.seed(0)
    net = llama_tiny(vocab_size=50)
    out = paged_kv_cache_residency(net, num_blocks=16, block_size=8,
                                   blocks_in_use=10, pinned_blocks=4,
                                   spilled_blocks=6)
    per_block = F32 * 4 * (2 * 8 * 16)
    assert out["pinned_bytes"] == 4 * per_block
    assert out["spilled_bytes_host"] == 6 * per_block
    # pinned pages are INSIDE the resident pool, never double-counted
    assert out["pinned_bytes"] <= out["resident_bytes"]
    # sharded pool: device bytes halve, HOST bytes do not (host copies
    # are full replicated pages — the swap program replicates its read)
    sharded = paged_kv_cache_residency(
        net, num_blocks=16, block_size=8, cache_spec=P(None, "tp"),
        mesh={"tp": 2}, pinned_blocks=4, spilled_blocks=6)
    assert sharded["pinned_bytes"] == out["pinned_bytes"] // 2
    assert sharded["spilled_bytes_host"] == out["spilled_bytes_host"]
    assert sharded["bytes_per_block_host"] == \
        2 * sharded["bytes_per_block"]
    # host tier budgeted separately: HBM budget passes, host overflows
    rep = check_memory(
        sym.Variable("tokens"), budget_bytes=out["total_bytes"] * 2,
        known_shapes={"tokens": (4, 8)},
        kv_caches=[(s, d) for s, d in out["shapes"]],
        host_budget_bytes=out["spilled_bytes_host"] - 1,
        host_kv_bytes=out["spilled_bytes_host"])
    assert not rep.ok
    m6 = rep.filter(code="M006").diagnostics
    assert len(m6) == 1
    assert m6[0].details["host_kv_bytes"] == out["spilled_bytes_host"]
    m3 = rep.filter(code="M003").diagnostics[0]
    assert m3.details["host_kv_cache"] == out["spilled_bytes_host"]
    # within the host budget: clean
    assert check_memory(
        sym.Variable("tokens"), budget_bytes=out["total_bytes"] * 2,
        known_shapes={"tokens": (4, 8)},
        kv_caches=[(s, d) for s, d in out["shapes"]],
        host_budget_bytes="1GiB",
        host_kv_bytes=out["spilled_bytes_host"]).ok


def test_paged_residency_reads_tier_counters_from_live_engine():
    """``engine=`` carries the hierarchy's live pinned/spilled counters
    into the pricer."""
    from mxtpu.analysis import paged_kv_cache_residency
    from mxtpu.models.transformer import (TransformerLM,
                                          transformer_lm_sharding_rules)
    from mxtpu.parallel import PagedContinuousBatchingEngine
    from mxtpu.parallel.mesh import DeviceMesh

    mx.random.seed(7)
    lm = TransformerLM(32, units=16, hidden_size=32, num_layers=1,
                       num_heads=2, num_kv_heads=2)
    lm.initialize()
    eng = PagedContinuousBatchingEngine(
        lm, DeviceMesh(dp=1), transformer_lm_sharding_rules(),
        num_slots=2, max_length=32, block_size=8, prefill_chunk=8,
        pin_bytes="1MiB", host_cache_bytes="1MiB")
    rng = onp.random.RandomState(0)
    eng.submit(mx.nd.array(rng.randint(0, 32, (1, 17)),
                           dtype="int32"), 4)
    eng.run()
    priced = paged_kv_cache_residency(lm, 0, 0, engine=eng)
    st = eng.stats
    assert st["pinned_blocks"] == 2
    assert priced["pinned_blocks"] == 2
    assert priced["pinned_bytes"] == 2 * priced["bytes_per_block"]
    assert priced["spilled_blocks"] == st["spilled_blocks"] == 0
    # bytes_per_block from the pricer matches the engine's own pricing
    # of its placed pool (what the byte budgets divide by)
    assert priced["bytes_per_block_host"] == eng._bytes_per_block


# -- the XLA cross-check (acceptance: within 10%) ----------------------

def _rel_err(est_total, xla_total):
    return abs(est_total - xla_total) / xla_total


def test_crosscheck_mlp_within_10pct():
    """Reference graph 1: MLP."""
    def mlp(w1, b1, w2, b2, x):
        h = jnp.maximum(x @ w1 + b1, 0.0)
        return h @ w2 + b2

    args = (jax.ShapeDtypeStruct((256, 512), jnp.float32),
            jax.ShapeDtypeStruct((512,), jnp.float32),
            jax.ShapeDtypeStruct((512, 128), jnp.float32),
            jax.ShapeDtypeStruct((128,), jnp.float32),
            jax.ShapeDtypeStruct((64, 256), jnp.float32))
    est = estimate_jit_memory(mlp, *args, param_argnums=(0, 1, 2, 3))
    xla = xla_memory_stats(mlp, *args)
    assert _rel_err(est.total_bytes, xla["total"]) < 0.10, (est, xla)


def test_crosscheck_sharded_transformer_block_within_10pct():
    """Reference graph 2: a transformer block (MHA + SwiGLU FFN) with
    Megatron-sharded params over a 2-way tp mesh; per-device argument
    bytes must match what XLA reports for the sharded module."""
    from jax.sharding import Mesh, NamedSharding

    D, H, T, B = 256, 4, 32, 8
    hd = D // H

    def block(wq, wk, wv, wo, w1, w2, x):
        q = (x @ wq).reshape(B, T, H, hd).transpose(0, 2, 1, 3)
        k = (x @ wk).reshape(B, T, H, hd).transpose(0, 2, 1, 3)
        v = (x @ wv).reshape(B, T, H, hd).transpose(0, 2, 1, 3)
        a = jax.nn.softmax(q @ k.transpose(0, 1, 3, 2) / hd ** 0.5)
        o = (a @ v).transpose(0, 2, 1, 3).reshape(B, T, D)
        h = x + o @ wo
        return h + jax.nn.silu(h @ w1) @ w2

    devs = jax.devices()[:2]
    mesh = Mesh(onp.asarray(devs).reshape(2), ("tp",))
    col = NamedSharding(mesh, P(None, "tp"))
    row = NamedSharding(mesh, P("tp", None))
    rep = NamedSharding(mesh, P())
    f = jax.ShapeDtypeStruct
    args = (f((D, D), jnp.float32), f((D, D), jnp.float32),
            f((D, D), jnp.float32), f((D, D), jnp.float32),
            f((D, 4 * D), jnp.float32), f((4 * D, D), jnp.float32),
            f((B, T, D), jnp.float32))
    in_sh = (col, col, col, row, col, row, rep)
    specs = [P(None, "tp"), P(None, "tp"), P(None, "tp"), P("tp", None),
             P(None, "tp"), P("tp", None), P()]
    # tp-sharded block: the matmul intermediates are tp-sharded too
    # (Megatron column->row), so intermediate liveness divides by tp
    est = estimate_jit_memory(block, *args, arg_specs=specs,
                              mesh={"tp": 2},
                              param_argnums=tuple(range(6)),
                              activation_shards=2)
    xla = xla_memory_stats(block, *args, in_shardings=in_sh,
                           out_shardings=rep)
    assert _rel_err(est.total_bytes, xla["total"]) < 0.10, (est, xla)


def test_crosscheck_decode_step_with_kv_cache_within_10pct():
    """Reference graph 3: one-token decode step — dynamic_update_slice
    into a (B, KV, T, D) cache + attention over the full cache.  Cache
    residency dominates, the serving regime."""
    B, KV, T, D = 8, 4, 256, 64

    def step(cache_k, cache_v, wq, wo, x, pos):
        q = (x @ wq).reshape(B, KV, 1, D)
        k = jax.lax.dynamic_update_slice(
            cache_k, q, (0, 0, pos, 0))
        v = jax.lax.dynamic_update_slice(
            cache_v, (x @ wq).reshape(B, KV, 1, D), (0, 0, pos, 0))
        a = jax.nn.softmax(q @ k.transpose(0, 1, 3, 2) / D ** 0.5)
        o = (a @ v).reshape(B, KV * D)
        return o @ wo, k, v

    f = jax.ShapeDtypeStruct
    args = (f((B, KV, T, D), jnp.float32), f((B, KV, T, D), jnp.float32),
            f((KV * D, KV * D), jnp.float32),
            f((KV * D, KV * D), jnp.float32),
            f((B, KV * D), jnp.float32),
            jnp.int32(7))
    est = estimate_jit_memory(step, *args, param_argnums=(2, 3))
    xla = xla_memory_stats(step, *args)
    assert _rel_err(est.total_bytes, xla["total"]) < 0.10, (est, xla)


def test_nested_jit_intermediates_are_counted():
    """A nested ``jax.jit`` is a call primitive like any other: what is
    live inside it is live in the program (``jax.nn.silu`` is one, which
    is how the sharded block above lost its FFN transient)."""
    def inner(x):
        return jnp.exp(x) * jnp.sin(x)  # both factors live beside the product

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    flat = estimate_jit_memory(lambda x: inner(x) + 1.0, x)
    nested = estimate_jit_memory(lambda x: jax.jit(inner)(x) + 1.0, x)
    assert flat.activation_peak_bytes == 3 * 256 * 256 * 4
    assert nested.activation_peak_bytes == flat.activation_peak_bytes


@pytest.mark.parametrize("read_after", [False, True])
def test_a_value_whose_last_reader_is_a_call_is_freed_inside_it(read_after):
    """XLA inlines the call and frees ``a`` after the sine; a value the
    caller reads again stays for the whole call.  (A unit of
    recomputation's backward pass is such a call, and what the unit kept
    is such a value: tests/test_kimi_linear.py.)"""
    def inner(a):
        b = jnp.sin(a)
        return jnp.exp(b) * b               # b, the exponential, the product

    def flat(x):
        a = x + 1.0
        return inner(a) + a if read_after else inner(a)

    def nested(x):
        a = x + 1.0
        return jax.jit(inner)(a) + a if read_after else jax.jit(inner)(a)

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    size = 256 * 256 * 4
    assert estimate_jit_memory(flat, x).activation_peak_bytes == \
        (4 if read_after else 3) * size
    assert estimate_jit_memory(nested, x).activation_peak_bytes == \
        (4 if read_after else 3) * size


# -- callable path of the registered pass ------------------------------

def test_check_memory_callable_with_budget():
    def f(w, x):
        return jnp.tanh(x @ w)

    args = (jax.ShapeDtypeStruct((64, 64), jnp.float32),
            jax.ShapeDtypeStruct((8, 64), jnp.float32))
    rep = check_memory(f, budget_bytes=1024, sample_args=args)
    assert [d.subject for d in rep.filter(code="M001")] == ["f"]
    rep = check_memory(f, budget_bytes="1MiB", sample_args=args)
    assert rep.ok

    with pytest.raises(ValueError, match="sample_args"):
        check_memory(f, budget_bytes=1024)


# ----------------------------------- kernel HBM traffic (ISSUE 16)


def _prefetch_values(spec, name):
    return {p.name: p.values for p in spec.prefetch}[name]


def test_kernel_hbm_traffic_decode_is_o_valid_pages():
    """The decode kernel's headline claim, asserted deterministically:
    sweeping the REAL index maps over the full grid, the page-pool
    operand is fetched once per VALID page per (row, kv-head) walk
    (plus at most one null-page transition each) — not once per grid
    step, which is the gather path's traffic."""
    from mxtpu.analysis import kernel_hbm_traffic
    from mxtpu.ops.pallas import paged_attention as pa

    spec = pa.kernel_spec(B=16, KV=8, rep=4, W=1, D=128, block_size=16,
                          max_length=512, cache_dtype="float32")
    B, KV, M = spec.grid
    valid = int(_prefetch_values(spec, "nv").sum())
    tr = kernel_hbm_traffic(spec)
    assert tr["grid_points"] == B * KV * M
    for name in ("pool_k", "pool_v"):
        op = tr["per_operand"][name]
        assert KV * valid <= op["fetches"] <= KV * valid + B * KV
        assert op["fetches"] < tr["grid_points"] // 2
        assert op["bytes"] == op["fetches"] * op["block_bytes"]
    # bit-stable: the model is pure host math over the spec
    assert kernel_hbm_traffic(spec) == tr


def test_kernel_hbm_traffic_prefill_q_tiles_fetch_once():
    """Prefill's traffic shape: each q tile is DMAd exactly once per
    (kv head, tile) — the page walk runs in the innermost grid axis,
    so the q operand never thrashes — and the pool walk touches only
    table-live pages."""
    from mxtpu.analysis import kernel_hbm_traffic
    from mxtpu.ops.pallas import prefill_attention as pf

    spec = pf.kernel_spec(T=128, KV=8, rep=4, D=128, block_size=16,
                          max_length=2048, start_pos=1920,
                          cache_dtype="float32")
    KV, n_qt, M = spec.grid
    nv = int(_prefetch_values(spec, "nv")[0])
    tr = kernel_hbm_traffic(spec)
    assert tr["per_operand"]["q"]["fetches"] == KV * n_qt
    pool = tr["per_operand"]["pool_k"]
    assert pool["fetches"] <= KV * n_qt * (nv + 1)
    assert pool["unique_blocks"] <= KV * (nv + 1)


def test_prefill_chunk_tile_residency_beats_full_kv_4x():
    """ISSUE-16 acceptance: at a T=2048 prompt (last 128-token chunk,
    max_length=2048) the XLA gather path materializes the full fp32
    K+V rows — 2 MiB per (slot, kv-head) — while the kernel's
    per-grid-step VMEM (one q tile + one page tile, double-buffered,
    plus scratch) prices >= 4x smaller in the same cost model."""
    from mxtpu.analysis import kernel_vmem_estimate
    from mxtpu.ops.pallas import prefill_attention as pf

    spec = pf.kernel_spec(T=128, KV=8, rep=4, D=128, block_size=16,
                          max_length=2048, start_pos=1920,
                          cache_dtype="float32")
    est = kernel_vmem_estimate(spec)
    xla_row_bytes = 2 * 2048 * 128 * 4          # K + V, fp32, ~2 MiB
    assert xla_row_bytes >= 4 * est["total_bytes"], (
        "chunk-tile residency regressed: %d vs full-K/V %d"
        % (est["total_bytes"], xla_row_bytes))


def test_kernel_hbm_traffic_grid_cap_is_loud():
    """An oversized grid raises instead of silently sampling — the
    traffic model is exact or absent, never approximately right."""
    from mxtpu.analysis import kernel_hbm_traffic
    from mxtpu.ops.pallas import paged_attention as pa

    spec = pa.kernel_spec(B=16, KV=8, rep=4, W=1, D=128, block_size=16,
                          max_length=512, cache_dtype="float32")
    with pytest.raises(ValueError, match="grid"):
        kernel_hbm_traffic(spec, workload={"max_grid_points": 16})
