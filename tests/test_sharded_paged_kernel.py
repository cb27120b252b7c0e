"""GSPMD-partitioned serving kernels (ISSUE 16 tentpole (a)+(c)): with
tp>1 the paged decode and chunked-prefill Pallas kernels ride a
``shard_map`` over the ``cache_spec`` heads axis instead of falling
back to XLA.  The claims pinned here:

- tp=2 paged decode + chunked prefill trace through the kernels
  (invocation counters move) and the token streams are bit-identical
  to the ungated XLA gather arm, fp32 and int8 cache.
- Speculative verify (W>1) and the hierarchical-cache swap path run
  over the sharded kernel with the same bit-exactness.
- The fused int8 epilogue (quantized weights x int8 KV): the split
  projection is bitwise the unfused projection, the V rows land
  pre-quantized exactly as quantize-on-write would store them, and
  quantized-engine streams match the ungated arm at tp=1 and tp=2.
- Compile discipline: kernel selection is baked into the jit key, so
  the gated arm compiles exactly the same program families as the
  ungated arm over a mixed speculative/int8 workload (compile_budget
  pinned).
- The slot engine (contiguous cache) is untouched by the gate — the
  honest half of "both engines": its streams are identical across
  gate arms and no kernel counter moves.

Runs on the virtual 8-device CPU mesh from conftest."""

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import nd
from mxtpu.analysis import compile_budget
from mxtpu.contrib.quantization import quantize_weights
from mxtpu.models.transformer import (TransformerLM,
                                      transformer_lm_sharding_rules)
from mxtpu.ops.pallas import counters
from mxtpu.parallel import (ContinuousBatchingEngine,
                            PagedContinuousBatchingEngine)
from mxtpu.parallel.mesh import DeviceMesh

VOCAB = 20
GATE = "MXTPU_PALLAS_PAGED_ATTN"


def _model(quantize=False):
    mx.random.seed(1)
    lm = TransformerLM(VOCAB, units=32, hidden_size=64, num_layers=1,
                       num_heads=4, num_kv_heads=2)
    lm.initialize()
    rules = transformer_lm_sharding_rules()
    if quantize:
        # deferred shapes: one forward pass before the Dense rewrite
        lm(nd.array(np.zeros((1, 4), np.int32), dtype="int32"))
        rules = quantize_weights(lm, bits=8, rules=rules)
    return lm, rules


def _paged(lm, rules, tp=2, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_length", 64)
    kw.setdefault("block_size", 8)
    kw.setdefault("prefill_chunk", 8)
    return PagedContinuousBatchingEngine(lm, DeviceMesh(dp=1, tp=tp),
                                         rules, **kw)


def _workload(eng, n=6):
    """Two prompts (one long enough for several prefill chunks, one
    ragged) -> the two greedy streams as numpy arrays."""
    rng = np.random.RandomState(0)
    rids = [eng.submit(nd.array(rng.randint(0, VOCAB, (1, 12)),
                                dtype="int32"), n),
            eng.submit(nd.array(rng.randint(0, VOCAB, (1, 9)),
                                dtype="int32"), n)]
    res = eng.run()
    return [res[r].asnumpy() for r in rids]


# ------------------------------------------ tp=2 default-path parity


@pytest.mark.parametrize("cache_dtype", ["float32", "int8"])
def test_tp2_decode_and_prefill_ride_sharded_kernels(cache_dtype,
                                                     monkeypatch):
    """ISSUE-16 acceptance: at tp=2 BOTH kernels trace (counters
    asserted) and streams match the XLA arm bit-for-bit."""
    lm, rules = _model()
    monkeypatch.setenv(GATE, "0")
    want = _workload(_paged(lm, rules, cache_dtype=cache_dtype))
    monkeypatch.setenv(GATE, "1")
    counters.reset()
    got = _workload(_paged(lm, rules, cache_dtype=cache_dtype))
    c = counters.counts()
    assert c.get("paged_attention", 0) >= 1, "decode kernel never traced"
    assert c.get("paged_prefill", 0) >= 1, "prefill kernel never traced"
    for w, g in zip(want, got):
        assert np.array_equal(w, g)


def test_tp2_speculative_verify_rides_sharded_kernel(monkeypatch):
    """W>1 verify windows over the sharded kernel: the step AND verify
    programs each trace the decode kernel (>=2 bumps) and the
    speculative int8 streams stay bit-identical to the XLA arm."""
    lm, rules = _model()
    monkeypatch.setenv(GATE, "0")
    want = _workload(_paged(lm, rules, cache_dtype="int8", spec_k=3))
    monkeypatch.setenv(GATE, "1")
    counters.reset()
    got = _workload(_paged(lm, rules, cache_dtype="int8", spec_k=3))
    assert counters.counts().get("paged_attention", 0) >= 2
    for w, g in zip(want, got):
        assert np.array_equal(w, g)


def test_tp2_hierarchical_swap_over_sharded_kernel(monkeypatch):
    """pin_bytes=1 forces every chain to the host tier; re-submitting
    the prompt swaps it back in, and decode over the swapped-in pages
    rides the sharded kernel with streams equal to the XLA arm."""
    lm, rules = _model()

    def run():
        eng = _paged(lm, rules, cache_dtype="int8",
                     pin_bytes=1, host_cache_bytes="1MiB")
        rng = np.random.RandomState(31)
        p = nd.array(rng.randint(0, VOCAB, (1, 19)), dtype="int32")
        eng.submit(p, 5)
        eng.run()
        r2 = eng.submit(p, 5)
        res = eng.run()
        return res[r2].asnumpy(), dict(eng.stats)

    monkeypatch.setenv(GATE, "0")
    want, st0 = run()
    assert st0["swapped_in_blocks"] >= 1
    monkeypatch.setenv(GATE, "1")
    counters.reset()
    got, st1 = run()
    assert st1["swapped_in_blocks"] >= 1
    assert counters.counts().get("paged_attention", 0) >= 1
    assert np.array_equal(want, got)


# ------------------------------------------------ fused int8 epilogue


def test_fused_epilogue_projection_within_ulps():
    """The split projection (wq_matmul_i8 on the Q/K columns +
    wq_matmul_i8_q8 on the V columns) is the unfused qkv projection to
    within a few ulp of the row's largest value, and the pre-quantized
    V rows are what quantize-on-write (_q8_quantize) would have stored,
    give or take one step where a value sits on a rounding boundary.
    NOT bitwise: a product over a slice of the weight and a slice of
    the product over the whole weight are two XLA programs, free to
    tile the contraction differently (73 of 96 q/k values differ on
    this host, by at most 8.9e-8 at magnitudes up to 0.71).  What a
    user relies on — the same tokens end to end — is the next test's
    and tests/test_quantized_serving.py's to hold."""
    import jax.numpy as jnp
    from mxtpu.ops.tensor import _q8_quantize

    lm, _ = _model(quantize=True)
    attn = lm.layers[0].attn
    H, KV, D = attn._heads, attn._kv_heads, attn._head_dim
    cut = (H + KV) * D
    x = nd.array(np.random.RandomState(3).randn(2, 1, 32)
                 .astype("float32"))
    full = attn.qkv(x).asnumpy()
    qk, vq, vs = attn._project_qkv_fused_q8(x)
    ulp = np.spacing(np.abs(full).max())
    np.testing.assert_allclose(qk.asnumpy(), full[:, :, :cut], rtol=0,
                               atol=4 * ulp)
    q_ref, s_ref = _q8_quantize(
        jnp.asarray(full[:, :, cut:].reshape(2, 1, KV, D)))
    steps = np.abs(vq.asnumpy().reshape(2, 1, KV, D).astype(np.int32)
                   - np.asarray(q_ref).astype(np.int32))
    assert steps.max() <= 1
    np.testing.assert_allclose(vs.asnumpy(), np.asarray(s_ref),
                               rtol=1e-6)


@pytest.mark.parametrize("tp", [1, 2])
def test_fused_epilogue_streams_match_xla_arm(tp, monkeypatch):
    """int8 weights x int8 KV: with the gate on the engine never
    materializes float weights or a dequantized cache between
    projection and attention, and the streams still match the ungated
    arm bit-for-bit (tp=1 and tp=2)."""
    lm, rules = _model(quantize=True)
    attn = lm.layers[0].attn
    monkeypatch.setenv(GATE, "1")
    pool_k, pool_v = attn.init_block_pool(4, 8, dtype="int8")
    assert attn._fused_q8_epilogue_on(pool_v), \
        "fused epilogue not eligible on int8 weights + int8 cache"
    monkeypatch.setenv(GATE, "0")
    want = _workload(_paged(lm, rules, tp=tp, cache_dtype="int8"))
    monkeypatch.setenv(GATE, "1")
    counters.reset()
    got = _workload(_paged(lm, rules, tp=tp, cache_dtype="int8"))
    assert counters.counts().get("paged_attention", 0) >= 1
    for w, g in zip(want, got):
        assert np.array_equal(w, g)


# ------------------------------------------------- compile discipline


def _kernel_families(eng):
    fam = {}
    for k in eng._dec._jit_cache:
        if k[0] in ("page_prefill", "step_pages", "verify_pages"):
            fam[k[0]] = fam.get(k[0], 0) + 1
    return fam


def test_gated_mixed_workload_holds_compile_budget(monkeypatch):
    """Kernel selection lives in the jit key, not in per-call
    branching: over a mixed speculative/int8 workload the gated arm
    compiles exactly the same program families as the ungated arm,
    and the gated run fits the ungated arm's compile budget."""
    lm, rules = _model(quantize=True)

    def run():
        eng = _paged(lm, rules, cache_dtype="int8", spec_k=3)
        _workload(eng)
        return _kernel_families(eng)

    monkeypatch.setenv(GATE, "0")
    base = run()
    assert base.get("page_prefill", 0) >= 1
    monkeypatch.setenv(GATE, "1")
    with compile_budget(sum(base.values()),
                        sites=("serving.page_prefill",
                               "serving.step_pages",
                               "serving.verify_pages")):
        gated = run()
    assert gated == base


# ------------------------------------------------ slot engine honesty


def test_slot_engine_unaffected_by_gate(monkeypatch):
    """The contiguous-cache engine has no paged pool, so the kernels
    never apply: gate on/off streams are identical and the kernel
    counters stay flat."""
    lm, rules = _model()

    def run():
        eng = ContinuousBatchingEngine(lm, DeviceMesh(dp=1, tp=2),
                                       rules, num_slots=2,
                                       max_length=64)
        return _workload(eng)

    monkeypatch.setenv(GATE, "0")
    want = run()
    monkeypatch.setenv(GATE, "1")
    counters.reset()
    got = run()
    assert counters.counts() == {}
    for w, g in zip(want, got):
        assert np.array_equal(w, g)
