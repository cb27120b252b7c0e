"""Quantized serving path (ISSUE 10): int8 KV cache + weight-only
int8/int4 matmuls.

The repo's serving invariant is kept WHERE IT IS EXACT: a quantized
engine's streams are bit-identical to an isolated quantized
``ShardedDecoder.generate(cache_dtype="int8")`` — greedy, seeded-
sampled, penalized, shared-prefix, chunked, speculative, and under a
fault plan with retries, on BOTH engines.  Accuracy vs the FLOAT
reference is a tolerance claim (documented in docs/inference.md):
prefill logits within 2% relative, and the greedy token streams on the
parity prompts here decode identically.

Weight-only quantization: ``contrib.quantization.quantize_weights``
rewrites Dense projections to packed int8/int4 + scales with dequant
fused into the matmul program; forward accuracy and tensor-parallel
parity are pinned below.  Compile discipline: the int8 workloads hold
the same compile budgets as float (the dtype keys ONE extra program
family, never per-request churn).

Runs on the virtual 8-device CPU mesh from conftest."""

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import nd
from mxtpu.analysis import check_compiles, compile_budget, get_ledger
from mxtpu.analysis.memory_estimate import (kv_cache_residency,
                                            paged_kv_cache_residency)
from mxtpu.contrib.quantization import (QuantizedDense, pack_int4,
                                        quantize_weights, unpack_int4)
from mxtpu.models.transformer import (TransformerLM, llama_tiny,
                                      transformer_lm_sharding_rules)
from mxtpu.parallel import (ContinuousBatchingEngine,
                            PagedContinuousBatchingEngine,
                            ShardedDecoder, make_mesh)
from mxtpu.parallel.mesh import DeviceMesh
from mxtpu.resilience import fault_plan

MAXLEN = 32


@pytest.fixture(scope="module")
def tiny():
    mx.random.seed(77)
    net = llama_tiny(vocab_size=50)
    net.initialize()
    return net


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(dp=1, tp=2)


@pytest.fixture(scope="module")
def isolated(tiny, mesh):
    """The per-request reference: one static-batch quantized generate."""
    return ShardedDecoder(tiny, mesh, transformer_lm_sharding_rules())


def _want(isolated, p, n, **kw):
    return isolated.generate(p, max_new_tokens=n, max_length=MAXLEN,
                             cache_dtype="int8", **kw).asnumpy()


def _prompt(rng, t, vocab=50):
    return nd.array(rng.randint(0, vocab, (1, t)), dtype="int32")


# ------------------------------------------------------ cache accounting

def test_int8_cache_bytes_ratio_slot_and_paged(tiny):
    """Satellite 1: int8 pool bytes = 0.5x bf16 PLUS the per-head scale
    tensors (one f32 scale per head per position = 4/(2*D) of the bf16
    payload) — the scales are priced, not free."""
    D = 16  # llama_tiny head_dim
    bf, _ = kv_cache_residency(tiny, 4, 64, "bfloat16")
    i8, shapes = kv_cache_residency(tiny, 4, 64, "int8")
    assert i8 / bf == pytest.approx(0.5 + 2.0 / D)
    # the shape list names the scale tensors explicitly
    assert ((4, 2, 64), "float32") in shapes
    assert ((4, 2, 64, 16), "int8") in shapes

    pb = paged_kv_cache_residency(tiny, 16, 8, "bfloat16")
    p8 = paged_kv_cache_residency(tiny, 16, 8, "int8",
                                  blocks_in_use=3)
    assert (p8["bytes_per_block"] / pb["bytes_per_block"]
            == pytest.approx(0.5 + 2.0 / D))
    assert p8["resident_bytes"] == 3 * p8["bytes_per_block"]


def test_int8_cache_sharded_residency_prices_scales(tiny, mesh):
    """tp-sharded pricing: payload AND scales divide by the kv-head
    shard count (the scale tensors share the payload's head axis)."""
    from mxtpu.parallel.sharding import PartitionSpec as P

    spec = P(None, "tp", None, None)
    rep, _ = kv_cache_residency(tiny, 4, 64, "int8")
    shd, _ = kv_cache_residency(tiny, 4, 64, "int8", cache_spec=spec,
                                mesh=mesh)
    assert shd * 2 == rep


# ------------------------------------------------- accuracy vs float ref

def test_int8_prefill_logits_within_tolerance(tiny):
    """The documented accuracy claim: quantized-cache prefill logits
    within 2% relative of the float path (per-head-per-token symmetric
    int8 — 127 levels over each head vector's own range)."""
    rng = np.random.RandomState(5)
    p = _prompt(rng, 12)
    fp_caches = tiny.init_cache(1, MAXLEN)
    q_caches = tiny.init_cache(1, MAXLEN, "int8")
    ref, _ = tiny.prefill(p, fp_caches)
    out, _ = tiny.prefill(p, q_caches)
    ref, out = ref.asnumpy(), out.asnumpy()
    rel = np.abs(out - ref).max() / np.abs(ref).max()
    assert rel < 0.02, rel


@pytest.mark.slow
def test_int8_greedy_matches_fp_on_parity_prompts(isolated):
    """Greedy int8 decode reproduces the float token stream on the
    parity prompts (ties aside, 127-level per-vector quantization does
    not move this model's argmax).

    slow (round 16, tier-1 wall-time budget): an int8-vs-FLOAT
    agreement claim, not a stream-parity anchor — the bit-exact
    engine-vs-isolated int8 parity tests below stay in tier-1."""
    rng = np.random.RandomState(0)
    for t, n in ((5, 8), (11, 6)):
        p = _prompt(rng, t)
        fp = isolated.generate(p, max_new_tokens=n,
                               max_length=MAXLEN).asnumpy()
        q8 = _want(isolated, p, n)
        assert np.array_equal(fp, q8)


# ------------------------------------------- engine parity (bit-exact)

@pytest.mark.slow
def test_slot_engine_int8_streams_bit_identical(tiny, mesh, isolated):
    """Greedy + seeded-sampled + penalized int8 streams on the SLOT
    engine, each bit-identical to its isolated quantized generate."""
    eng = ContinuousBatchingEngine(tiny, mesh,
                                   transformer_lm_sharding_rules(),
                                   num_slots=2, max_length=MAXLEN,
                                   cache_dtype="int8")
    rng = np.random.RandomState(0)
    # token counts trimmed round 15 (tier-1 wall-time budget); the
    # invariant is one bit-exact stream per sampling MODE, not length
    reqs = [
        (_prompt(rng, 5), 5, {}),
        (_prompt(rng, 9), 4, dict(temperature=0.8, top_k=5, seed=11)),
        (_prompt(rng, 7), 4, dict(temperature=0.7, top_p=0.9, seed=3,
                                  repetition_penalty=1.3)),
        (_prompt(rng, 12), 3, dict(repetition_penalty=1.5)),
    ]
    rids = [eng.submit(p, n, **kw) for p, n, kw in reqs]
    res = eng.run()
    for rid, (p, n, kw) in zip(rids, reqs):
        assert np.array_equal(res[rid].asnumpy(),
                              _want(isolated, p, n, **kw))


def test_paged_engine_int8_shared_chunked_speculative(tiny, mesh,
                                                      isolated):
    """The PAGED engine at cache_dtype="int8" with prefix sharing,
    chunked prefill AND speculation enabled: every stream bit-identical
    to its isolated quantized generate; shared pages really shared
    (quantization is per token, so prefix cache content is donor-
    independent), pool drains clean."""
    eng = PagedContinuousBatchingEngine(
        tiny, mesh, transformer_lm_sharding_rules(), num_slots=2,
        max_length=MAXLEN, block_size=8, prefill_chunk=8,
        cache_dtype="int8", spec_k=2)
    rng = np.random.RandomState(2)
    shared = rng.randint(0, 50, (1, 13))
    pa = nd.array(np.concatenate(
        [shared, rng.randint(0, 50, (1, 4))], axis=1), dtype="int32")
    pb = nd.array(np.concatenate(
        [shared, rng.randint(0, 50, (1, 2))], axis=1), dtype="int32")
    long = _prompt(rng, 17)             # 3 chunks at prefill_chunk=8
    sampled = _prompt(rng, 6)

    # token counts trimmed round 15 (tier-1 wall-time budget)
    ra = eng.submit(pa, 5)
    eng.step()                          # A prefills + registers pages
    eng.step()
    rb = eng.submit(pb, 4)              # shares A's full prefix pages
    rc = eng.submit(long, 3)
    rd = eng.submit(sampled, 4, temperature=0.9, top_k=8, seed=21)
    res = eng.run()
    assert np.array_equal(res[ra].asnumpy(), _want(isolated, pa, 5))
    assert np.array_equal(res[rb].asnumpy(), _want(isolated, pb, 4))
    assert np.array_equal(res[rc].asnumpy(), _want(isolated, long, 3))
    assert np.array_equal(
        res[rd].asnumpy(),
        _want(isolated, sampled, 4, temperature=0.9, top_k=8, seed=21))
    st = eng.stats
    assert st["prefix_hit_requests"] >= 1
    assert st["blocks_in_use"] == 0     # clean drain


def test_int8_speculative_accepts_stay_bit_identical():
    """Speculation must actually FIRE on the int8 path (cycling micro
    model + repetitive prompt — the test_speculative recipe) and the
    stream stays bit-identical to the isolated quantized generate."""
    mx.random.seed(1)
    lm = TransformerLM(20, units=32, hidden_size=64, num_layers=1,
                       num_heads=4, num_kv_heads=2)
    lm.initialize()
    mesh = DeviceMesh(dp=1)
    rules = transformer_lm_sharding_rules()
    iso = ShardedDecoder(lm, mesh, rules)
    rng = np.random.RandomState(0)
    pat = rng.randint(0, 20, (1, 4))
    prompt = nd.array(np.tile(pat, 4).astype(np.int32))
    want = iso.generate(prompt, max_new_tokens=12, max_length=64,
                        cache_dtype="int8").asnumpy()
    eng = ContinuousBatchingEngine(lm, mesh, rules, num_slots=2,
                                   max_length=64, cache_dtype="int8",
                                   spec_k=3)
    rid = eng.submit(prompt, 12)
    res = eng.run()
    assert np.array_equal(res[rid].asnumpy(), want)
    assert eng.stats["accepted_tokens"] > 0   # speculation really fired


def test_int8_fault_plan_retry_bit_identical(tiny, mesh, isolated):
    """The PR-4 containment contract at int8: a deterministic
    serving.step fault quarantines one request, its retry restarts
    bit-identically, and the NEIGHBOR stream never shifts."""
    eng = PagedContinuousBatchingEngine(
        tiny, mesh, transformer_lm_sharding_rules(), num_slots=2,
        max_length=MAXLEN, block_size=8, prefill_chunk=8,
        cache_dtype="int8")
    rng = np.random.RandomState(4)
    pv = _prompt(rng, 6)                # the faulted request
    pn = _prompt(rng, 9)                # the neighbor
    with fault_plan("serving.step#0@2:raise=RuntimeError(injected)"):
        rv = eng.submit(pv, 6, retries=1)
        rn = eng.submit(pn, 7, temperature=0.6, top_k=4, seed=9)
        res = eng.run()
    assert np.array_equal(res[rv].asnumpy(), _want(isolated, pv, 6))
    assert np.array_equal(
        res[rn].asnumpy(),
        _want(isolated, pn, 7, temperature=0.6, top_k=4, seed=9))
    assert eng.stats["retried_requests"] == 1
    assert eng.stats["blocks_in_use"] == 0


# --------------------------------------------------- weight-only matmuls

def test_pack_unpack_int4_roundtrip():
    rng = np.random.RandomState(0)
    q = rng.randint(-7, 8, (6, 10)).astype(np.int8)
    assert np.array_equal(unpack_int4(pack_int4(q)), q)


def test_quantize_weights_int8_accuracy_and_structure():
    mx.random.seed(3)
    lm = llama_tiny(vocab_size=50)
    lm.initialize()
    x = nd.array(np.random.RandomState(0).randint(0, 50, (1, 6)),
                 dtype="int32")
    ref = lm(x).asnumpy()
    rules = quantize_weights(lm, bits=8,
                             rules=transformer_lm_sharding_rules())
    out = lm(x).asnumpy()
    rel = np.abs(out - ref).max() / np.abs(ref).max()
    assert rel < 0.02, rel
    # every projection of the 2-layer tiny decoder got rewritten
    # (qkv/out + gate/up/down per layer, plus lm_head)
    assert len(rules.quantized_params) == 11
    assert any(isinstance(b, QuantizedDense)
               for b in lm.layers[0].attn._children.values())
    # the packed weight kept its NAME (rules keep matching) and dtype
    qkv = lm.layers[0].attn.qkv
    assert qkv.weight.name.endswith("qkv_weight")
    assert str(qkv.weight.dtype) == "int8"
    # scale rules were appended with exact names
    assert any("wscale" in pat for pat, _ in rules.iter_rules())


def test_quantize_weights_int4_group_scales():
    mx.random.seed(3)
    lm = llama_tiny(vocab_size=50)
    lm.initialize()
    x = nd.array(np.random.RandomState(0).randint(0, 50, (1, 6)),
                 dtype="int32")
    ref = lm(x).asnumpy()
    quantize_weights(lm, bits=4, group_size=32,
                     rules=transformer_lm_sharding_rules())
    out = lm(x).asnumpy()
    rel = np.abs(out - ref).max() / np.abs(ref).max()
    assert rel < 0.25, rel              # 15 levels, group-wise scales
    qkv = lm.layers[0].attn.qkv
    assert qkv.weight.shape[1] == 64 // 2          # packed nibbles
    # qkv out dim = units + 2*KV*D = 64 + 2*2*16 = 128
    assert qkv.wscale.shape == (128, 64 // 32)     # (O, groups)


def test_quantize_weights_requires_initialized():
    lm = llama_tiny(vocab_size=50)   # never initialized
    with pytest.raises(mx.base.MXTPUError, match="initialize"):
        quantize_weights(lm, bits=8)


def test_quantized_weights_tp_parity(mesh):
    """The packed weight keeps the fp weight's TP layout and the scale
    rules ride along: tp=2 sharded decode of a weight-quantized block
    emits the same tokens as the single-device run."""
    mx.random.seed(9)
    lm = llama_tiny(vocab_size=50)
    lm.initialize()
    rng = np.random.RandomState(1)
    p = _prompt(rng, 7)
    lm(p)                               # resolve deferred shapes
    rules = quantize_weights(lm, bits=8,
                             rules=transformer_lm_sharding_rules())
    one = ShardedDecoder(lm, DeviceMesh(dp=1), rules).generate(
        p, max_new_tokens=4, max_length=MAXLEN).asnumpy()
    two = ShardedDecoder(lm, mesh, rules).generate(
        p, max_new_tokens=4, max_length=MAXLEN).asnumpy()
    assert np.array_equal(one, two)


@pytest.mark.slow
def test_fully_quantized_engine_bit_identical():
    """The full quantized serving path — weight-only int8 matmuls AND
    int8 KV cache — still holds the engine parity invariant (both sides
    quantized identically, so the proof is by construction; this pins
    the plumbing).

    slow (round 16, tier-1 wall-time budget): the int8-CACHE bit-exact
    parity anchors (slot + paged) and the weight-quantized tp parity
    test stay in tier-1; this composite pins only their combination."""
    mx.random.seed(15)
    lm = llama_tiny(vocab_size=50)
    lm.initialize()
    lm(nd.array(np.zeros((1, 4), np.int32)))   # resolve deferred shapes
    rules = quantize_weights(lm, bits=8,
                             rules=transformer_lm_sharding_rules())
    mesh = DeviceMesh(dp=1)
    iso = ShardedDecoder(lm, mesh, rules)
    eng = PagedContinuousBatchingEngine(
        lm, mesh, rules, num_slots=2, max_length=MAXLEN, block_size=8,
        prefill_chunk=8, cache_dtype="int8")
    rng = np.random.RandomState(6)
    p1, p2 = _prompt(rng, 5), _prompt(rng, 10)
    r1 = eng.submit(p1, 4)
    r2 = eng.submit(p2, 4, temperature=0.8, top_k=6, seed=13)
    res = eng.run()
    assert np.array_equal(res[r1].asnumpy(), _want(iso, p1, 4))
    assert np.array_equal(
        res[r2].asnumpy(),
        _want(iso, p2, 4, temperature=0.8, top_k=6, seed=13))


# ------------------------------------------------------ compile budgets

def test_int8_slot_engine_holds_compile_budget():
    """Satellite 5: the int8-cache mixed workload compiles exactly the
    float workload's program count (2 prefill buckets + 1 pooled step)
    — quantization changes the programs' BODIES, never their FAMILY
    structure; C001 stays clean."""
    # the C001 verdict is about THIS workload's compiles, not what other
    # files' tests left in the process-wide ledger of this worker
    get_ledger().reset()
    mx.random.seed(77)
    tiny = TransformerLM(50, units=32, hidden_size=64, num_layers=1,
                         num_heads=2, num_kv_heads=2)
    tiny.initialize()
    eng = ContinuousBatchingEngine(tiny, DeviceMesh(dp=1),
                                   transformer_lm_sharding_rules(),
                                   num_slots=2, max_length=32,
                                   cache_dtype="int8")
    rng = np.random.RandomState(31)
    with compile_budget(3, sites=("serving.slot_prefill",
                                  "serving.step_slots")):
        for t in (3, 5, 12):
            eng.submit(nd.array(rng.randint(0, 50, (1, t)),
                                dtype="int32"), 3)
        eng.run()
    assert "serving.slot_prefill" not in [
        d.subject for d in check_compiles().filter(code="C001")]
    cache = eng._dec._jit_cache
    assert len([k for k in cache if k[0] == "slot_prefill"]) == 2
    assert len([k for k in cache if k[0] == "step_slots"]) == 1


def test_int8_paged_engine_holds_compile_budget():
    """The paged twin: chunked shared-prefix int8 workload stays at 2
    chunk-bucket prefills + 1 paged step, C001-clean."""
    get_ledger().reset()
    mx.random.seed(77)
    tiny = TransformerLM(50, units=32, hidden_size=64, num_layers=1,
                         num_heads=2, num_kv_heads=2)
    tiny.initialize()
    eng = PagedContinuousBatchingEngine(
        tiny, DeviceMesh(dp=1), transformer_lm_sharding_rules(),
        num_slots=2, max_length=32, block_size=8, prefill_chunk=16,
        cache_dtype="int8")
    rng = np.random.RandomState(31)
    with compile_budget(3, sites=("serving.page_prefill",
                                  "serving.step_pages")):
        for t in (3, 12, 20):
            eng.submit(nd.array(rng.randint(0, 50, (1, t)),
                                dtype="int32"), 3)
        eng.run()
    assert "serving.page_prefill" not in [
        d.subject for d in check_compiles().filter(code="C001")]
    cache = eng._dec._jit_cache
    assert len([k for k in cache if k[0] == "page_prefill"]) == 2
    assert len([k for k in cache if k[0] == "step_pages"]) == 1
