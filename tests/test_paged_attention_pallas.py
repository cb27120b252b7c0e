"""Interpret-mode matrix for the ragged paged-attention Pallas kernel
(ops/pallas/paged_attention) vs the XLA gather path — the established
test_flash_attention pattern: every geometry axis the kernel branches
on gets a row (block sizes, ragged per-slot lengths, null-page-0
tables, dead padded lanes, GQA head ratios, verify windows W > 1, the
int8-dequant-in-kernel variant), plus the integration claim: with
MXTPU_PALLAS_PAGED_ATTN=1 the paged engine's ``step_pages`` /
``verify_pages`` actually ride the kernel and the token streams match
the ungated run."""

import numpy as np
import pytest
import jax.numpy as jnp

import mxtpu as mx
from mxtpu import nd
from mxtpu.ops.pallas import paged_attention as pa
from mxtpu.ops.pallas.paged_attention import (paged_decode_attention,
                                              xla_reference)

R = np.random.RandomState(0)


def _setup(B=3, KV=2, rep=2, W=1, D=16, bs=8, M=4, N=9, quant=False,
           pos=None, tables=None, dtype="float32"):
    H = KV * rep
    q = jnp.asarray(R.randn(B, H, W, D).astype(dtype))
    if tables is None:
        tables = R.randint(1, N, (B, M)).astype(np.int32)
    tables = jnp.asarray(tables)
    if pos is None:
        pos = R.randint(0, M * bs - W, B).astype(np.int32)
    pos = jnp.asarray(np.asarray(pos, np.int32))
    if quant:
        pk = jnp.asarray(R.randint(-127, 128, (N, KV, bs, D)).astype(
            np.int8))
        pv = jnp.asarray(R.randint(-127, 128, (N, KV, bs, D)).astype(
            np.int8))
        ks = jnp.asarray((R.rand(N, KV, bs) * 0.1 + 1e-3).astype(
            np.float32))
        vs = jnp.asarray((R.rand(N, KV, bs) * 0.1 + 1e-3).astype(
            np.float32))
        return q, pk, pv, tables, pos, dict(k_scales=ks, v_scales=vs)
    pk = jnp.asarray(R.randn(N, KV, bs, D).astype("float32"))
    pv = jnp.asarray(R.randn(N, KV, bs, D).astype("float32"))
    return q, pk, pv, tables, pos, {}


def _check(q, pk, pv, tables, pos, kw, rtol=1e-4, atol=1e-5):
    out = paged_decode_attention(q, pk, pv, tables, pos, **kw)
    ref = xla_reference(q, pk, pv, tables, pos, **kw)
    np.testing.assert_allclose(np.asarray(out, dtype="float32"),
                               np.asarray(ref, dtype="float32"),
                               rtol=rtol, atol=atol)


@pytest.mark.parametrize("bs", [4, 8, 16])
def test_kernel_matches_xla_across_block_sizes(bs):
    _check(*_setup(bs=bs, M=32 // bs))


def test_kernel_ragged_lengths_and_boundaries():
    """Per-slot positions at page boundaries, start, and full extent."""
    _check(*_setup(B=4, pos=np.array([0, 7, 8, 31])))


def test_kernel_null_page_padded_tables():
    """Table entries past a slot's allocation are null page 0; rows
    whose valid extent ends early must never read their padding."""
    tables = np.array([[3, 0, 0, 0], [5, 6, 0, 0], [1, 2, 7, 8]],
                      np.int32)
    _check(*_setup(B=3, tables=tables, pos=np.array([5, 12, 30])))


def test_kernel_dead_lane_is_finite():
    """A dead pool lane (all-null table, pos 0) flows through with
    garbage-but-FINITE output — the engines mask it downstream, but it
    must not poison the kernel (NaN would)."""
    tables = np.array([[2, 3, 0, 0], [0, 0, 0, 0]], np.int32)
    q, pk, pv, t, pos, kw = _setup(B=2, tables=tables,
                                   pos=np.array([9, 0]))
    out = paged_decode_attention(q, pk, pv, t, pos, **kw)
    assert np.isfinite(np.asarray(out)).all()
    ref = xla_reference(q, pk, pv, t, pos, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("rep", [1, 2, 4])
def test_kernel_gqa_head_ratios(rep):
    _check(*_setup(rep=rep))


@pytest.mark.parametrize("W", [2, 4])
def test_kernel_verify_window_lanes(W):
    """Speculative windows: lane w of slot b attends <= pos[b] + w —
    including windows crossing a page boundary."""
    _check(*_setup(W=W, B=4, pos=np.array([0, 6, 7, 20])))


@pytest.mark.parametrize("W", [1, 4])
def test_kernel_int8_dequant_variant(W):
    _check(*_setup(W=W, quant=True), rtol=1e-3, atol=1e-3)


def test_kernel_bf16_queries():
    _check(*_setup(dtype="bfloat16"), rtol=2e-2, atol=2e-2)


# ------------------------------------------------- tree ancestor masks

def _chain_anc(B, W):
    """Degenerate linear chain: lane w's strict ancestors are lanes
    0..w-1 -> bitmask (1 << w) - 1."""
    return np.tile(((1 << np.arange(W)) - 1).astype(np.int32), (B, 1))


@pytest.mark.parametrize("quant", [False, True])
def test_kernel_tree_ancestor_mask_matches_xla(quant):
    """Tree verify: lane w attends its own root path only (committed
    positions + ancestor lanes + itself, via the per-lane strict-
    ancestor bitmask), including windows crossing page boundaries."""
    q, pk, pv, tables, pos, kw = _setup(
        W=4, B=4, pos=np.array([0, 6, 7, 20]), quant=quant)
    anc = jnp.asarray(pa._model_anc(4, 4, branch=2))
    tol = dict(rtol=1e-3, atol=1e-3) if quant else {}
    out = paged_decode_attention(q, pk, pv, tables, pos, anc=anc, **kw)
    ref = xla_reference(q, pk, pv, tables, pos, anc=anc, **kw)
    np.testing.assert_allclose(np.asarray(out, dtype="float32"),
                               np.asarray(ref, dtype="float32"),
                               **(tol or dict(rtol=1e-4, atol=1e-5)))


def test_kernel_tree_degenerate_chain_is_bitwise_linear():
    """A chain ancestor table reproduces the triangular <= pos + w
    window mask BIT-FOR-BIT on the kernel AND the XLA reference — the
    identity that lets mixed linear/tree pools share one verify
    program."""
    q, pk, pv, tables, pos, kw = _setup(W=4, B=4,
                                        pos=np.array([0, 6, 7, 20]))
    anc = jnp.asarray(_chain_anc(4, 4))
    np.testing.assert_array_equal(
        np.asarray(paged_decode_attention(q, pk, pv, tables, pos,
                                          anc=anc, **kw)),
        np.asarray(paged_decode_attention(q, pk, pv, tables, pos,
                                          **kw)))
    np.testing.assert_array_equal(
        np.asarray(xla_reference(q, pk, pv, tables, pos, anc=anc,
                                 **kw)),
        np.asarray(xla_reference(q, pk, pv, tables, pos, **kw)))


def test_kernel_tree_window_past_bitmask_cap_raises_k004():
    """W > 32 cannot be expressed in the int32 ancestor bitmask — the
    call raises the K004 geometry rule even in interpret mode (it is a
    correctness bound, not a TPU lowering rule)."""
    q, pk, pv, tables, pos, kw = _setup(W=40, M=8,
                                        pos=np.zeros(3, np.int32))
    anc = jnp.asarray(np.zeros((3, 40), np.int32))
    with pytest.raises(ValueError, match="K004"):
        paged_decode_attention(q, pk, pv, tables, pos, anc=anc, **kw)


# ------------------------------------------------- engine integration

def _engine(cache_dtype, spec_k=0, spec_tree=None, prefill_chunk=8):
    from mxtpu.models.transformer import (TransformerLM,
                                          transformer_lm_sharding_rules)
    from mxtpu.parallel import PagedContinuousBatchingEngine
    from mxtpu.parallel.mesh import DeviceMesh

    mx.random.seed(1)   # the cycling micro model: drafts really accept
    lm = TransformerLM(20, units=32, hidden_size=64, num_layers=1,
                       num_heads=4, num_kv_heads=2)
    lm.initialize()
    return PagedContinuousBatchingEngine(
        lm, DeviceMesh(dp=1), transformer_lm_sharding_rules(),
        num_slots=2, max_length=64, block_size=8,
        prefill_chunk=prefill_chunk, cache_dtype=cache_dtype,
        spec_k=spec_k, spec_tree=spec_tree)


def _drive(cache_dtype, spec_k=0, spec_tree=None, eng=None):
    eng = eng or _engine(cache_dtype, spec_k, spec_tree)
    rng = np.random.RandomState(0)
    pat = rng.randint(0, 20, (1, 4))
    r1 = eng.submit(nd.array(np.tile(pat, 4).astype(np.int32)), 12)
    r2 = eng.submit(nd.array(rng.randint(0, 20, (1, 5)),
                             dtype="int32"), 8)
    res = eng.run()
    return (res[r1].asnumpy(), res[r2].asnumpy()), eng.stats


@pytest.mark.parametrize("cache_dtype", ["float32", "int8"])
def test_step_pages_rides_kernel_when_gated(cache_dtype, monkeypatch):
    """ISSUE-10 acceptance: with the env gate on, the paged engine's
    decode step traces through the Pallas kernel (invocation counter
    moves) and the streams match the ungated XLA-path run."""
    want, _ = _drive(cache_dtype)
    monkeypatch.setenv("MXTPU_PALLAS_PAGED_ATTN", "1")
    before = pa.invocation_count()
    got, st = _drive(cache_dtype)
    assert pa.invocation_count() > before, "kernel never traced"
    for w, g in zip(want, got):
        assert np.array_equal(w, g)
    # the engine names the path its program families resolved to
    paths = st["attention_paths"]
    assert {k.partition("[")[0] for k in paths} == {
        "paged_attention", "paged_prefill"}
    assert all(k.partition("[")[2].startswith(
        "D=8,bs=8,%s" % cache_dtype) for k in paths)
    assert set(paths.values()) == {"pallas: MXTPU_PALLAS_PAGED_ATTN=1"}


@pytest.mark.slow
def test_verify_pages_rides_kernel_when_gated(monkeypatch):
    """The speculative verify window rides the same kernel (W > 1
    lanes) — accepts still fire and the stream matches ungated.

    slow (round 16, tier-1 wall-time budget): the decode-step gated
    integration stays in tier-1 via test_step_pages_rides_kernel_when_
    gated, and W > 1 kernel-vs-XLA parity via the verify-window rows of
    the unit matrix above."""
    want, st0 = _drive("int8", spec_k=3)
    assert st0["accepted_tokens"] > 0
    monkeypatch.setenv("MXTPU_PALLAS_PAGED_ATTN", "1")
    before = pa.invocation_count()
    got, st = _drive("int8", spec_k=3)
    assert pa.invocation_count() > before
    assert st["accepted_tokens"] > 0
    for w, g in zip(want, got):
        assert np.array_equal(w, g)


@pytest.mark.slow
def test_tree_verify_rides_kernel_when_gated(monkeypatch):
    """TREE verify rides the kernel too (the ancestor bitmask flows in
    as a fourth scalar-prefetch operand) — trees really accept and the
    streams match the ungated XLA-path run bit-for-bit.

    slow (round 23, tier-1 wall-time budget — the round-16 pattern of
    test_verify_pages_rides_kernel_when_gated): kernel-vs-XLA TREE
    parity stays in tier-1 via the ancestor-mask unit matrix above
    (test_kernel_tree_ancestor_mask_matches_xla + the degenerate-chain
    bitwise identity), and the gated engine integration via
    test_step_pages_rides_kernel_when_gated."""
    want, st0 = _drive("int8", spec_tree=(6, 2))
    assert st0["tree_nodes_drafted"] > 0
    assert st0["accepted_tokens"] > 0
    monkeypatch.setenv("MXTPU_PALLAS_PAGED_ATTN", "1")
    before = pa.invocation_count()
    got, st = _drive("int8", spec_tree=(6, 2))
    assert pa.invocation_count() > before
    assert st["tree_nodes_drafted"] > 0
    for w, g in zip(want, got):
        assert np.array_equal(w, g)


# ------------------------------------------------- tri-state gating


def test_tri_state_mode_parsing(monkeypatch):
    """MXTPU_PALLAS_PAGED_ATTN is a tri-state: 0/off/false, 1/on/true,
    everything else (incl. unset) resolves to auto."""
    for v, want in [("0", "0"), ("off", "0"), ("FALSE", "0"),
                    ("1", "1"), ("on", "1"), ("True", "1"),
                    ("auto", "auto"), ("", "auto"), ("bogus", "auto")]:
        monkeypatch.setenv("MXTPU_PALLAS_PAGED_ATTN", v)
        assert pa.paged_attention_mode() == want
    monkeypatch.delenv("MXTPU_PALLAS_PAGED_ATTN", raising=False)
    assert pa.paged_attention_mode() == "auto"


def test_auto_resolves_off_on_interpret_only_cpu_host(monkeypatch):
    """The K007 rule applied at runtime: on a CPU backend the default
    `auto` keeps the XLA gather path (no interpret-mode overhead);
    `1` forces the kernels (the parity arm), `0` forces XLA.  Both
    kernels share one resolution."""
    from mxtpu.ops.pallas import prefill_attention as pf

    monkeypatch.delenv("MXTPU_PALLAS_PAGED_ATTN", raising=False)
    assert pa.paged_attention_enabled() is False
    assert pf.paged_prefill_enabled() is False
    monkeypatch.setenv("MXTPU_PALLAS_PAGED_ATTN", "1")
    assert pa.paged_attention_enabled() is True
    assert pf.paged_prefill_enabled(D=16, block_size=8,
                                    pool_dtype="float32", T=8,
                                    rep=2) is True
    monkeypatch.setenv("MXTPU_PALLAS_PAGED_ATTN", "0")
    assert pa.paged_attention_enabled(D=128, block_size=32,
                                      pool_dtype="int8") is False
    assert pf.paged_prefill_enabled() is False


def test_auto_default_keeps_xla_arm_on_cpu(monkeypatch):
    """Honest default flip: on this interpret-only host the engine's
    default-auto run never traces a kernel (counter-asserted), so the
    existing CPU parity suites keep testing the XLA reference arm."""
    from mxtpu.ops.pallas import counters

    monkeypatch.delenv("MXTPU_PALLAS_PAGED_ATTN", raising=False)
    before = dict(counters.counts())
    _, st = _drive("float32")
    after = counters.counts()
    for name in ("paged_attention", "paged_prefill"):
        assert after.get(name, 0) == before.get(name, 0)
    assert st["attention_paths"] and all(
        v.startswith("xla: K007") for v in st["attention_paths"].values())


def test_attention_paths_are_the_engines_own(monkeypatch):
    """Two live engines of one geometry: each reports the verdicts baked
    into ITS programs — the second's forced kernel does not overwrite
    the first's record, and the second's 16-token chunk bucket does not
    show up in the first's."""
    monkeypatch.delenv("MXTPU_PALLAS_PAGED_ATTN", raising=False)
    first = _engine("float32")
    assert first.stats["attention_paths"] == {}     # nothing traced yet
    _drive("float32", eng=first)
    monkeypatch.setenv("MXTPU_PALLAS_PAGED_ATTN", "1")
    second = _engine("float32", prefill_chunk=16)
    _drive("float32", eng=second)
    mine, theirs = (e.stats["attention_paths"] for e in (first, second))
    assert mine and all(v.startswith("xla: K007") for v in mine.values())
    assert set(theirs.values()) == {"pallas: MXTPU_PALLAS_PAGED_ATTN=1"}
    assert any(",T=16," in k for k in theirs)
    assert not any(",T=16," in k for k in mine)


def test_auto_declines_visibly_on_an_accelerator(monkeypatch):
    """On a real accelerator `auto` still picks the XLA gather path for
    geometry the chip would refuse — the engine's default block_size=16
    under an int8 cache (sublane tile 32) — but never in silence: one
    RuntimeWarning naming the violated K-rule per recording scope (one
    decoder's programs), and the verdict recorded for
    ``stats["attention_paths"]``."""
    import warnings

    import jax

    from mxtpu.ops.pallas import prefill_attention as pf

    monkeypatch.delenv("MXTPU_PALLAS_PAGED_ATTN", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pa.recording_paths({}) as paths:
        with pytest.warns(RuntimeWarning, match="K002: block_size=16"):
            assert pa.paged_attention_enabled(
                D=128, block_size=16, pool_dtype="int8") is False
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # the second decline is silent
            assert pa.paged_attention_enabled(
                D=128, block_size=16, pool_dtype="int8") is False
        assert pa.paged_attention_enabled(
            D=128, block_size=32, pool_dtype="int8") is True
        with pytest.warns(RuntimeWarning, match="K002: q tile 12"):
            assert pf.paged_prefill_enabled(
                D=128, block_size=16, pool_dtype="bfloat16", T=3, rep=4,
                q_dtype="bfloat16") is False
    assert paths["paged_attention[D=128,bs=16,int8]"].startswith(
        "xla: K002")
    assert paths["paged_attention[D=128,bs=32,int8]"] == \
        "pallas: geometry legal on tpu"
    assert paths["paged_prefill[D=128,bs=16,bfloat16,T=3,rep=4,"
                 "q=bfloat16]"].startswith("xla: K002")
