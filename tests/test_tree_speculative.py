"""Tree speculative decoding (ISSUE 18): multi-branch draft trees
verified in ONE pooled cache read with per-lane ancestor masks.

The acceptance claim is the module docstring's bit-exactness contract
extended to trees: every tree-speculated stream — greedy, seeded-
sampled, penalized; slot and paged pools; fp32 and int8 caches; under
``serving.verify`` fault plans with retries — is bit-identical to the
isolated non-speculative ``ShardedDecoder.generate`` reference, and a
rerun reproduces it.  Compile discipline rides the same power-of-two
window ladder as linear verify, so the tree program family is bounded
by the ladder, never per-tree-shape (C001-clean).

Same cycling-micro-model fixture discipline as tests/test_speculative:
model seed 1 at vocab 20, module-scoped engines, branchy prompts whose
trailing n-grams recur with DIFFERENT continuations so the TreeDrafter
proposes real forks (and real side-branch accepts — the cache fix-up
path is exercised, not just compiled)."""

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import nd
from mxtpu.models.sampler import TreeDrafter
from mxtpu.models.transformer import (TransformerLM,
                                      transformer_lm_sharding_rules)
from mxtpu.parallel import (ContinuousBatchingEngine,
                            PagedContinuousBatchingEngine,
                            ShardedDecoder)
from mxtpu.parallel.mesh import DeviceMesh
from mxtpu.resilience import fault_plan

MAXLEN = 64

# branchy prompts: the trailing bigram recurs with two continuations,
# so propose_tree grafts an alternate branch at the divergence point
P_FORK = [1, 2, 3, 1, 2, 4, 1, 2, 3, 1, 2]
P_FORK2 = [5, 6, 7, 5, 6, 8, 5, 6, 7, 5, 6]
P_FORK3 = [9, 3, 2, 9, 3, 5, 9, 3, 2, 9, 3]


@pytest.fixture(scope="module")
def tiny():
    mx.random.seed(1)
    net = TransformerLM(20, units=32, hidden_size=64, num_layers=1,
                        num_heads=4, num_kv_heads=2)
    net.initialize()
    return net


@pytest.fixture(scope="module")
def mesh():
    return DeviceMesh(dp=1)


@pytest.fixture(scope="module")
def isolated(tiny, mesh):
    return ShardedDecoder(tiny, mesh, transformer_lm_sharding_rules())


def _want(isolated, p, n, **kw):
    return isolated.generate(p, max_new_tokens=n, max_length=MAXLEN,
                             **kw).asnumpy()


def _arr(tokens):
    return nd.array(np.asarray([tokens], np.int32))


# ---------------------------------------------------- drafter unit block

def test_tree_drafter_grammar_is_topological():
    """parent[j] is a WINDOW LANE < j+1 (lane order topological, lane 0
    = root), depths are 1-based path lengths consistent with parents."""
    d = TreeDrafter(max_nodes=8, branch=2)
    toks, par, dep = d.propose_tree(P_FORK, 8, 8)
    assert toks and len(toks) == len(par) == len(dep)
    for j, p in enumerate(par):
        assert 0 <= p <= j
        assert dep[j] == (1 if p == 0 else dep[p - 1] + 1)


def test_tree_drafter_forks_at_divergence():
    """The trailing 3-gram [1, 2, 3] occurred twice with DIFFERENT
    continuations (5 most recently, 4 before that): the primary chain
    takes 5 and the alternate grafts 4 as its SIBLING — and sibling
    tokens under one parent are unique."""
    h = [1, 2, 3, 4, 1, 2, 3, 5, 1, 2, 3]
    toks, par, dep = TreeDrafter(max_nodes=8, branch=2).propose_tree(
        h, 8, 8)
    kids = {}
    for j, p in enumerate(par):
        kids.setdefault(p, []).append(toks[j])
    assert any(len(v) > 1 for v in kids.values()), "no fork proposed"
    for v in kids.values():
        assert len(v) == len(set(v)), "sibling tokens must be unique"
    assert 5 in toks and 4 in toks
    assert toks[0] == 5          # most-recent occurrence is primary


def test_tree_drafter_branch_cap_and_node_budget():
    toks1, par1, _ = TreeDrafter(max_nodes=8, branch=1).propose_tree(
        P_FORK, 8, 8)
    kids = {}
    for j, p in enumerate(par1):
        kids.setdefault(p, []).append(j)
    assert all(len(v) <= 1 for v in kids.values())  # branch=1 = a chain
    toks2, _, _ = TreeDrafter(max_nodes=8, branch=2).propose_tree(
        P_FORK, 2, 8)
    assert len(toks2) <= 2                          # caller node budget
    toks3, _, dep3 = TreeDrafter(max_nodes=8, branch=2).propose_tree(
        P_FORK, 8, 1)
    assert toks3 and max(dep3) <= 1                 # depth budget


def test_tree_drafter_deterministic():
    d = TreeDrafter(max_nodes=6, branch=2)
    assert d.propose_tree(P_FORK, 6, 6) == d.propose_tree(P_FORK, 6, 6)


# ---------------------------------------------------- config validation

def test_spec_tree_config_forms(tiny, mesh):
    """(nodes, branch) tuples, bare ints and "nodes,branch" strings all
    normalize; out-of-range configs are rejected loudly (the 31-node
    cap is the verify kernel's 32-lane int32 ancestor bitmask)."""
    from mxtpu.parallel.serving import _parse_spec_tree

    assert _parse_spec_tree((6, 2)) == (6, 2)
    assert _parse_spec_tree(6) == (6, 2)
    assert _parse_spec_tree("6,3") == (6, 3)
    assert _parse_spec_tree("31") == (31, 2)
    with pytest.raises(ValueError, match=r"\[1, 31\]"):
        _parse_spec_tree((32, 2))
    with pytest.raises(ValueError, match=r"\[1, 31\]"):
        _parse_spec_tree(0)
    with pytest.raises(ValueError, match="branch"):
        _parse_spec_tree((4, 0))
    with pytest.raises(ValueError, match="spec_tree"):
        _parse_spec_tree(object())


def test_spec_tree_env_ambient(tiny, mesh, monkeypatch):
    monkeypatch.setenv("MXTPU_SPEC_TREE", "5,3")
    eng = ContinuousBatchingEngine(tiny, mesh,
                                   transformer_lm_sharding_rules(),
                                   num_slots=2, max_length=MAXLEN)
    assert eng._spec_tree == (5, 3)
    monkeypatch.delenv("MXTPU_SPEC_TREE")
    eng = ContinuousBatchingEngine(tiny, mesh,
                                   transformer_lm_sharding_rules(),
                                   num_slots=2, max_length=MAXLEN)
    assert eng._spec_tree is None


def test_spec_tree_rejects_draft_block(tiny, mesh):
    """Tree drafting is self-drafted; combining it with a draft model
    is a config conflict, failed loudly like the MoE draft_block
    cases."""
    with pytest.raises(ValueError, match="draft_block"):
        ContinuousBatchingEngine(tiny, mesh,
                                 transformer_lm_sharding_rules(),
                                 num_slots=2, max_length=MAXLEN,
                                 spec_k=2, draft_block=tiny,
                                 spec_tree=(4, 2))


def test_submit_spec_tree_needs_spec_engine(tiny, mesh):
    eng = ContinuousBatchingEngine(tiny, mesh,
                                   transformer_lm_sharding_rules(),
                                   num_slots=2, max_length=MAXLEN)
    with pytest.raises(ValueError, match="spec_tree"):
        eng.submit(_arr(P_FORK), 4, spec_tree=(4, 2))
    with pytest.raises(ValueError, match=r"\[1, 31\]"):
        ContinuousBatchingEngine(tiny, mesh,
                                 transformer_lm_sharding_rules(),
                                 num_slots=2, max_length=MAXLEN,
                                 spec_tree=(32, 2))


# ---------------------------------------------------- parity anchors

REQS = [  # (prompt, max_new, sampling knobs) — one per sampling mode
    (P_FORK, 20, dict()),
    (P_FORK2, 20, dict(temperature=0.8, seed=7)),
    (P_FORK3, 18, dict(temperature=0.6, seed=9,
                       repetition_penalty=1.3)),
]


def _run_tree(eng, isolated, submit_overrides=None):
    """The reference is the isolated generate at the ENGINE's cache
    dtype (docs/inference.md "Quantized serving": an int8 pool matches
    an isolated int8 generate).  A float32 reference for an int8 pool
    is another computation: on P_FORK its top-2 logits at new token 17
    lie 7.0e-5 apart (0.3192337 / 0.3191634) and quantization picks the
    other one, with or without speculation."""
    rids, wants = [], []
    for j, (p, mn, kw) in enumerate(REQS):
        sub = dict(kw)
        if submit_overrides:
            sub.update(submit_overrides(j))
        rids.append(eng.submit(_arr(p), mn, **sub))
        wants.append(_want(isolated, _arr(p), mn,
                           cache_dtype=eng._cache_dtype, **kw))
    res = eng.run()
    for rid, want in zip(rids, wants):
        np.testing.assert_array_equal(res[rid].asnumpy(), want)
    return eng.stats


@pytest.fixture(scope="module")
def slot_tree_eng(tiny, mesh):
    """Shared tree-speculative slot pool (spec_tree=(6, 2))."""
    return ContinuousBatchingEngine(tiny, mesh,
                                    transformer_lm_sharding_rules(),
                                    num_slots=3, max_length=MAXLEN,
                                    spec_tree=(6, 2))


@pytest.fixture(scope="module")
def paged_tree_eng(tiny, mesh):
    """Shared tree-speculative PAGED pool: int8 cache, chunked
    prefill, linear spec_k fallback armed for mixed pools."""
    return PagedContinuousBatchingEngine(
        tiny, mesh, transformer_lm_sharding_rules(), num_slots=3,
        max_length=MAXLEN, cache_dtype="int8", block_size=8,
        prefill_chunk=8, spec_k=3, spec_tree=(6, 2))


@pytest.mark.slow
def test_slot_tree_streams_bit_identical(slot_tree_eng, isolated):
    """ISSUE-18 acceptance, slot engine: greedy, seeded-sampled and
    penalized tree-speculated streams all equal the isolated
    non-speculative reference bit-for-bit, trees really draft, and
    side-branch accepts really re-pack the cache (the fixup program
    compiled — proof the non-identity path ran, not just compiled)."""
    st = _run_tree(slot_tree_eng, isolated)
    assert st["tree_nodes_drafted"] > 0
    assert st["tree_paths"] > 0
    assert st["accepted_tokens"] > 0
    assert "verify_tree_slots" in st["compiled_programs"]


def test_slot_tree_rerun_is_deterministic(slot_tree_eng, isolated):
    """Same engine, second pass over the same workload: bit-identical
    again (per-slot key streams re-derive from the seeds; the n-gram
    tree drafter is a pure function of history)."""
    _run_tree(slot_tree_eng, isolated)


def test_paged_tree_mixed_pool_bit_identical(paged_tree_eng, isolated):
    """ISSUE-18 acceptance, paged engine: int8 cache + chunked prefill
    + a MIXED pool (request 1 opts out to LINEAR drafting with
    spec_tree=False) — linear windows ride the tree verify program as
    degenerate chains, and every stream still matches the isolated
    int8 reference bit-for-bit."""
    st = _run_tree(paged_tree_eng, isolated,
                   submit_overrides=lambda j: (
                       {"spec_tree": False} if j == 1 else {}))
    assert st["tree_nodes_drafted"] > 0
    assert st["drafted_tokens"] > st["tree_nodes_drafted"], \
        "the linear rider never drafted"
    assert "verify_tree_pages" in st["compiled_programs"]
    assert st["blocks_in_use"] == 0


@pytest.mark.slow
def test_paged_tree_shared_prefix_composes(tiny, mesh, isolated):
    """Tree speculation composes with cross-request prefix sharing:
    the second request reuses the donor's prompt pages AND tree-drafts
    its continuation; both streams stay bit-identical.

    slow (round 23, tier-1 wall-time budget): a composition cell — the
    paged bit-exact anchor (mixed pool, int8, chunked prefill) stays in
    tier-1 above, and prefix sharing keeps its own fast anchors in
    tests/test_serving_paged.py."""
    eng = PagedContinuousBatchingEngine(
        tiny, mesh, transformer_lm_sharding_rules(), num_slots=2,
        max_length=MAXLEN, block_size=8, prefill_chunk=8,
        spec_tree=(6, 2))
    long = P_FORK + P_FORK  # 22 tokens: multi-chunk, multi-page
    r1 = eng.submit(_arr(long), 10)
    for _ in range(3):      # admit + 3 chunks -> pages registered
        eng.step()
    r2 = eng.submit(_arr(long + [2]), 10)
    res = eng.run()
    np.testing.assert_array_equal(
        res[r1].asnumpy(), _want(isolated, _arr(long), 10))
    np.testing.assert_array_equal(
        res[r2].asnumpy(), _want(isolated, _arr(long + [2]), 10))
    assert eng.stats["prefix_hit_requests"] >= 1
    assert eng.stats["tree_nodes_drafted"] > 0


@pytest.mark.slow
@pytest.mark.parametrize("cache_dtype", ["float32", "int8"])
@pytest.mark.parametrize("paged", [False, True])
def test_tree_parity_grid(tiny, mesh, isolated, cache_dtype, paged):
    """The slow full matrix: engines x cache dtypes, all three
    sampling modes per cell (the fast anchors above pin one diagonal
    into tier-1)."""
    if paged:
        eng = PagedContinuousBatchingEngine(
            tiny, mesh, transformer_lm_sharding_rules(), num_slots=3,
            max_length=MAXLEN, cache_dtype=cache_dtype, block_size=8,
            prefill_chunk=8, spec_tree=(6, 2))
    else:
        eng = ContinuousBatchingEngine(
            tiny, mesh, transformer_lm_sharding_rules(), num_slots=3,
            max_length=MAXLEN, cache_dtype=cache_dtype,
            spec_tree=(6, 2))
    _run_tree(eng, isolated)


# ---------------------------------------------------- fault coverage

def test_tree_verify_fault_retry_bit_identical(tiny, mesh, isolated):
    """A ``serving.verify`` fault during a TREE iteration quarantines
    only its slot; the neighbor's tree stream is untouched and the
    faulted request's retry restarts from scratch bit-identically —
    the linear-speculation guarantee carried to trees."""
    eng = ContinuousBatchingEngine(tiny, mesh,
                                   transformer_lm_sharding_rules(),
                                   num_slots=2, max_length=MAXLEN,
                                   spec_tree=(6, 2))
    r1 = eng.submit(_arr(P_FORK), 14, temperature=0.8, seed=11)
    r2 = eng.submit(_arr(P_FORK2), 12, retries=1)
    with fault_plan("serving.verify#%d@1:raise=RuntimeError(bad-verify)"
                    % r2) as plan:
        res = eng.run()
    assert plan.stats()["serving.verify"]["fired"] == 1
    np.testing.assert_array_equal(
        res[r1].asnumpy(),
        _want(isolated, _arr(P_FORK), 14, temperature=0.8, seed=11))
    assert eng.status(r2) == "ok"
    np.testing.assert_array_equal(
        res[r2].asnumpy(), _want(isolated, _arr(P_FORK2), 12))
    assert eng.error(r2)["site"] == "serving.verify"


def test_tree_draft_fault_quarantines_only_offender(tiny, mesh,
                                                    isolated):
    """A ``serving.draft`` fault (fired before the tree proposal) fails
    only its request; the neighbor's tree stream stays bit-identical to
    the fault-free reference."""
    eng = PagedContinuousBatchingEngine(
        tiny, mesh, transformer_lm_sharding_rules(), num_slots=2,
        max_length=MAXLEN, block_size=8, prefill_chunk=8,
        spec_tree=(6, 2))
    r1 = eng.submit(_arr(P_FORK), 14)
    r2 = eng.submit(_arr(P_FORK3), 12)
    with fault_plan("serving.draft#%d@2:raise=OSError(bad-tree)"
                    % r2) as plan:
        res = eng.run()
    assert plan.stats()["serving.draft"]["fired"] == 1
    np.testing.assert_array_equal(
        res[r1].asnumpy(), _want(isolated, _arr(P_FORK), 14))
    assert eng.status(r2) == "failed"
    assert eng.error(r2)["site"] == "serving.draft"
    assert eng.stats["blocks_in_use"] == 0


@pytest.mark.slow
def test_malformed_tree_draft_quarantines(tiny, mesh, isolated,
                                          monkeypatch):
    """A drafter that emits a NON-topological parent table (parent lane
    >= own lane) is caught at _TreeDraft construction inside the draft
    phase and quarantines only that slot — malformed trees can never
    reach the compiled verify call.

    slow (round 23, tier-1 wall-time budget): the serving.draft
    quarantine-isolation anchor stays in tier-1 via
    test_tree_draft_fault_quarantines_only_offender; this is the
    defence-in-depth variant for a buggy drafter."""
    eng = ContinuousBatchingEngine(tiny, mesh,
                                   transformer_lm_sharding_rules(),
                                   num_slots=2, max_length=MAXLEN,
                                   spec_tree=(6, 2))
    r1 = eng.submit(_arr(P_FORK), 10)
    r2 = eng.submit(_arr(P_FORK2), 10)
    drafter = eng._tree_drafter_for((6, 2))
    real = drafter.propose_tree
    state = {"n": 0}

    def poisoned(history, max_nodes, max_depth):
        toks, par, dep = real(history, max_nodes, max_depth)
        if toks and history[:len(P_FORK2)] == P_FORK2:
            state["n"] += 1
            par = list(par)
            par[0] = 5          # lane 1 naming parent lane 5: cyclic
        return toks, par, dep

    monkeypatch.setattr(drafter, "propose_tree", poisoned)
    res = eng.run()
    assert state["n"] >= 1
    np.testing.assert_array_equal(
        res[r1].asnumpy(), _want(isolated, _arr(P_FORK), 10))
    assert eng.status(r2) == "failed"
    assert eng.error(r2)["site"] == "serving.draft"
    assert eng.error(r2)["type"] == "ValueError"


# ---------------------------------------------------- compile budget

def test_tree_program_family_rides_the_window_ladder(slot_tree_eng):
    """The tree verify family is bounded by the power-of-two window
    ladder (W in {2, 4, 8} for spec_tree nodes <= 7), NEVER per tree
    shape — plus at most one fix-up program per pool shape.  Rides the
    module engine after its parity traffic, so this asserts over every
    tree shape the tests above pushed through."""
    progs = slot_tree_eng.stats["compiled_programs"]
    n_tree = sum(1 for p in progs if p == "verify_tree_slots")
    assert 1 <= n_tree <= 3, progs     # |pow2 ladder of W <= 8| = 3
    assert sum(1 for p in progs if p == "fixup_slots") <= 1, progs


def test_tree_workload_is_c001_clean(tiny, mesh):
    """compile_budget over a fresh mixed linear/tree workload: the
    verify-tree + fix-up sites stay within the ladder bound under the
    discipline checker (no unbounded per-shape growth — C001-clean)."""
    from mxtpu.analysis import compile_budget

    eng = ContinuousBatchingEngine(tiny, mesh,
                                   transformer_lm_sharding_rules(),
                                   num_slots=2, max_length=MAXLEN,
                                   spec_k=3, spec_tree=(6, 2))
    with compile_budget(4, sites=("serving.verify_tree_slots",
                                  "serving.fixup_slots")):
        eng.submit(_arr(P_FORK), 12)
        eng.submit(_arr(P_FORK2), 10, spec_tree=False)  # linear rider
        eng.run()
        eng.submit(_arr(P_FORK3), 12)                   # reuse, no growth
        eng.run()


# ------------------------------------- red-team the static analyzers

def test_kernel_check_locates_malformed_ancestor_table():
    """Red-team K004: a tree spec whose ancestor table violates the
    strict-ancestor grammar (a lane carrying a bit >= its own lane) is
    a LOCATED ERROR on the pool operands — the model index maps
    validate anc semantics during the sweep, so a malformed table can
    never be modeled as a mask the kernel would refuse to run."""
    from mxtpu.analysis import check_kernels
    from mxtpu.ops.pallas import paged_attention as pa

    bad = pa._model_anc(4, 4)
    bad[:, 1] |= 1 << 1          # lane 1 naming ITSELF an ancestor
    spec = pa.kernel_spec(B=4, KV=2, rep=2, W=4, D=128, block_size=8,
                          max_length=64, num_blocks=16, anc=bad)
    rep = check_kernels([spec])
    hit = rep.filter(code="K004")
    assert not rep.ok and len(hit.diagnostics) >= 1
    assert {d.subject for d in hit.diagnostics} <= {
        "%s.pool_k" % spec.name, "%s.pool_v" % spec.name}
    assert any("own lane" in d.message for d in hit.diagnostics)


def test_kernel_check_locates_unclosed_ancestor_table():
    """Red-team K004, transitivity: a lane naming an ancestor without
    inheriting THAT lane's ancestors (an unrooted side chain) is also
    a located ERROR — and the unmodified model table passes clean."""
    from mxtpu.analysis import check_kernels
    from mxtpu.ops.pallas import paged_attention as pa

    bad = pa._model_anc(4, 4)
    bad[:, 3] = 1 << 1           # lists lane 1 but drops the root bit
    spec = pa.kernel_spec(B=4, KV=2, rep=2, W=4, D=128, block_size=8,
                          max_length=64, num_blocks=16, anc=bad)
    rep = check_kernels([spec])
    assert not rep.ok
    assert any("root" in d.message or "transitively" in d.message
               for d in rep.filter(code="K004").diagnostics)
    ok = pa.kernel_spec(B=4, KV=2, rep=2, W=4, D=128, block_size=8,
                        max_length=64, num_blocks=16, tree=True)
    assert check_kernels([ok]).ok


def test_kernel_check_tree_mesh_mismatch_is_k009():
    """Red-team K009: a tree spec declaring a shard count that does not
    divide the kv heads is recorded as-is by the builder and located
    by the pass (GSPMD would pad around the kernel, not run it)."""
    from mxtpu.analysis import check_kernels
    from mxtpu.ops.pallas import paged_attention as pa

    spec = pa.kernel_spec(B=8, KV=8, rep=4, W=8, D=128, block_size=32,
                          max_length=512, cache_dtype="int8",
                          tree=True, mesh_axis=("tp", 3))
    rep = check_kernels([spec])
    k9 = rep.filter(code="K009")
    assert not rep.ok and len(k9.diagnostics) == 1
    assert "mesh-axis mismatch" in k9.diagnostics[0].message


def test_default_kernel_specs_include_tree_and_pass_clean():
    """The shipped self-application covers the tree geometries (fp32
    and int8, W in {4, 8}, plus a tp-sharded variant) and the whole
    set verdicts clean — the merge gate now prices tree verify too."""
    from mxtpu.analysis import check_kernels
    from mxtpu.analysis.kernel_check import default_kernel_specs

    specs = default_kernel_specs()
    trees = [s for s in specs
             if any(p.name == "anc" for p in s.prefetch)]
    assert len(trees) >= 4
    assert any(s.mesh_axis is not None for s in trees)
    assert check_kernels(specs).ok


def test_tree_verify_hbm_traffic_is_o_valid_pages():
    """ISSUE-18 traffic claim, asserted deterministically: sweeping the
    tree spec's REAL index maps, the page pool is fetched O(valid
    pages) per kv-head walk — NOT once per grid step, which is what
    W separate per-branch reads would cost."""
    from mxtpu.analysis import kernel_hbm_traffic
    from mxtpu.ops.pallas import paged_attention as pa

    spec = pa.kernel_spec(B=16, KV=8, rep=4, W=8, D=128, block_size=16,
                          max_length=512, cache_dtype="float32",
                          tree=True)
    grid_points = 1
    for g in spec.grid:
        grid_points *= g
    KV = spec.grid[1]
    valid = int({p.name: p.values for p in spec.prefetch}["nv"].sum())
    tr = kernel_hbm_traffic(spec)
    assert tr["grid_points"] == grid_points
    for name in ("pool_k", "pool_v"):
        op = tr["per_operand"][name]
        # at least one fetch per valid page per kv head, but far off
        # the once-per-grid-step traffic of W per-branch reads
        assert op["fetches"] >= KV * valid
        assert op["fetches"] < tr["grid_points"] // 2
    assert kernel_hbm_traffic(spec) == tr


# ---------------------------------------------------- stats plumbing

def test_tree_stats_flow_through_registry(slot_tree_eng):
    """The tree counters surface in engine stats (and through the
    MetricsRegistry snapshot path every other engine counter rides)."""
    st = slot_tree_eng.stats
    assert st["tree_nodes_drafted"] >= st["tree_paths"] > 0
    assert st["drafted_tokens"] >= st["tree_nodes_drafted"]
    assert 0 < st["draft_hit_rate"] <= 1.0
