"""ShardedDecoder: tp-sharded params + on-mesh KV caches must reproduce
the replicated eager decode exactly (VERDICT r4 item 5).  Runs on the
virtual 8-device CPU mesh from conftest.
"""

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import nd
from mxtpu.models.transformer import llama_tiny
from mxtpu.parallel import (ShardedDecoder, ShardingRules, make_mesh)
from mxtpu.models.transformer import transformer_lm_sharding_rules


@pytest.fixture(scope="module")
def tiny():
    net = llama_tiny(vocab_size=50)
    net.initialize()
    return net


def _mesh_tp2():
    return make_mesh(dp=2, tp=2)


def test_sharded_greedy_matches_replicated(tiny):
    rng = np.random.RandomState(3)
    B, Tp, new = 2, 4, 6
    prompt = nd.array(rng.randint(0, 50, (B, Tp)), dtype="int32")

    expect = tiny.generate(prompt, max_new_tokens=new).asnumpy()

    mesh = _mesh_tp2()
    dec = ShardedDecoder(tiny, mesh, transformer_lm_sharding_rules())
    got = dec.generate(prompt, max_new_tokens=new).asnumpy()
    np.testing.assert_array_equal(got, expect)


def test_sharded_step_logits_match_full_context(tiny):
    """Per-position logits through the sharded jitted step equal the
    full-context forward (same check as the eager decode test, but over
    the mesh)."""
    rng = np.random.RandomState(5)
    B, T = 2, 5
    ids = nd.array(rng.randint(0, 50, (B, T)), dtype="int32")
    full = tiny(ids).asnumpy()

    mesh = _mesh_tp2()
    dec = ShardedDecoder(tiny, mesh, transformer_lm_sharding_rules())
    out = dec.generate(ids, max_new_tokens=1).asnumpy()
    # greedy continuation from the full-context argmax must agree
    np.testing.assert_array_equal(
        out[:, -1], full[:, -1].argmax(axis=-1).astype(out.dtype))


def test_single_compiled_step_serves_all_positions(tiny):
    """The decode position is traced: exactly TWO compiled programs for
    an entire generation — one chunked prefill (whole prompt) and one
    decode step reused at every position (the whole point of the
    dynamic-slice cache write)."""
    rng = np.random.RandomState(7)
    prompt = nd.array(rng.randint(0, 50, (2, 3)), dtype="int32")
    mesh = _mesh_tp2()
    dec = ShardedDecoder(tiny, mesh, transformer_lm_sharding_rules())
    dec.generate(prompt, max_new_tokens=4)
    assert len(dec._jit_cache) == 2
    assert sum(1 for k in dec._jit_cache if k[0] == "prefill") == 1


def test_sharded_sampling_reproducible(tiny):
    rng = np.random.RandomState(9)
    prompt = nd.array(rng.randint(0, 50, (1, 3)), dtype="int32")
    mesh = _mesh_tp2()
    dec = ShardedDecoder(tiny, mesh, transformer_lm_sharding_rules())
    a = dec.generate(prompt, max_new_tokens=5, temperature=0.8,
                     seed=123).asnumpy()
    b = dec.generate(prompt, max_new_tokens=5, temperature=0.8,
                     seed=123).asnumpy()
    np.testing.assert_array_equal(a, b)


def test_bucketed_prefill_reuses_compiled_program(tiny):
    """Prompts of lengths 3 and 5 share the padded-to-8 prefill program
    (one prefill + one step entry total), and bucketing changes no
    output."""
    rng = np.random.RandomState(21)
    mesh = _mesh_tp2()
    dec = ShardedDecoder(tiny, mesh, transformer_lm_sharding_rules())
    dec_ref = ShardedDecoder(tiny, mesh, transformer_lm_sharding_rules(),
                             bucket_prefill=False)
    # NO explicit max_length: the default cache length buckets too, so
    # prompt lengths whose totals land in the same power-of-two bucket
    # share one prefill AND one step program (totals 6 and 8 -> cache 8)
    for Tp in (3, 5):
        prompt = nd.array(rng.randint(0, 50, (2, Tp)), dtype="int32")
        got = dec.generate(prompt, max_new_tokens=3).asnumpy()
        want = dec_ref.generate(prompt, max_new_tokens=3).asnumpy()
        np.testing.assert_array_equal(got, want)
    prefills = [k for k in dec._jit_cache if k[0] == "prefill"]
    assert len(prefills) == 1  # both lengths hit the T=8 bucket
    assert len([k for k in dec._jit_cache if k[0] == "step"]) == 1
    assert len([k for k in dec_ref._jit_cache if k[0] == "prefill"]) == 2


def test_bucketed_prefill_matches_eager_generate(tiny):
    rng = np.random.RandomState(22)
    prompt = nd.array(rng.randint(0, 50, (2, 5)), dtype="int32")
    expect = tiny.generate(prompt, max_new_tokens=6).asnumpy()
    dec = ShardedDecoder(tiny, _mesh_tp2(),
                         transformer_lm_sharding_rules())
    got = dec.generate(prompt, max_new_tokens=6).asnumpy()
    np.testing.assert_array_equal(got, expect)


def test_moe_block_disables_bucketing():
    """Padded tokens would join capacity-limited expert routing, so MoE
    blocks must opt out of prefill bucketing automatically."""
    from mxtpu.models.transformer import TransformerLM

    mx.random.seed(9)
    lm = TransformerLM(vocab_size=40, units=16, hidden_size=32,
                       num_layers=2, num_heads=4, num_kv_heads=2,
                       num_experts=4, capacity_factor=4.0)
    lm.initialize()
    mesh = _mesh_tp2()
    dec = ShardedDecoder(lm, mesh, transformer_lm_sharding_rules())
    assert dec._block_has_moe()
    prompt = nd.array(np.random.RandomState(23).randint(0, 40, (2, 3)),
                      dtype="int32")
    expect = lm.generate(prompt, max_new_tokens=3).asnumpy()
    got = dec.generate(prompt, max_new_tokens=3).asnumpy()
    np.testing.assert_array_equal(got, expect)


# -- the decoder's table of program kinds ------------------------------
# One case a row of parallel.decode.PROGRAMS: the kind builds, keys,
# reports and hits through the one path (ShardedDecoder._run).  What
# the programs COMPUTE is the parity suites' business
# (test_serving*.py, test_speculative*.py, test_tree_speculative.py).

_B, _T, _W, _BS, _NB, _M = 2, 32, 4, 8, 9, 4


def _i32(*shape):
    import jax.numpy as jnp
    return jnp.zeros(shape, jnp.int32)


# kind -> (over the block pool?, its inputs after the cache leaves)
_KIND_INPUTS = {
    "step": (False, lambda: (_i32(_B, 1), _i32())),
    "prefill": (False, lambda: (_i32(_B, 8),)),
    "step_slots": (False, lambda: (_i32(_B, 1), _i32(_B))),
    "slot_prefill": (False, lambda: (_i32(1, 8), _i32())),
    "verify_slots": (False, lambda: (_i32(_B, _W), _i32(_B), _i32(_B))),
    "verify_tree_slots": (False, lambda: (
        _i32(_B, _W), _i32(_B), _i32(_B), _i32(_B, _W, _W),
        _i32(_B, _W))),
    "fixup_slots": (False, lambda: (_i32(_B), _i32(_B, _W))),
    "step_pages": (True, lambda: (_i32(_B, 1), _i32(_B, _M), _i32(_B))),
    "page_prefill": (True, lambda: (_i32(1, 8), _i32(_M), _i32(), _i32(),
                                    _i32())),
    "verify_pages": (True, lambda: (_i32(_B, _W), _i32(_B, _M), _i32(_B),
                                    _i32(_B))),
    "verify_tree_pages": (True, lambda: (
        _i32(_B, _W), _i32(_B, _M), _i32(_B), _i32(_B), _i32(_B, _W, _W),
        _i32(_B, _W), _i32(_B, _W))),
    "fixup_pages": (True, lambda: (_i32(_B, _M), _i32(_B), _i32(_B, _W))),
}


@pytest.fixture(scope="module")
def kinds_dec(tiny):
    dec = ShardedDecoder(tiny, make_mesh(tp=1),
                         transformer_lm_sharding_rules())
    dec._ensure_staged(nd.array(np.zeros((_B, 8)), dtype="int32"))
    return dec


@pytest.mark.parametrize("kind", sorted(_KIND_INPUTS))
def test_program_kind_builds_keys_reports_and_hits(tiny, kinds_dec, kind):
    from mxtpu.analysis import get_ledger
    from mxtpu.parallel.decode import PROGRAMS

    dec = kinds_dec
    paged, extras = _KIND_INPUTS[kind]

    def leaves():       # donated by every call: fresh ones each time
        return dec._place_cache(
            tiny.init_block_pool(_NB, _BS) if paged
            else tiny.init_cache(_B, _T))

    def lookups():
        rec = get_ledger().site("serving.%s" % kind)
        return (rec.hits, rec.miss_count) if rec else (0, 0)

    hits, misses = lookups()
    programs = len(dec._jit_cache)
    out = dec._run(kind, leaves(), *extras())
    assert len(dec._jit_cache) == programs + 1
    assert sum(1 for k in dec._jit_cache if k[0] == kind) == 1
    assert lookups() == (hits, misses + 1)
    dec._run(kind, leaves(), *extras())
    assert len(dec._jit_cache) == programs + 1
    assert lookups() == (hits + 1, misses + 1)
    new_leaves = out if PROGRAMS[kind].caches_only else out[1]
    assert len(new_leaves) == len(tiny.layers)
    if not PROGRAMS[kind].caches_only:
        assert out[0].shape[-1] == 50       # logits over the vocabulary


def test_only_the_mixer_implements_the_cache_forms():
    """The seam: a cache form is written once, in the sequence mixer.
    The layers define none and the model keeps the two names its public
    callers use (generate, models/sampler.py); every form passes through
    cached_forward by NAME; every program kind of the decoder drives a
    form the mixer has, or (the fix-ups) an operation on cache leaves."""
    from mxtpu.models.moe import MoEDecoderLayer
    from mxtpu.models.transformer import (CACHE_FORMS, PREFILL_FORMS,
                                          LlamaDecoderLayer,
                                          MultiHeadAttention,
                                          TransformerLM)
    from mxtpu.parallel.decode import PROGRAMS

    for cls, named in ((LlamaDecoderLayer, set()), (MoEDecoderLayer, set()),
                       (TransformerLM, {"step", "prefill"})):
        assert {f for f in CACHE_FORMS if hasattr(cls, f)} == named, cls
        assert "cached_forward" in vars(cls), cls
    for form in CACHE_FORMS:
        assert callable(getattr(MultiHeadAttention, form)), form
    assert set(PREFILL_FORMS) <= set(CACHE_FORMS)
    assert set(PROGRAMS) == set(_KIND_INPUTS)
    for kind, row in PROGRAMS.items():
        assert row.form in CACHE_FORMS or kind.startswith("fixup_"), kind
    assert {row.form for row in PROGRAMS.values()} - {None} == \
        set(CACHE_FORMS)
