"""KeyeVLTextLM through SPMDTrainer.step against its plain reference
(chipbench/references/keye_vl.py: the indexer's scores, the selection by
a row's top-k, attention over the kept keys and the indexer's loss a
block of query rows at a time, every held expert on every token):
logits, both loss terms, every leaf's first gradient and three Adam
steps, float32, at toy widths on seeded weights.  And the parts one by
one: the selection's prefix, the partition of the gradient, the rotation
with three streams, ``flash_attention(keep=...)``, the k-th largest and
the kernel that counts it from one read of the scores, the softmax-routed
experts, the counters, the benchmark's patterns for the kernels' events."""

import importlib
import json
import os
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import mxtpu as mx
from mxtpu import autograd
from mxtpu.models import keye_vl
from mxtpu.observability.metrics import get_registry
from mxtpu.ops import dsa, moe
from mxtpu.ops.pallas import counters, indexer

from chipbench import harness, models, models_keye

fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
ref = importlib.import_module("chipbench.references.keye_vl")
CFG = harness.load_json(harness.HERE, "tests", "configs", "keye-tiny.json")
STEPS, LR, B, T = 3, 1e-3, 2, 40
TOPK = CFG["sa_config"]["topk"]
INDEXER = ("index_q", "index_k", "index_k_gain", "index_k_bias", "index_w")


def _batch(seed=0):
    rng = np.random.default_rng(seed)
    return tuple(rng.integers(0, CFG["vocab_size"], (B, T), dtype=np.int32)
                 for _ in range(2))


def _as_nd(*arrays):
    return tuple(mx.nd.array(a, dtype="int32") for a in arrays)


def _weights(seed=5):
    weights = ref.init_weights(CFG, seed)
    return weights, {k: jnp.asarray(v) for k, v in weights.items()}


def _is_indexer(name):
    return name.split(".")[-1] in INDEXER


def test_the_toy_configuration_has_every_part():
    assert TOPK < T                                     # the selection binds
    assert CFG["num_experts"] < CFG["num_experts_total"]        # a share
    assert CFG["num_key_value_heads"] < CFG["num_attention_heads"]
    assert len(set(CFG["rope_scaling"]["mrope_section"])) > 1
    assert sum(CFG["rope_scaling"]["mrope_section"]) == CFG["head_dim"] // 2


# -------------------------------------------------- the model, forward

@pytest.fixture(scope="module")
def forward():
    tokens, _ = _batch()
    weights, w = _weights()
    net, _ = models_keye.keye_vl_lm(CFG, weights)
    before = keye_vl.dsa_counts()
    logits, index_loss = net(*_as_nd(tokens))
    return ((logits.asnumpy(), index_loss.asnumpy()),
            tuple(np.asarray(a) for a in ref.logits_of(CFG, w, tokens)),
            before, keye_vl.dsa_counts(), net)


def test_logits_match_the_reference(forward):
    (got, _), (want, _, _), *_ = forward
    assert got.shape == (B, T, CFG["vocab_size"])
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)


def test_the_indexers_loss_matches_the_reference(forward):
    (_, got), (_, want, _), *_ = forward
    assert got.shape == (B,) and (want > 0.01).all()
    np.testing.assert_allclose(got, want, rtol=2e-6)


def test_the_counters_count_the_kept_pairs_and_the_loss(forward):
    (_, index_loss), (_, _, kept), before, after, net = forward
    layers = CFG["num_hidden_layers"]
    prefix = sum(min(t + 1, TOPK) for t in range(T))    # with no tie
    assert layers * B * prefix <= kept.sum() < layers * B * prefix * 1.1
    assert after["selected_pairs"] - before.get("selected_pairs", 0.0) \
        == kept.sum()
    np.testing.assert_allclose(
        after["kl_sum"] - before.get("kl_sum", 0.0), index_loss.sum(),
        rtol=1e-5)
    means = [after[net.decoder_layer(i)[0].inner.prefix.rstrip("_")
                   + ".kept_mean"] for i in range(layers)]
    np.testing.assert_allclose(sum(means) * B * T, kept.sum(), rtol=1e-6)
    snap = get_registry().snapshot()
    assert snap["dsa.selected_pairs"] >= kept.sum()
    for name in (indexer.SCORES_FWD_NAME, indexer.PROBS_NAME):
        assert snap["kernel_invocations." + name] >= layers


def test_three_position_streams_reach_the_rotation():
    """Streams that differ give other logits than text's, and the
    reference's: positions (3, T) go all the way down."""
    tokens, _ = _batch()
    weights, w = _weights()
    net, _ = models_keye.keye_vl_lm(CFG, weights)
    rng = np.random.default_rng(3)
    positions = np.sort(rng.integers(0, 3 * T, (3, T)), axis=1).astype(
        np.int32)
    got, got_loss = net(*_as_nd(tokens, positions))
    want, want_loss, _ = ref.logits_of(CFG, w, tokens,
                                       jnp.asarray(positions))
    text, _ = net(*_as_nd(tokens))
    np.testing.assert_allclose(got.asnumpy(), np.asarray(want), rtol=0,
                               atol=2e-6)
    np.testing.assert_allclose(got_loss.asnumpy(), np.asarray(want_loss),
                               rtol=2e-6)
    assert np.abs(got.asnumpy() - text.asnumpy()).max() > 1e-3


# ------------------------------------------ the model through the trainer

@pytest.fixture(scope="module")
def trained():
    """Three steps of the trainer and of the reference on the same
    batches: losses, the first gradient's leaf norms, the weights."""
    weights, w = _weights()
    train = dict(dtype="float32", optimizer="adam", learning_rate=LR,
                 remat=True)
    trainer, named = models_keye.keye_vl_trainer(CFG, train, weights,
                                                 jax.devices()[:1])
    state = tuple({k: jnp.zeros_like(v) for k, v in w.items()}
                  for _ in range(2))
    losses, ref_losses, grads = [], [], None
    for n in range(STEPS):
        tokens, labels = _batch(n)
        losses.append(float(trainer.step(*_as_nd(tokens, labels))))
        total, g = jax.value_and_grad(
            lambda w_: ref.loss_sum(CFG, w_, tokens, labels))(w)
        g = {k: v / (B * T) for k, v in g.items()}
        ref_losses.append(float(total) / (B * T))
        if n == 0:
            _, mean = models.trainer_state(trainer, named)
            grads = ({k: float(v) / (1 - ref.BETA1) for k, v in
                      models.leaf_norms(mean).items()},
                     {k: float(v) for k, v in models.leaf_norms(g).items()})
        w, state = ref.adam_step(w, g, state, LR, n + 1)
    params, _ = models.trainer_state(trainer, named)
    return losses, ref_losses, grads, \
        {k: np.asarray(v) for k, v in params.items()}, \
        {k: np.asarray(v) for k, v in w.items()}, weights


def test_the_two_term_loss_matches_the_reference(trained):
    losses, ref_losses, *_ = trained
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-6)


@pytest.mark.parametrize("name", sorted(ref.weight_shapes(CFG)))
def test_every_leafs_first_gradient_matches_the_reference(trained, name):
    got, want = trained[2]
    assert want[name] > 0
    np.testing.assert_allclose(got[name], want[name], rtol=2e-5)


def test_three_adam_steps_match_the_reference(trained):
    *_, params, w, start = trained
    for name in sorted(w):
        moved = np.abs(w[name] - start[name]).max()
        assert moved > 0, name
        np.testing.assert_allclose(params[name], w[name], rtol=0,
                                   atol=0.02 * moved, err_msg=name)


# --------------------------------------------- the gradient's partition

@pytest.mark.parametrize("term", ["cross_entropy", "indexer"])
def test_each_term_moves_its_own_leaves_alone(term):
    """The cross-entropy's gradient is exactly zero on the indexer's
    leaves and the indexer's loss's exactly zero on all the others."""
    tokens, labels = _batch()
    weights, _ = _weights()
    net, named = models_keye.keye_vl_lm(CFG, weights)
    loss = net.loss(index_weight=0.0 if term == "cross_entropy" else 1.0)
    with autograd.record():
        out = net(*_as_nd(tokens))
        total = loss(out, _as_nd(labels)[0]) if term == "cross_entropy" \
            else out[1]
    total.backward()
    for name, param in named.items():
        size = float(np.abs(param.grad().asnumpy()).max())
        if _is_indexer(name) == (term == "indexer"):
            assert size > 0, name
        else:
            assert size == 0, name


# ------------------------------------------------------------ the parts

def _attention_inputs(T, D, heads=4, groups=2, idx_heads=3, d=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = lambda k, *s: jax.random.normal(k, s, jnp.float32)
    return (n(ks[0], 1, heads, T, D), n(ks[1], 1, groups, T, D),
            n(ks[2], 1, groups, T, D), n(ks[3], 1, idx_heads, T, d),
            n(ks[4], 1, T, d), n(ks[5], 1, idx_heads, T))


def test_rows_with_no_more_than_topk_predecessors_are_dense_attention():
    """The selection's prefix: a query with at most ``top_k`` keys
    before it keeps them all, and its output is causal flash attention's
    bit for bit."""
    q, k, v, *idx = _attention_inputs(T=160, D=16)
    o, _, kept = dsa.indexed_attention(q, k, v, *idx, top_k=64)
    dense = fa.flash_attention(q, jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1),
                               causal=True)
    np.testing.assert_array_equal(np.asarray(o[:, :, :64]),
                                  np.asarray(dense[:, :, :64]))
    assert np.abs(np.asarray(o[:, :, 64:] - dense[:, :, 64:])).max() > 1e-3
    # ties at a row's threshold (exact zeros of the ReLU) are all kept
    assert float(kept[0]) >= sum(min(t + 1, 64) for t in range(160))


@pytest.mark.parametrize("k", [1, 5, 64])
def test_kth_largest_is_the_sorted_rows_kth(k):
    x = jax.random.normal(jax.random.PRNGKey(k), (3, 64, 50))
    x = x.at[0, :9].set(0.0).at[1, ::2].multiply(-0.0)   # ties, both zeros
    got = dsa.kth_largest(x, k, axis=1)
    want = jnp.sort(x, axis=1)[:, -k]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("D", [64, 128, 256])
def test_flash_attention_with_kept_keys_matches_the_dense_path(D):
    """Forward and backward of ``flash_attention(keep=...)`` in interpret
    mode against ``_dense_attention`` under the same mask, at a key count
    the blocks have to pad (200 of 256)."""
    T = 200
    ks = jax.random.split(jax.random.PRNGKey(D), 5)
    q, k, v, g = (jax.random.normal(key, (1, 2, T, D), jnp.float32)
                  for key in ks[:4])
    scores = jax.random.normal(ks[4], (1, T, T), jnp.float32)
    at = jnp.arange(T)
    causal = at[:, None] <= at[None, :]                 # keys first
    least = jnp.sort(jnp.where(causal, scores, -jnp.inf), axis=1)[:, -24]
    least = jnp.where(at >= 24, least, -jnp.inf)
    scale = D ** -0.5

    def through(attend):
        (o, lse), back = jax.vjp(lambda q, k, v: attend(q, k, v), q, k, v)
        return (o, lse) + back((g, jnp.zeros_like(lse)))

    got = through(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, keep=(scores, least)))
    want = through(lambda q, k, v: fa._dense_attention(
        q, k, v, scale, True, (scores, least)))
    for a, b, name in zip(got, want, ("o", "lse", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-5, err_msg=name)
    # rows past the 24th keep 24 keys: not what plain causal gives
    plain = fa.flash_attention(q, k, v, causal=True)
    assert np.abs(np.asarray(got[0] - plain)[:, :, 24:]).max() > 1e-2


@pytest.mark.parametrize("T, tiles, heads, groups, top_k", [
    (200, 1, 4, 2, 24), (600, 5, 8, 2, 64)], ids=["one-tile", "five-tiles"])
def test_the_indexers_kernels_match_their_equations(T, tiles, heads, groups,
                                                    top_k):
    """Scores forward and backward and the mean probabilities against
    plain XLA, at lengths that pad: 200 of 256 is one tile, and 600 of
    640 five tiles of 128 a side, so that the key gradient's sum over
    query tiles and heads in its resident block, the query gradient's
    carry over key tiles, the steps above the diagonal and key heads
    that four query heads share are all in it."""
    H, d = 3, 8
    q, k, _, q_idx, k_idx, w_idx = _attention_inputs(
        T, 16, heads=heads, groups=groups, idx_heads=H, d=d)
    padded, tile = indexer._geometry(T)
    assert padded // tile == tiles
    at = jnp.arange(T)
    causal = at[:, None] <= at[None, :]

    def plain(q_idx, k_idx, w_idx):
        z = jnp.einsum("bhtd,bsd->bhst", q_idx, k_idx,
                       precision=jax.lax.Precision.HIGHEST)
        return jnp.where(causal, jnp.sum(
            w_idx[:, :, None, :] * jax.nn.relu(z), 1), indexer.MASKED)

    g = jax.random.normal(jax.random.PRNGKey(9), (1, T, T))
    got, back = jax.vjp(indexer.indexer_scores, q_idx, k_idx, w_idx)
    want, ref_back = jax.vjp(plain, q_idx, k_idx, w_idx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    for a, b, name in zip(back(g), ref_back(g), ("q_idx", "k_idx", "w")):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4, err_msg=name)
    least = dsa._threshold(got, top_k)
    scale, group = 0.25, heads // groups
    o, lse = fa.flash_attention(q, jnp.repeat(k, group, 1),
                                jnp.repeat(k, group, 1), causal=True,
                                scale=scale, keep=(got, least))
    pbar = indexer.indexer_probs(q, k, lse, got, least, scale)
    want = dsa._dense_probs(q, k, lse, got, least, scale)
    np.testing.assert_allclose(np.asarray(pbar), np.asarray(want), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(pbar.sum(1)), 1.0, rtol=1e-5)


def test_the_scores_backward_is_one_traced_kernel():
    """A traced forward and backward of the scores bumps the forward's
    counter and ONE backward counter, once each; the keys' pass has no
    kernel, no name and no counter of its own any more."""
    *_, q_idx, k_idx, w_idx = _attention_inputs(160, 16)
    before = counters.counts()
    jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(jnp.maximum(indexer.indexer_scores(*a), 0.0)),
        argnums=(0, 1, 2)))(q_idx, k_idx, w_idx)
    after = counters.counts()
    moved = {name: after[name] - before.get(name, 0) for name in after
             if after[name] != before.get(name, 0)}
    assert moved == {indexer.SCORES_FWD_NAME: 1, indexer.SCORES_BWD_NAME: 1}
    assert indexer.SCORES_BWD_NAME == "indexer_scores_bwd_q_k"
    snap = get_registry().snapshot()
    assert snap["kernel_invocations." + indexer.SCORES_BWD_NAME] >= 1
    assert "kernel_invocations.indexer_scores_bwd_k" not in snap
    assert "kernel_invocations.indexer_scores_bwd_q" not in snap


# ------------------------------------------- the selection's thresholds

def _selection_scores(T, batch=2):
    """(batch, T, T) scores as the selection meets them, keys first:
    negative values, runs of ties (sequence 0 in quarters, so that every
    threshold is shared), exact zeros of both signs (sequence 1),
    ``MASKED`` above the diagonal."""
    x = jax.random.normal(jax.random.PRNGKey(T), (batch, T, T))
    x = x.at[0].set(jnp.round(4.0 * x[0]) / 4.0)
    x = x.at[1, ::2].set(jnp.maximum(x[1, ::2], 0.0)).at[1, ::4].multiply(-1.0)
    at = jnp.arange(T)
    return jnp.where(at[:, None] <= at[None, :], x, indexer.MASKED)


@pytest.mark.parametrize("top_k", [
    lambda T: 1, lambda T: 64, lambda T: T - 1, lambda T: T,
    lambda T: T + 5], ids=["1", "64", "T-1", "T", "T+5"])
@pytest.mark.parametrize("T", [200, 256, 1024])
def test_the_thresholds_kernel_is_kth_largest_to_the_bit(T, top_k):
    """``indexer_threshold`` (interpret mode) against the plain form it
    replaces and against a sort, at a length that pads (200 of 256), one
    tile and two: the same bits on every row that has more than
    ``top_k`` keys, -inf on the others, and nothing counted at all where
    no row has."""
    k = top_k(T)
    scores = _selection_scores(T)
    held = np.asarray(scores)
    assert np.signbit(held[1][held[1] == 0.0]).any()    # both zeros
    before = counters.count(indexer.THRESHOLD_NAME)
    got = np.asarray(dsa._threshold(scores, k))
    assert got.shape == (2, T) and got.dtype == np.float32
    assert (got[:, :k] == -np.inf).all()
    assert counters.count(indexer.THRESHOLD_NAME) - before == (k < T)
    if k >= T:
        return
    plain = np.asarray(dsa.kth_largest(scores, k, axis=1))
    np.testing.assert_array_equal(got[:, k:].view(np.uint32),
                                  plain[:, k:].view(np.uint32))
    np.testing.assert_array_equal(got[:, k:],
                                  np.sort(held, axis=1)[:, -k][:, k:])
    # ties at the threshold: a row keeps more than top_k keys
    kept = (held[0] >= got[0][None, :]).sum(0)
    assert (kept[k:] >= k).all() and (T - k < 2 or (kept[k:] > k).any())


def test_the_thresholds_pass_no_gradient():
    scores = _selection_scores(256, batch=1)

    def total(s):
        least = dsa._threshold(s, 64)
        return jnp.sum(jnp.where(jnp.isfinite(least), least, 0.0))

    assert float(total(scores)) != 0.0
    assert not np.asarray(jax.grad(total)(scores)).any()


def test_a_traced_threshold_bumps_its_counter_once():
    before = counters.count(indexer.THRESHOLD_NAME)
    jax.make_jaxpr(lambda s: dsa._threshold(s, 64))(
        jnp.zeros((1, 256, 256), jnp.float32))
    assert indexer.THRESHOLD_NAME == "indexer_threshold"
    assert counters.count(indexer.THRESHOLD_NAME) == before + 1
    assert get_registry().snapshot()[
        "kernel_invocations.indexer_threshold"] == before + 1
    # the plain form's shapes trace no kernel
    jax.make_jaxpr(lambda s: dsa._threshold(s, 2))(
        jnp.zeros((1, 8, 8), jnp.float32))
    assert counters.count(indexer.THRESHOLD_NAME) == before + 1


def _metric(name):
    return harness.load_json(harness.HERE, "metrics", name + ".json")


@pytest.mark.parametrize("T, loops", [(256, 0), (8, 1)],
                         ids=["kernel", "plain"])
def test_the_lowered_attention_loops_over_the_scores_only_in_the_plain_form(
        T, loops):
    """What ``indexer_time_share.keye`` told the selection by, a
    ``while`` that carries the (batch, T, T) unsigned bits of the
    scores: gone from the lowered op where the kernels tile, still there
    at the plain form's shapes."""
    hlo = jax.jit(lambda *a: dsa.indexed_attention(*a, top_k=T // 4)).lower(
        *_attention_inputs(T, 16)).compiler_ir("hlo").as_hlo_text()
    counted = re.compile(
        _metric("indexer_time_share.keye")["args"]["counted"]["pattern"])
    found = [line for line in map(str.strip, hlo.splitlines())
             if " while(" in line and counted.search(line)]
    assert len(found) == loops, found
    assert ("u32[1,%d,%d]" % (T, T) in hlo) == bool(loops)


# heads of event names as the traced runs have them (an event's name is
# its whole HLO instruction, cut at 120 characters;
# chiprun_out/pr36/final/C.traced.json), and what the threshold kernel's
# events and a fusion that reads its result will read
EVENTS = [
    "%while.110 = (s32[]{:T(128)}, u32[1,8192]{1,0:T(1,128)S(1)}, "
    "u32[1,8192,8192]{2,1,0:T(8,128)}, s32[]{:T(128)}, s32[]{...",
    "%while.103 = (s32[]{:T(128)}, f32[8192,2048]{1,0:T(8,128)}, "
    "f32[65536]{0:T(1024)}, f32[16,2048,768]{2,1,0:T(8,128)}, ...",
    "%convert_reduce_fusion.32 = s32[8192]{0:T(1024)S(1)} fusion("
    "u32[1,8192,8192]{2,1,0:T(8,128)} %get-tuple-element.6301,...",
    "%flash_attention_bwd.6 = (f32[32,8192,128]{2,1,0:T(8,128)}, "
    "f32[32,8192,128]{2,1,0:T(8,128)}, f32[32,8192,128]",
    "%jvp_flash_attention_fwd_.9 = (f32[32,8192,128]{2,1,0:T(8,128)}, "
    "f32[32,16,512]{2,1,0:T(8,128)S(1)}) custom-ca",
    "%indexer_scores_bwd_q_k.8 = (f32[1,16,64,8192]{3,2,1,0:T(8,128)}, "
    "f32[1,16,1,8192]{3,2,1,0:T(1,128)S(1)}, f32[",
    "%indexer_probs.8 = f32[1,8192,8192]{2,1,0:T(8,128)} custom-call("
    "f32[1,32,8192,128]{3,2,1,0:T(8,128)} %pad_maxi",
    "%jvp_indexer_probs_.7 = f32[1,8192,8192]{2,1,0:T(8,128)} custom-call("
    "f32[1,32,8192,128]{3,2,1,0:T(8,128)} %pad",
    "%jvp_indexer_scores_fwd_.9 = f32[1,8192,8192]{2,1,0:T(8,128)} "
    "custom-call(f32[1,8192,64]{2,1,0:T(8,128)S(1)} %",
    "%indexer_scores_fwd.6 = f32[1,8192,8192]{2,1,0:T(8,128)} custom-call("
    "f32[1,8192,64]{2,1,0:T(8,128)S(1)} %copy-",
    "%add_select_fusion.31 = f32[1,8192,8192]{2,1,0:T(8,128)} fusion("
    "f32[1,8192,8192]{2,1,0:T(8,128)} %indexer_scor",
    "%exponential_reduce_fusion.6 = f32[8192]{0:T(1024)S(1)} fusion("
    "f32[1,8192,8192]{2,1,0:T(8,128)} %jvp_indexer_s",
    "%reshape_select_fusion.16 = u32[1,8192]{1,0:T(1,128)S(1)} fusion("
    "pred[8192]{0:T(1024)(128)(4,1)S(1)} %broadcas",
    "%ragged-dot-none.69 = f32[16,2048,768]{2,1,0:T(8,128)} custom-call("
    "s32[1]{0:T(128)} %get-tuple-element.6131, s"]
THRESHOLD_EVENTS = [
    "%indexer_threshold.6 = f32[1,8192]{1,0:T(1,128)} custom-call("
    "f32[1,8192,8192]{2,1,0:T(8,128)} %indexer_scores_fwd.6)",
    "%jvp_indexer_threshold_.7 = f32[1,1,8192]{2,1,0:T(1,128)} custom-call("
    "f32[1,8192,8192]{2,1,0:T(8,128)} %jvp_indexer_s"]
READS_IT = ("%broadcast_compare_fusion.14 = pred[1,8192,8192]{2,1,0} fusion("
            "f32[1,1,8192]{2,1,0:T(1,128)} %indexer_threshold.6, f32[1,8192")


def _recorded_events():
    """Every event name of PR 36's traced window, where the builder's
    records are at hand (they are not committed)."""
    path = os.path.join(harness.ROOT, "chiprun_out", "pr36", "final",
                        "C.traced.json")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [name for name, _ in
                json.loads(f.read().strip().splitlines()[-1])
                ["breakdown"]["device_ops"]]


def test_the_selections_share_reads_the_threshold_kernel_and_nothing_else():
    spec = _metric("selection_time_share.keye")
    assert spec["reader"] == "kernel_time_share"
    assert spec["workloads"] == ["keye-vl.pretrain-seq8192"]
    patterns = [re.compile(p) for p in spec["args"]["patterns"]]
    hit = lambda name: any(p.search(name) for p in patterns)
    assert all(map(hit, THRESHOLD_EVENTS))
    assert not any(map(hit, EVENTS + [READS_IT] + _recorded_events()))
    # the limits chipbench/tests/test_benchmark_json.py holds a name, a
    # unit and a line to
    assert re.match(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$", spec["name"])
    assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", spec["unit"])
    for text in (spec["layer"], spec["args"]["note"]):
        assert 1 <= len(text) <= 200 and "\n" not in text \
            and "\t" not in text
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    declared = [m for m in bench["per_layer"] if m["name"] == spec["name"]]
    assert declared == [{
        key: spec[key] for key in ("name", "unit", "better", "source",
                                   "layer", "moves", "workloads")}]


@pytest.mark.parametrize("name", [
    "indexer_roofline.keye", "indexer_time_share.keye",
    "sparse_attn_roofline.keye", "attn_time_share.keye"])
def test_no_accepted_pattern_prices_the_threshold_kernel(name):
    """The kernel forms no product: a roofline share that matched its
    events would price them as one.  (The time share it belongs to
    cannot be edited by this PR; ``selection_time_share.keye`` reads
    it.)"""
    args = _metric(name)["args"]
    patterns = list(args.get("patterns", [])) \
        + list(args.get("kernels", {}).values())
    assert patterns
    if "counted" in args:
        patterns.append(args["counted"]["pattern"])
    patterns = [re.compile(p) for p in patterns]
    for event in THRESHOLD_EVENTS + [READS_IT]:
        assert not any(p.search(event) for p in patterns), event


def test_mrope_with_three_streams_and_with_one():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 24, 16))
    positions = np.sort(np.random.default_rng(1).integers(
        0, 90, (3, 24)), axis=1).astype(np.int32)
    got = mx.nd.mrope(mx.nd.array(x), mx.nd.array(positions, dtype="int32"),
                      sections=(2, 3, 3), base=1e7).asnumpy()
    want = ref.rotate(jnp.swapaxes(x, 1, 2), jnp.asarray(positions), 1e7,
                      [2, 3, 3])
    np.testing.assert_allclose(got, np.asarray(jnp.swapaxes(want, 1, 2)),
                               rtol=1e-6, atol=1e-6)
    # every stream the index: F.rope, bit for bit
    text = np.broadcast_to(np.arange(24, dtype=np.int32), (3, 24))
    one = mx.nd.mrope(mx.nd.array(x), mx.nd.array(text, dtype="int32"),
                      sections=(2, 3, 3), base=1e7).asnumpy()
    np.testing.assert_array_equal(one, mx.nd.rope(mx.nd.array(x),
                                                  base=1e7).asnumpy())
    with pytest.raises(ValueError, match="do not add up"):
        mx.nd.mrope(mx.nd.array(x), mx.nd.array(text, dtype="int32"),
                    sections=(2, 3), base=1e7)


def test_softmax_routed_experts_match_the_reference():
    """``moe_expert_share(score="softmax")`` with no selection bias
    against the reference's layer, forward and the router's gradient."""
    cfg = dict(CFG, num_experts=4, held_experts_first=4)
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    C, F, E = CFG["hidden_size"], CFG["moe_intermediate_size"], 16
    n = lambda k, *s: 0.3 * jax.random.normal(k, s)
    w = {"router": n(ks[0], E, C), "experts_gate": n(ks[1], 4, C, F),
         "experts_up": n(ks[2], 4, C, F), "experts_down": n(ks[3], 4, F, C)}
    x = jax.random.normal(ks[4], (2, 24, C))

    def program(router):
        return moe.moe_expert_share(
            x, router, jnp.zeros((E,)), w["experts_gate"], w["experts_up"],
            w["experts_down"], held_first=4, top_k=4, score="softmax")[0]

    def reference(router):
        return ref._expert_layer(cfg, dict(w, router=router), "", x,
                                 "highest")

    np.testing.assert_allclose(np.asarray(program(w["router"])),
                               np.asarray(reference(w["router"])),
                               rtol=1e-5, atol=2e-6)
    got, want = (jax.grad(lambda r: jnp.sum(jnp.square(f(r))))(w["router"])
                 for f in (program, reference))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5 * float(jnp.max(jnp.abs(want))))
    with pytest.raises(KeyError):
        moe.moe_expert_share(x, w["router"], jnp.zeros((E,)),
                             w["experts_gate"], w["experts_up"],
                             w["experts_down"], score="tanh")


def test_from_config_builds_the_share_and_refuses_dense_layers():
    net = keye_vl.keye_vl_from_config(
        CFG, held=(CFG["held_experts_first"], CFG["num_experts"]),
        num_experts_total=CFG["num_experts_total"])
    _, ffn = net.decoder_layer(0)
    assert ffn.inner.experts_gate.shape[0] == CFG["num_experts"]
    assert ffn.inner.router.weight.shape[0] == CFG["num_experts_total"]
    assert ffn.inner.shared is None
    with pytest.raises(ValueError, match="dense layers"):
        keye_vl.keye_vl_from_config(dict(CFG, mlp_only_layers=[0]))
    with pytest.raises(ValueError, match="one key head"):
        keye_vl.keye_vl_from_config(dict(CFG, sa_config=dict(
            CFG["sa_config"], indexer_num_kv_heads=2)))
