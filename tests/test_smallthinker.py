"""SmallThinkerLM through SPMDTrainer.step against its plain reference
(chipbench/references/smallthinker.py: dense attention a block of query
rows at a time over every key, masked by the window where the layer is
windowed, a key head for each group of query heads, every held expert on
every token, the router on the attention's normed input): logits, loss,
every leaf's first gradient and three Adam steps, float32, at toy widths
on seeded weights, with and without recomputation.  And the parts one by
one: what the layouts build, the router before attention, the ReLU
experts and their custom backward, the shares of the expert layer, what
a windowed layer sees."""

import importlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import mxtpu as mx
from mxtpu.models import smallthinker
from mxtpu.models.parts import ExpertShare, GroupedQueryAttention
from mxtpu.ops import moe

from chipbench import harness, models, models_smallthinker

ref = importlib.import_module("chipbench.references.smallthinker")
CFG = harness.load_json(harness.HERE, "tests", "configs",
                        "smallthinker-tiny.json")
STEPS, LR, B, T = 3, 1e-3, 2, 40


def _batch(seed=0):
    rng = np.random.default_rng(seed)
    return tuple(rng.integers(0, CFG["vocab_size"], (B, T), dtype=np.int32)
                 for _ in range(2))


def _as_nd(*arrays):
    return tuple(mx.nd.array(a, dtype="int32") for a in arrays)


def _weights(seed=5):
    weights = ref.init_weights(CFG, seed)
    return weights, {k: jnp.asarray(v) for k, v in weights.items()}


def test_the_toy_configuration_has_every_part():
    assert CFG["rope_layout"] == CFG["sliding_window_layout"] == [0, 1, 1, 1]
    assert CFG["sliding_window_size"] < T          # the window binds
    assert CFG["moe_num_primary_experts"] < CFG["num_experts_total"]
    assert CFG["held_experts_first"] > 0
    assert CFG["num_key_value_heads"] < CFG["num_attention_heads"]
    assert CFG["head_dim"] * CFG["num_attention_heads"] != CFG["hidden_size"]


# -------------------------------------------------- the model, forward

@pytest.fixture(scope="module")
def forward():
    tokens, _ = _batch()
    weights, w = _weights()
    net, _ = models_smallthinker.smallthinker_lm(CFG, weights)
    logits = net(*_as_nd(tokens))
    return logits.asnumpy(), np.asarray(ref.logits_of(CFG, w, tokens))


def test_logits_match_the_reference(forward):
    got, want = forward
    assert got.shape == (B, T, CFG["vocab_size"])
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


@pytest.mark.parametrize("layer", [0, 1, 3], ids=["global", "window",
                                                  "last_window"])
def test_a_windowed_layer_sees_no_key_behind_its_window(layer):
    """A change to the first position moves every later query's output
    in the global layer, and in a windowed one only the queries whose
    window holds it: the rest stay where they were, to the bit."""
    weights, _ = _weights()
    net, _ = models_smallthinker.smallthinker_lm(CFG, weights)
    attn, window = net.layers[layer].attn, CFG["sliding_window_size"]
    rng = np.random.default_rng(3)
    x = rng.standard_normal((B, T, CFG["hidden_size"])).astype(np.float32)
    moved = x.copy()
    moved[:, 0] += 1.0
    out, out_moved = (attn(mx.nd.array(a)).asnumpy() for a in (x, moved))
    change = np.abs(out_moved - out).max(axis=(0, 2))
    assert (change[:window] > 1e-6).all()
    if CFG["sliding_window_layout"][layer]:
        np.testing.assert_array_equal(out_moved[:, window:], out[:, window:])
    else:
        assert (change[window:] > 1e-6).all()


# ------------------------------------------ the model through the trainer

@pytest.fixture(scope="module", params=[True, False],
                ids=["remat", "no-remat"])
def trained(request):
    """Three steps of the trainer and of the reference on the same
    batches: losses, the first gradient's leaf norms, the weights."""
    weights, w = _weights()
    train = dict(dtype="float32", optimizer="adam", learning_rate=LR,
                 remat=request.param)
    trainer, named = models_smallthinker.smallthinker_trainer(
        CFG, train, weights, ref.selection_bias(CFG), jax.devices()[:1])
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


def test_the_loss_matches_the_reference(trained):
    losses, ref_losses, *_ = trained
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-6)


@pytest.mark.parametrize("name", sorted(ref.weight_shapes(CFG)))
def test_every_leafs_first_gradient_matches_the_reference(trained, name):
    got, want = trained[2]
    assert want[name] > 0
    np.testing.assert_allclose(got[name], want[name], rtol=2e-5)


def test_three_adam_steps_match_the_reference(trained):
    _, _, _, params, w, start = trained
    for name in sorted(w):
        moved = np.abs(w[name] - start[name]).max()
        assert moved > 0, name
        np.testing.assert_allclose(params[name], w[name], rtol=0,
                                   atol=0.02 * moved, err_msg=name)


# ------------------------------------------------------------ the parts

@pytest.mark.parametrize("rope,windows", [
    ([0, 1, 1, 1], [0, 1, 1, 1]), ([1, 0, 1], [0, 0, 1])])
def test_the_layouts_build_the_right_layer_kinds(rope, windows):
    net = smallthinker.smallthinker_from_config(dict(
        CFG, rope_layout=rope, sliding_window_layout=windows,
        num_hidden_layers=len(rope)))
    assert len(net.layers) == len(rope)
    for layer, r, w in zip(net.layers, rope, windows):
        attn = layer.attn
        assert type(attn) is GroupedQueryAttention
        assert attn._window == (CFG["sliding_window_size"] if w else None)
        assert attn._base == (CFG["rope_theta"] if r else None)
        assert attn.q_norm is None and attn.k_norm is None
        assert isinstance(layer.moe, ExpertShare)
        assert (layer.moe._score, layer.moe._act) == ("softmax", "relu")
        assert layer.moe.shared is None
    assert not [n for n in net.collect_params() if "norm" in n
                and "_attn_q" in n]


@pytest.mark.parametrize("change,message", [
    (dict(rope_layout=[0, 1]), "num_hidden_layers"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings")])
def test_a_config_the_model_cannot_be_is_refused(change, message):
    with pytest.raises(ValueError, match=message):
        smallthinker.smallthinker_from_config(dict(CFG, **change))


def _one_layer():
    weights, _ = _weights()
    net, named = models_smallthinker.smallthinker_lm(CFG, weights)
    return net, named, net.layers[1]


def test_the_router_reads_the_input_before_attention():
    """The layer's choice of experts stays where it was when the
    attention's weights move, and moves when the router's input does."""
    net, named, layer = _one_layer()
    rng = np.random.default_rng(1)
    x = mx.nd.array(rng.standard_normal((B, T, CFG["hidden_size"]))
                    .astype(np.float32))
    out = layer(x).asnumpy()
    load = layer.moe.load.data().asnumpy()
    for name in ("q", "k", "v", "out"):
        p = named["layer1." + name]
        p.set_data(mx.nd.array(p.data().asnumpy() + rng.standard_normal(
            p.shape).astype(np.float32)))
    moved = layer(x).asnumpy()
    assert np.abs(moved - out).max() > 1e-2
    np.testing.assert_array_equal(layer.moe.load.data().asnumpy(), load)
    gain = named["layer1.attn_norm"]
    gain.set_data(mx.nd.array(rng.standard_normal(gain.shape)
                              .astype(np.float32)))
    layer(x)
    assert (layer.moe.load.data().asnumpy() != load).any()


def _expert_inputs(E=16, C=24, F=12, S=40, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = lambda k, *shape: 0.3 * jax.random.normal(k, shape)  # noqa: E731
    return ({"router": n(ks[0], E, C), "experts_gate": n(ks[1], E, C, F),
             "experts_up": n(ks[2], E, C, F),
             "experts_down": n(ks[3], E, F, C)},
            jax.random.normal(ks[4], (2, S, C)),
            jax.random.normal(ks[5], (2, S, C)))


@pytest.mark.parametrize("act", ["relu", "silu"])
def test_the_experts_activation_and_its_backward(act):
    """``act`` in the forward and in the custom backward: the share
    against the same layer written out densely, output and every
    input's gradient; ReLU is not SiLU."""
    w, b, a = _expert_inputs()
    E, k = w["router"].shape[0], 4
    fn = moe.ACTIVATIONS[act]

    def program(b, a, gate):
        return moe.moe_expert_share(
            b, w["router"], jnp.zeros((E,)), gate, w["experts_up"],
            w["experts_down"], a, top_k=k, score="softmax", act=act)[0]

    def dense(b, a, gate):
        scores = jax.nn.softmax(a @ w["router"].T)
        top, chosen = jax.lax.top_k(scores, k)
        top = top / top.sum(-1, keepdims=True)
        share = jnp.stack([jnp.sum(jnp.where(chosen == e, top, 0.0), -1)
                           for e in range(E)])
        hp = jax.lax.Precision.HIGHEST
        h = fn(jnp.einsum("btc,ecf->ebtf", b, gate, precision=hp)) \
            * jnp.einsum("btc,ecf->ebtf", b, w["experts_up"], precision=hp)
        return jnp.einsum("ebtf,efc->btc", h * share[..., None],
                          w["experts_down"], precision=hp)

    args = (b, a, w["experts_gate"])
    np.testing.assert_allclose(np.asarray(program(*args)),
                               np.asarray(dense(*args)), rtol=1e-5,
                               atol=1e-6)
    loss = lambda f: lambda *x: jnp.sum(jnp.sin(f(*x)))  # noqa: E731
    for got, want in zip(jax.grad(loss(program), argnums=(0, 1, 2))(*args),
                         jax.grad(loss(dense), argnums=(0, 1, 2))(*args)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)
    other = "silu" if act == "relu" else "relu"
    assert np.abs(np.asarray(program(*args)) - np.asarray(
        moe.moe_expert_share(b, w["router"], jnp.zeros((E,)),
                             w["experts_gate"], w["experts_up"],
                             w["experts_down"], a, top_k=k, score="softmax",
                             act=other)[0])).max() > 1e-3


@pytest.mark.parametrize("tile", [4096, 48], ids=["wide_tiles",
                                                  "narrow_tiles"])
def test_eight_shares_add_up_to_the_uncut_reference_layer(monkeypatch,
                                                          tile):
    """8 shares of 2 of 16 experts, each routed over all 16 from the
    attention's input: their outputs sum to what the uncut reference
    gives for the whole layer, and their loads to every pair."""
    monkeypatch.setattr(moe, "PAIRS_PER_TILE", tile)
    w, b, a = _expert_inputs()
    E, k = 16, 6
    uncut = dict(CFG, num_experts_total=E, moe_num_primary_experts=E,
                 held_experts_first=0, moe_num_active_primary_experts=k)
    want = ref.expert_layer(uncut, w, "", b, a, "highest")
    summed, pairs = 0.0, 0
    for first in range(0, E, E // 8):
        sl = slice(first, first + E // 8)
        y, load = moe.moe_expert_share(
            b, w["router"], jnp.zeros((E,)), w["experts_gate"][sl],
            w["experts_up"][sl], w["experts_down"][sl], a, held_first=first,
            top_k=k, score="softmax", act="relu")
        summed = summed + y
        pairs += int(np.asarray(load)[:-1].sum())
    np.testing.assert_allclose(np.asarray(summed), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    assert pairs == b.shape[0] * b.shape[1] * k
    # routed from the experts' own input instead, the layer is another
    assert np.abs(np.asarray(ref.expert_layer(
        uncut, w, "", b, b, "highest")) - np.asarray(want)).max() > 1e-3
