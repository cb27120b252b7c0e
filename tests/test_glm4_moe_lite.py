"""Glm4MoeLiteLM through SPMDTrainer.step against its plain reference
(chipbench/references/glm4_moe_lite.py: dense attention over rows, the
rotation written out, every held expert on every token, the prediction
module and the two-term loss): both heads' logits, the loss, the first
gradient and three Adam steps, float32, at toy widths on seeded weights.
And the parts one by one: the rotation, the low-rank query, the shares
of the experts, the counters, recomputation per unit."""

import importlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import mxtpu as mx
from mxtpu import gluon
from mxtpu.models import glm4_moe_lite, kimi_linear
from mxtpu.observability.metrics import get_registry
from mxtpu.parallel import SPMDTrainer, make_mesh

from chipbench import harness, models, models_glm

ref = importlib.import_module("chipbench.references.glm4_moe_lite")
CFG = harness.load_json(harness.HERE, "tests", "configs", "glm-tiny.json")
STEPS, LR, B, T = 3, 1e-3, 2, 40
LAMBDA = CFG["mtp_weight"]


def _batch(seed=0):
    rng = np.random.default_rng(seed)
    return tuple(rng.integers(0, CFG["vocab_size"], (B, T), dtype=np.int32)
                 for _ in range(2))


def _toy_trainer(remat=True, optimizer="adam", lr=LR):
    weights = ref.init_weights(CFG, 5)
    train = dict(dtype="float32", optimizer=optimizer, learning_rate=lr,
                 remat=remat)
    trainer, named = models_glm.glm4_moe_lite_trainer(
        CFG, train, weights, ref.selection_bias(CFG), jax.devices()[:1])
    return trainer, named, weights


def _as_nd(*arrays):
    return tuple(mx.nd.array(a, dtype="int32") for a in arrays)


def test_the_toy_configuration_has_every_part():
    assert CFG["first_k_dense_replace"] == 1 < CFG["num_hidden_layers"]
    assert CFG["n_routed_experts"] < CFG["num_experts_total"]   # a share
    assert CFG["v_head_dim"] != CFG["qk_nope_head_dim"] \
        + CFG["qk_rope_head_dim"]
    assert CFG["num_nextn_predict_layers"] == 1
    assert sorted(ref.selection_bias(CFG)) == [1, 2, 3]     # 3: the module


# ------------------------------------------------- both heads' logits

@pytest.fixture(scope="module")
def logits():
    tokens, _ = _batch()
    weights = ref.init_weights(CFG, 5)
    net, _ = models_glm.glm4_moe_lite_lm(CFG, weights,
                                         ref.selection_bias(CFG))
    got = net(*_as_nd(tokens))
    want = ref.logits_of(CFG, {k: jnp.asarray(v)
                               for k, v in weights.items()}, tokens)
    return [g.asnumpy() for g in got], [np.asarray(w) for w in want]


@pytest.mark.parametrize("head", [0, 1], ids=["main", "module"])
def test_logits_of_both_heads_match_the_reference(logits, head):
    got, want = logits[0][head], logits[1][head]
    assert got.shape == (B, T, CFG["vocab_size"])
    # float32 with products at full precision on both sides: what is
    # left is the order of summation (logits are of order 0.5)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)


def test_the_two_heads_differ(logits):
    assert np.abs(logits[0][0] - logits[0][1]).max() > 0.05


# ------------------------------------- the steps through SPMDTrainer

@pytest.fixture(scope="module")
def both():
    """The program's and the reference's readings of the same steps."""
    trainer, named, weights = _toy_trainer()
    tokens, labels = _batch()
    w = {k: jnp.asarray(v) for k, v in weights.items()}
    state = tuple(jax.tree_util.tree_map(jnp.zeros_like, w)
                  for _ in range(2))
    out = {"loss": [], "ref_loss": [], "start": weights}
    for n in range(STEPS):
        out["loss"].append(float(trainer.step(*_as_nd(tokens, labels))._data))
        total, grads = jax.value_and_grad(
            lambda w_: ref.loss_sum(CFG, w_, tokens, labels))(w)
        grads = jax.tree_util.tree_map(lambda g: g / tokens.size, grads)
        if n == 0:
            _, mean = models.trainer_state(trainer, named)
            out["grad"] = {k: np.asarray(v) / (1 - ref.BETA1)
                           for k, v in mean.items()}
            out["ref_grad"] = {k: np.asarray(v) for k, v in grads.items()}
            out["terms"] = [float(t) for t in ref.loss_terms(
                CFG, w, tokens, labels)]
        out["ref_loss"].append(float(total) / tokens.size)
        w, state = ref.adam_step(w, grads, state, LR, n + 1)
    params, _ = models.trainer_state(trainer, named)
    out["params"] = {k: np.asarray(v) for k, v in params.items()}
    out["ref_params"] = {k: np.asarray(v) for k, v in w.items()}
    return out


@pytest.mark.parametrize("step", range(STEPS))
def test_loss_of_every_step_matches_the_reference(both, step):
    # two means of float32 terms summed in another order
    assert both["loss"][step] == pytest.approx(both["ref_loss"][step],
                                               rel=2e-6)


def test_the_loss_is_the_two_terms_each_a_mean_over_its_positions(both):
    main, mtp = both["terms"]
    assert both["ref_loss"][0] == pytest.approx(
        main / (B * T) + LAMBDA * mtp / (B * (T - 1)), rel=1e-6)
    assert mtp > 0


LEAVES = sorted(ref.weight_shapes(CFG))


@pytest.mark.parametrize("leaf", LEAVES)
def test_first_gradient_of_every_leaf_matches_the_reference(both, leaf):
    """The embedding's and the head's are the sums of their two uses
    (the model's and the module's): one leaf each on both sides.  The
    tolerance is Kimi-Linear's: float32 sums in another order, measured
    against the leaf's largest entry."""
    got, want = both["grad"][leaf], both["ref_grad"][leaf]
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-5 * np.abs(want).max())


def test_every_leaf_has_a_gradient_and_the_reference_names_them_all(both):
    assert set(both["grad"]) == set(both["ref_grad"]) == set(both["start"])
    assert [k for k, g in both["ref_grad"].items() if not g.any()] == []


@pytest.mark.parametrize("leaf", ["embed", "lm_head"])
def test_the_shared_leaves_take_gradient_from_both_uses(both, leaf):
    """Without the module's term (the fault ``mtp_left_out``) the
    reference's gradient of the shared leaf is another, and no leaf of
    the module has one."""
    tokens, labels = _batch()
    w = {k: jnp.asarray(v) for k, v in both["start"].items()}
    alone = jax.grad(lambda w_: ref.loss_sum(
        dict(CFG, fault="mtp_left_out"), w_, tokens, labels))(w)
    one_use = np.asarray(alone[leaf]) / tokens.size
    gap = np.abs(both["grad"][leaf] - one_use).max()
    assert gap > 0.05 * np.abs(one_use).max()
    assert not np.asarray(alone["mtp.eh_proj"]).any()


def test_three_adam_steps_land_where_the_references_do(both):
    # Adam's first steps move every entry by about the rate whatever its
    # gradient's size: 2% of the largest move, as Kimi-Linear's test
    for name, want in both["ref_params"].items():
        moved = np.abs(want - both["start"][name]).max()
        np.testing.assert_allclose(both["params"][name], want, rtol=0,
                                   atol=0.02 * moved + 1e-7, err_msg=name)


# --------------------------------------------------------- the counters

def test_the_mtp_counters_move_with_the_steps():
    """Positions that entered the module's loss and the two terms' sums
    since the start, under ``mtp.*`` in the registry (summed over the
    live modules)."""
    trainer, _, _ = _toy_trainer()
    seen = []
    for n in range(2):
        trainer.step(*_as_nd(*_batch(n)))
        seen.append(glm4_moe_lite.mtp_counts())
    snap = get_registry().snapshot()
    for name in ("positions", "loss_sum", "main_loss_sum"):
        assert seen[1][name] > seen[0][name] > 0
        assert snap["mtp." + name] == pytest.approx(seen[1][name])
    assert seen[1]["positions"] - seen[0]["positions"] == B * (T - 1)


def test_the_counters_hold_the_terms_sums_and_nothing_reads_them():
    trainer, _, weights = _toy_trainer()
    net = trainer._block
    tokens, labels = _batch(1)
    loss = float(trainer.step(*_as_nd(tokens, labels))._data)
    positions, mtp_sum, main_sum = (
        float(np.asarray(getattr(net.mtp, name).data()._data)[0])
        for name in ("positions", "loss_sum", "main_loss_sum"))
    assert positions == B * (T - 1)
    assert main_sum / (B * T) + LAMBDA * mtp_sum / positions \
        == pytest.approx(loss, rel=1e-5)
    main, mtp = ref.loss_terms(CFG, {k: jnp.asarray(v) for k, v in
                                     weights.items()}, tokens, labels)
    assert (main_sum, mtp_sum) == pytest.approx((float(main), float(mtp)),
                                                rel=1e-5)
    # frozen: no gradient, no optimizer state, handed back with the step
    assert all(getattr(net.mtp, name).grad_req == "null"
               for name in ("positions", "loss_sum", "main_loss_sum"))


def test_expert_loads_see_this_models_layers_the_modules_among_them():
    trainer, _, _ = _toy_trainer()
    tokens, labels = _batch(2)
    trainer.step(*_as_nd(tokens, labels))
    net = trainer._block
    loads = kimi_linear.expert_loads()
    mine = [net.decoder_layer(i)[1].inner for i in (1, 2)] \
        + [net.mtp.ffn.inner]
    for layer in mine:
        load = loads[layer.prefix.rstrip("_")]
        assert sum(load["held"]) + load["elsewhere"] \
            == B * T * CFG["num_experts_per_tok"]
    snap = get_registry().snapshot()
    name = net.mtp.ffn.inner.prefix.rstrip("_")
    assert "moe.%s.held_sum.0" % name in snap
    assert "moe.%s.elsewhere_sum" % name in snap


# ---------------------------------------------------------- the rotation

def _attention_pair(**cfg_more):
    """(the program's mixer holding seeded weights, the same weights by
    the reference's names, the configuration)."""
    cfg = dict(CFG, **cfg_more)
    shapes = ref._attention_shapes(cfg, "a.")
    rng = np.random.default_rng(3)
    w = {k: (0.2 * rng.standard_normal(s)).astype("float32")
         if kind == "matrix" else
         (1 + 0.1 * rng.standard_normal(s)).astype("float32")
         for k, (s, kind) in shapes.items()}
    mixer = kimi_linear.LatentAttention(
        cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"],
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
        cfg["rms_norm_eps"], q_rank=cfg["q_lora_rank"],
        rope_base=cfg["rope_theta"])
    mixer.initialize()
    for block, name in ((mixer.q_a_proj, "q_a"), (mixer.q_norm, "q_norm"),
                        (mixer.q_b_proj, "q_b"), (mixer.dkv_proj, "dkv"),
                        (mixer.kv_norm, "kv_norm"), (mixer.ukv_proj, "ukv"),
                        (mixer.out_proj, "out")):
        block.weight.set_data(mx.nd.array(w["a." + name]))
    return mixer, {k: jnp.asarray(v) for k, v in w.items()}, cfg


@pytest.mark.parametrize("theta", [1e6, 1e4])
def test_latent_attention_rotates_as_the_reference_does(theta):
    """Positions 0..T-1 on the query's rotary columns and on the one key
    part all heads share, at the configuration's theta."""
    mixer, w, cfg = _attention_pair(rope_theta=theta)
    x = np.random.default_rng(4).standard_normal(
        (B, T, cfg["hidden_size"])).astype("float32")
    got = mixer(mx.nd.array(x)).asnumpy()
    want = np.asarray(ref._attention(cfg, w, "a.", jnp.asarray(x),
                                     "highest"))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)
    # the comparison sees the rotation, and theta
    for other in (dict(cfg, fault="rope_left_out"),
                  dict(cfg, rope_theta=theta * 10)):
        off = np.asarray(ref._attention(other, w, "a.", jnp.asarray(x),
                                        "highest"))
        assert np.abs(off - want).max() > 100 * np.abs(got - want).max()


def test_the_rotation_depends_on_the_distance_alone():
    """q_t . k_s after the rotation is a function of t - s: shifting both
    positions leaves the product as it was."""
    rng = np.random.default_rng(5)
    q, k = (jnp.asarray(rng.standard_normal((1, 12, 8)).astype("float32"))
            for _ in range(2))
    rq, rk = ref.rotate(q, 1e6), ref.rotate(k, 1e6)
    near = jnp.einsum("btd,bsd->bts", rq, rk)
    # the same vectors placed three positions later
    pad = jnp.zeros((1, 3, 8), jnp.float32)
    later = jnp.einsum(
        "btd,bsd->bts", ref.rotate(jnp.concatenate([pad, q], 1), 1e6)[:, 3:],
        ref.rotate(jnp.concatenate([pad, k], 1), 1e6)[:, 3:])
    np.testing.assert_allclose(np.asarray(near), np.asarray(later),
                               rtol=1e-5, atol=1e-5)
    # and it is the program's F.rope
    np.testing.assert_allclose(
        mx.nd.rope(mx.nd.array(np.asarray(q)), base=1e6).asnumpy(),
        np.asarray(rq), rtol=1e-6, atol=1e-6)


def test_the_query_is_low_rank_with_a_norm_of_its_own():
    mixer, _, cfg = _attention_pair()
    heads = cfg["num_attention_heads"] * (cfg["qk_nope_head_dim"]
                                          + cfg["qk_rope_head_dim"])
    assert mixer.q_a_proj.weight.shape == (cfg["q_lora_rank"],
                                           cfg["hidden_size"])
    assert mixer.q_norm.weight.shape == (cfg["q_lora_rank"],)
    assert mixer.q_b_proj.weight.shape == (heads, cfg["q_lora_rank"])
    assert not hasattr(mixer, "q_proj")
    # and the full-rank, position-free form is as it was
    plain = kimi_linear.LatentAttention(48, 2, 16, 16, 8, 24)
    assert plain.q_proj.weight.shape == (heads, 48)
    assert not hasattr(plain, "q_a_proj")


# ------------------------------------------------------ the shares add up

def test_the_shares_add_up_to_the_uncut_layer():
    """8 experts in 4 shares of 2, each an ``ExpertShare`` as the model
    builds it (router over all 8, top-4, renormalised x 1.8, one shared
    expert): the routed parts of all the shares plus the shared expert
    counted once are the reference's layer holding all 8."""
    E, held, C, F = 8, 2, CFG["hidden_size"], CFG["moe_intermediate_size"]
    cfg = dict(CFG, n_routed_experts=E, num_experts_total=E,
               held_experts_first=0)
    rng = np.random.default_rng(6)
    n = lambda *s: (0.2 * rng.standard_normal(s)).astype("float32")
    w = {"router": n(E, C), "experts_gate": n(E, C, F),
         "experts_up": n(E, C, F), "experts_down": n(E, F, C),
         "shared_gate": n(F, C), "shared_up": n(F, C),
         "shared_down": n(C, F)}
    bias = (0.01 * rng.standard_normal(E)).astype("float32")
    x = rng.standard_normal((B, T, C)).astype("float32")
    want = np.asarray(ref.base._expert_layer(
        ref._as_kimi_linear(cfg), {k: jnp.asarray(v) for k, v in w.items()},
        "", jnp.asarray(x), jnp.asarray(bias), "highest"))

    total = shared = None
    for first in range(0, E, held):
        layer = kimi_linear.ExpertShare(
            C, F, E, CFG["num_experts_per_tok"], held=(first, held),
            routed_scale=CFG["routed_scaling_factor"],
            renormalize=CFG["norm_topk_prob"],
            num_shared=CFG["n_shared_experts"])
        layer.initialize()
        sl = slice(first, first + held)
        layer.router.weight.set_data(mx.nd.array(w["router"]))
        layer.select_bias.set_data(mx.nd.array(bias))
        for name in ("gate", "up", "down"):
            getattr(layer, "experts_" + name).set_data(
                mx.nd.array(w["experts_" + name][sl]))
            getattr(layer.shared, name + "_proj").weight.set_data(
                mx.nd.array(w["shared_" + name]))
        shared = layer.shared(mx.nd.array(x)).asnumpy()
        y = layer(mx.nd.array(x)).asnumpy() - shared      # the routed part
        total = y if total is None else total + y
        load = np.asarray(layer.load.data()._data)
        assert load.sum() == B * T * CFG["num_experts_per_tok"]
    np.testing.assert_allclose(total + shared, want, rtol=1e-4, atol=1e-5)


# ------------------------------------------------- recomputation per unit

@pytest.fixture(scope="module")
def with_and_without():
    tokens, labels = _as_nd(*_batch(3))
    out = {}
    for remat in (False, True):
        trainer, named, _ = _toy_trainer(remat=remat, optimizer="sgd",
                                         lr=0.1)
        jitted, args = trainer.step_program(tokens, labels)
        text = str(jax.make_jaxpr(jitted)(*args))
        snap = get_registry().snapshot()
        loss = float(trainer.step(tokens, labels)._data)
        out[remat] = dict(loss=loss, checkpoints=text.count("remat2"),
                          kept=snap["remat.kept_outputs"],
                          params={k: np.asarray(p.data()._data)
                                  for k, p in named.items()})
    return out


def test_recomputation_gives_the_same_step_on_the_cpu(with_and_without):
    plain, remat = with_and_without[False], with_and_without[True]
    assert remat["loss"] == plain["loss"]
    for name, a in plain["params"].items():
        np.testing.assert_array_equal(a, remat["params"][name],
                                      err_msg=name)


def test_every_half_layer_is_a_unit_the_modules_two_among_them(
        with_and_without):
    plain, remat = with_and_without[False], with_and_without[True]
    halves = 2 * (CFG["num_hidden_layers"] + 1)
    # (the two heads' cross-entropies form their blocks of rows again in
    # the backward pass with or without the trainer's recomputation)
    assert remat["checkpoints"] - plain["checkpoints"] >= halves
    # flash's output and logsumexp of every attention call are kept
    assert remat["kept"] == 2 * (CFG["num_hidden_layers"] + 1)


def test_the_trainer_gives_up_the_eager_gradient_buffers():
    """One more copy of the model on the device that the compiled step
    never reads (2.8 GB at the cell's size); ``grad()`` makes a buffer
    anew for whoever asks."""
    trainer, named, _ = _toy_trainer()
    param = named["layer1.experts_gate"]
    assert param._grad is not None          # as ``initialize`` left it
    trainer.step(*_as_nd(*_batch()))
    assert all(p._grad is None and p.data()._grad is None
               for p in named.values())
    assert param.grad_req == "write"
    assert param.grad().shape == param.shape
    assert param.data()._grad is not None


# ------------------------------------------------------------ the builder

def test_from_config_builds_the_share_and_refuses_group_limits():
    net = glm4_moe_lite.glm4_moe_lite_from_config(
        CFG, held=(4, 4), num_experts_total=16)
    assert net.num_layers == 3 and net.num_dense == 1
    assert isinstance(net.decoder_layer(0)[1].inner, kimi_linear.GatedMLP)
    assert isinstance(net.decoder_layer(1)[1].inner,
                      kimi_linear.ExpertShare)
    assert net.mtp.eh_proj.weight.shape == (48, 96)
    with pytest.raises(ValueError, match="group"):
        glm4_moe_lite.glm4_moe_lite_from_config(dict(CFG, n_group=2))
    plain = glm4_moe_lite.glm4_moe_lite_from_config(
        dict(CFG, num_nextn_predict_layers=0))
    assert plain.mtp is None
    with pytest.raises(ValueError, match="no prediction module"):
        plain.loss()


def test_a_model_without_the_module_returns_one_set_of_logits():
    net = glm4_moe_lite.glm4_moe_lite_from_config(
        dict(CFG, num_nextn_predict_layers=0), held=(4, 4),
        num_experts_total=16)
    net.initialize(mx.init.Xavier())
    out = net(*_as_nd(_batch()[0]))
    assert out.shape == (B, T, CFG["vocab_size"])


@pytest.mark.parametrize("block_rows", [1024, 16, 7],
                         ids=["one_block", "five_blocks", "rows_of_5"])
def test_linear_cross_entropy_is_the_cross_entropy_of_the_logits(block_rows):
    """Value and both gradients against log-softmax and pick of the
    whole logits, whatever the blocks (80 rows: 80, 16 or 5 at a time)."""
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((B, T, 12)).astype("float32"))
    w = jnp.asarray(rng.standard_normal((31, 12)).astype("float32"))
    y = jnp.asarray(rng.integers(0, 31, (B, T)).astype(np.int32))
    from mxtpu.ops.nn import linear_cross_entropy

    def whole(x, w):
        logp = jax.nn.log_softmax(jnp.einsum(
            "btc,vc->btv", x, w, precision=jax.lax.Precision.HIGHEST), -1)
        return -jnp.take_along_axis(logp, y[..., None], -1)[..., 0]

    got = linear_cross_entropy(x, w, y, block_rows=block_rows)
    assert got.shape == (B, T)
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole(x, w)),
                               rtol=1e-5, atol=1e-6)
    weigh = jnp.asarray(rng.standard_normal((B, T)).astype("float32"))
    grads = jax.grad(lambda x, w: (linear_cross_entropy(
        x, w, y, block_rows=block_rows) * weigh).sum(), argnums=(0, 1))(x, w)
    wants = jax.grad(lambda x, w: (whole(x, w) * weigh).sum(),
                     argnums=(0, 1))(x, w)
    for g, want in zip(grads, wants):
        np.testing.assert_allclose(np.asarray(g), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)


def test_both_forms_of_the_model_take_the_same_step():
    """Logits out and a softmax cross-entropy of each, or the head's
    inputs out and each cross-entropy through the head in blocks of
    rows: the same loss and the same first gradient (float32 sums in
    another order)."""
    from mxtpu.parallel import SPMDTrainer

    weights = ref.init_weights(CFG, 5)
    tokens, labels = _as_nd(*_batch(4))
    read = {}
    for form in (True, False):
        net, named = models_glm.glm4_moe_lite_lm(
            CFG, weights, ref.selection_bias(CFG), return_logits=form)
        trainer = SPMDTrainer(
            net, net.loss(LAMBDA), "adam", models.one_chip_mesh(
                jax.devices()[:1]),
            optimizer_params={"learning_rate": LR}, remat=True)
        loss = float(trainer.step(tokens, labels)._data)
        _, mean = models.trainer_state(trainer, named)
        read[form] = loss, {k: np.asarray(v) for k, v in mean.items()}
    assert read[True][0] == pytest.approx(read[False][0], rel=2e-6)
    for name, want in read[True][1].items():
        np.testing.assert_allclose(read[False][1][name], want, rtol=2e-4,
                                   atol=2e-5 * np.abs(want).max(),
                                   err_msg=name)


def test_multi_token_loss_leaves_the_modules_last_position_out():
    rng = np.random.default_rng(7)
    logits, mtp = (rng.standard_normal((B, T, 11)).astype("float32")
                   for _ in range(2))
    labels = rng.integers(0, 11, (B, T)).astype(np.int32)
    seen = []
    loss = gluon.loss.MultiTokenLoss(0.3, record=lambda *a: seen.append(a))
    got = loss((mx.nd.array(logits), mx.nd.array(mtp)),
               mx.nd.array(labels, dtype="int32")).asnumpy()

    def ce(z, y):
        z = z - z.max(-1, keepdims=True)
        logp = z - np.log(np.exp(z).sum(-1, keepdims=True))
        return -np.take_along_axis(logp, y[..., None], -1)[..., 0]

    main, second = ce(logits, labels), ce(mtp[:, :-1], labels[:, 1:])
    np.testing.assert_allclose(got, main.mean(1) + 0.3 * second.mean(1),
                               rtol=1e-5)
    changed = mtp.copy()
    changed[:, -1] += 5.0                   # the last position is in no loss
    again = loss((mx.nd.array(logits), mx.nd.array(changed)),
                 mx.nd.array(labels, dtype="int32")).asnumpy()
    np.testing.assert_array_equal(got, again)
    positions, main_sum, mtp_sum = seen[0]
    assert int(positions.asnumpy()[0]) == B * (T - 1)
    assert float(main_sum.asnumpy()) == pytest.approx(main.sum(), rel=1e-5)
    assert float(mtp_sum.asnumpy()) == pytest.approx(second.sum(), rel=1e-5)
