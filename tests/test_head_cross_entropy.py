"""The three losses that take a head's cross-entropy through
``F.linear_cross_entropy`` — ``MultiTokenLoss`` (GLM-4.7-Flash, two
heads on one weight), ``IndexedAttentionLoss`` (Keye-VL) and
``NextTokenLoss`` (LFM2) — in ``SPMDTrainer``'s step at toy widths: the
cotangent that reaches the op is the same on every row with a target,
so the backward pass scales the gradient the forward formed; the step
is the whole logits' step; the trace counts the heads whose forward
formed the gradient, and BERT's and Kimi-Linear's steps, whose losses
take whole logits, count none."""

import importlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import mxtpu as mx
from mxtpu import base
from mxtpu.ops.pallas import counters
from mxtpu.parallel import SPMDTrainer

from chipbench import harness, models, models_glm, models_keye, \
    models_lfm2, models_lm

B, T, LR = 2, 40, 1e-3
FUSED = "linear_cross_entropy_fused"


def _lm(model, cfg, weights, return_logits):
    """(net, named, loss) as the model's runner builds them, in either
    form: logits out, or the head's inputs out."""
    ref = importlib.import_module("chipbench.references." + model)
    if model == "keye_vl":
        net, named = models_keye.keye_vl_lm(
            cfg, weights, return_logits=return_logits)
        return net, named, net.loss(cfg["index_loss_weight"])
    build = {"glm4_moe_lite": models_glm.glm4_moe_lite_lm,
             "lfm2_moe": models_lfm2.lfm2_moe_lm}[model]
    net, named = build(cfg, weights, ref.selection_bias(cfg),
                       return_logits=return_logits)
    loss = net.loss(cfg["mtp_weight"]) if model == "glm4_moe_lite" \
        else net.loss()
    return net, named, loss


def _tapped(seen):
    """The op's registry entry with a tap on its output: the cotangent
    each call's per-row loss receives reaches ``seen`` with the call's
    labels."""
    spec = base.get_op("linear_cross_entropy")

    @jax.custom_vjp
    def tap(nll, label):
        return nll

    def tap_bwd(label, g):
        jax.debug.callback(
            lambda g, y: seen.append((np.asarray(g), np.asarray(y))),
            g, label)
        return g, None

    tap.defvjp(lambda nll, label: (nll, label), tap_bwd)

    def fn(data, weight, label, **kwargs):
        return tap(spec.fn(data, weight, label, **kwargs),
                   label.astype(jnp.int32))

    return spec._replace(fn=fn)


@pytest.fixture(scope="module", params=[
    ("glm4_moe_lite", "glm-tiny.json", 2),
    ("keye_vl", "keye-tiny.json", 1),
    ("lfm2_moe", "lfm2-tiny.json", 1)], ids=["glm", "keye", "lfm2"])
def stepped(request):
    """One step of each form: (heads, the loss and first Adam moment of
    each form, the cotangents the tap saw, the fused counter's rise)."""
    model, config, heads = request.param
    cfg = harness.load_json(harness.HERE, "tests", "configs", config)
    ref = importlib.import_module("chipbench.references." + model)
    weights = ref.init_weights(cfg, 5)
    rng = np.random.default_rng(0)
    tokens, labels = (mx.nd.array(rng.integers(
        0, cfg["vocab_size"], (B, T), dtype=np.int32), dtype="int32")
        for _ in range(2))
    read, seen, rise = {}, [], None
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(base._OP_REGISTRY, "linear_cross_entropy", _tapped(seen))
        for form in (True, False):
            net, named, loss_fn = _lm(model, cfg, weights, form)
            trainer = SPMDTrainer(
                net, loss_fn, "adam", models.one_chip_mesh(
                    jax.devices()[:1]),
                optimizer_params={"learning_rate": LR}, remat=True)
            before = counters.count(FUSED)
            loss = float(trainer.step(tokens, labels)._data)
            jax.effects_barrier()
            if not form:
                rise = counters.count(FUSED) - before
            _, mean = models.trainer_state(trainer, named)
            read[form] = loss, {k: np.asarray(v) for k, v in mean.items()}
    return heads, read, seen, rise


def test_the_cotangent_is_one_value_on_the_rows_with_a_target(stepped):
    heads, _, seen, _ = stepped
    assert len(seen) == heads
    for g, label in seen:
        assert g.shape == label.shape == (B, T)
        assert (label >= 0).any()
        assert np.unique(g[label >= 0]).size == 1, np.unique(g)


def test_both_forms_take_the_same_step(stepped):
    """Logits out and a softmax cross-entropy of each, or the head's
    inputs out and each cross-entropy through the head in blocks of
    rows: the same loss and the same first gradient (float32 sums in
    another order)."""
    _, read, _, _ = stepped
    assert read[True][0] == pytest.approx(read[False][0], rel=2e-6)
    for name, want in read[True][1].items():
        np.testing.assert_allclose(read[False][1][name], want, rtol=2e-4,
                                   atol=2e-5 * np.abs(want).max(),
                                   err_msg=name)


def test_the_trace_counts_each_head_that_formed_its_gradient(stepped):
    heads, _, _, rise = stepped
    assert rise == heads


@pytest.mark.parametrize("model", ["bert", "kimi_linear"])
def test_whole_logits_steps_count_no_fused_head(model):
    config = {"bert": "bert-tiny.json",
              "kimi_linear": "kimi-linear-tiny.json"}[model]
    cfg = harness.load_json(harness.HERE, "tests", "configs", config)
    ref = importlib.import_module("chipbench.references." + model)
    seq = 128 if model == "bert" else T
    tokens = mx.nd.array(np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (B, seq), dtype=np.int32), dtype="int32")
    train = dict(dtype="float32", optimizer="adam", learning_rate=LR,
                 remat=True, seq=seq)
    weights, devices = ref.init_weights(cfg, 5), jax.devices()[:1]
    if model == "bert":
        trainer, _ = models.bert_trainer(cfg, train, weights, devices)
    else:
        trainer, _ = models_lm.kimi_linear_trainer(
            cfg, train, weights, ref.selection_bias(cfg), devices)
    before = counters.count(FUSED)
    trainer.lower_step(tokens, tokens)
    assert counters.count(FUSED) == before
