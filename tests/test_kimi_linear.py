"""KimiLinearLM through SPMDTrainer.step against its plain reference
(chipbench/references/kimi_linear.py: the recurrence step by step, dense
attention, a loop over the held experts): loss, first gradient and three
Adam steps, float32, with both kinds of layer, the leading dense layer
and expert layers that hold a share.  And recomputation per unit."""

import importlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import mxtpu as mx
from mxtpu import gluon
from mxtpu.analysis.memory_estimate import estimate_jit_memory
from mxtpu.models import kimi_linear, transformer
from mxtpu.observability.metrics import get_registry
from mxtpu.ops import remat as kept
from mxtpu.parallel import SPMDTrainer, make_mesh

from chipbench import harness, models, models_lm

ref = importlib.import_module("chipbench.references.kimi_linear")
CFG = harness.load_json(harness.HERE, "tests", "configs",
                        "kimi-linear-tiny.json")
STEPS, LR, B, T = 3, 1e-3, 1, 40      # T: no multiple of KDA's chunk


def test_the_toy_configuration_has_every_kind_of_layer():
    assert ref.layer_kinds(CFG) == [("kda", "dense"), ("kda", "moe"),
                                    ("kda", "moe"), ("mla", "moe"),
                                    ("kda", "moe")]
    assert CFG["num_experts"] < CFG["num_experts_total"]    # a share


def _toy_trainer():
    """(trainer, {name: Parameter}, starting weights, tokens, labels) of
    the toy configuration: Adam, recomputation per unit."""
    rng = np.random.default_rng(0)
    tokens, labels = (rng.integers(0, CFG["vocab_size"], (B, T),
                                   dtype=np.int32) for _ in range(2))
    weights = ref.init_weights(CFG, 5)
    train = dict(dtype="float32", optimizer="adam", learning_rate=LR,
                 remat=True)
    trainer, named = models_lm.kimi_linear_trainer(
        CFG, train, weights, ref.selection_bias(CFG), jax.devices()[:1])
    return trainer, named, weights, tokens, labels


@pytest.fixture(scope="module")
def both():
    """The program's and the reference's readings of the same steps."""
    trainer, named, weights, tokens, labels = _toy_trainer()
    w = {k: jnp.asarray(v) for k, v in weights.items()}
    state = tuple(jax.tree_util.tree_map(jnp.zeros_like, w)
                  for _ in range(2))
    out = {"loss": [], "ref_loss": []}
    for n in range(STEPS):
        out["loss"].append(float(trainer.step(
            mx.nd.array(tokens, dtype="int32"),
            mx.nd.array(labels, dtype="int32"))._data))
        total, grads = jax.value_and_grad(
            lambda w_: ref.loss_sum(CFG, w_, tokens, labels))(w)
        grads = jax.tree_util.tree_map(lambda g: g / tokens.size, grads)
        if n == 0:
            _, mean = models.trainer_state(trainer, named)
            out["grad"] = {k: np.asarray(v) / (1 - ref.BETA1)
                           for k, v in mean.items()}
            out["ref_grad"] = {k: np.asarray(v) for k, v in grads.items()}
        out["ref_loss"].append(float(total) / tokens.size)
        w, state = ref.adam_step(w, grads, state, LR, n + 1)
    params, _ = models.trainer_state(trainer, named)
    out["params"] = {k: np.asarray(v) for k, v in params.items()}
    out["ref_params"] = {k: np.asarray(v) for k, v in w.items()}
    out["start"] = weights
    return out


@pytest.mark.parametrize("step", range(STEPS))
def test_loss_of_every_step_matches_the_reference(both, step):
    assert both["loss"][step] == pytest.approx(both["ref_loss"][step],
                                               rel=2e-6)


KINDS = ["embed", "lm_head", "norm", "layer0.q_conv", "layer0.A_log",
         "layer0.dt_bias", "layer0.f_up", "layer0.g_up_bias", "layer0.beta",
         "layer0.o_norm", "layer0.down", "layer1.router",
         "layer1.experts_gate", "layer1.experts_down", "layer1.shared_up",
         "layer3.q", "layer3.dkv", "layer3.kv_norm", "layer3.ukv",
         "layer3.out", "layer4.k", "layer4.experts_up"]


@pytest.mark.parametrize("leaf", KINDS)
def test_first_gradient_matches_the_reference(both, leaf):
    got, want = both["grad"][leaf], both["ref_grad"][leaf]
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-5 * np.abs(want).max())


def test_every_leaf_has_a_gradient_and_the_reference_names_them_all(both):
    assert set(both["grad"]) == set(both["ref_grad"]) == set(both["start"])
    still = [k for k, g in both["ref_grad"].items() if not g.any()]
    assert still == []


def test_three_adam_steps_land_where_the_references_do(both):
    for name, want in both["ref_params"].items():
        moved = np.abs(want - both["start"][name]).max()
        np.testing.assert_allclose(both["params"][name], want, rtol=0,
                                   atol=0.02 * moved + 1e-7, err_msg=name)


# ------------------------------------------------- recomputation per unit

class _LMLoss(gluon.loss.Loss):
    def __init__(self):
        super().__init__(1.0, 0)
        self._ce = gluon.loss.SoftmaxCrossEntropyLoss()

    def hybrid_forward(self, F, out, labels):
        return self._ce(out.reshape((-1, out.shape[-1])),
                        labels.reshape((-1,)))


def _llama():
    return transformer.TransformerLM(64, units=32, hidden_size=64,
                                     num_layers=3, num_heads=2)


def _kimi():
    return kimi_linear.KimiLinearLM(
        64, 32, [("kda", "dense"), ("mla", "moe")], num_heads=2,
        kda_heads=2, kda_head_dim=16, kda_gate_rank=8, kv_rank=16,
        nope_dim=16, shared_dim=8, v_dim=16, hidden_size=64,
        expert_hidden_size=24, num_experts_total=8, top_k=2, held=(2, 4),
        routed_scale=2.0)


def _bert():
    class MLM(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.bert = transformer.BERTModel(
                    vocab_size=64, units=32, hidden_size=64, num_layers=3,
                    num_heads=2, max_length=16, dropout=0.0)

        def hybrid_forward(self, F, tokens):
            return self.bert(tokens)[2]
    return MLM()


@pytest.fixture(scope="module", params=[_llama, _kimi, _bert],
                ids=["llama", "kimi_linear", "bert"])
def with_and_without(request):
    tokens = mx.nd.array(np.random.RandomState(0).randint(0, 64, (2, 16)),
                         dtype="int32")
    out = {}
    for remat in (False, True):
        mx.random.seed(0)
        net = request.param()
        net.initialize(mx.init.Xavier())
        trainer = SPMDTrainer(net, _LMLoss(), "sgd",
                              make_mesh(dp=1, devices=jax.devices()[:1]),
                              optimizer_params={"learning_rate": 0.1},
                              remat=remat)
        jitted, args = trainer.step_program(tokens, tokens)
        peak = estimate_jit_memory(jitted, *args).activation_peak_bytes
        text = str(jax.make_jaxpr(jitted)(*args))
        loss = float(trainer.step(tokens, tokens)._data)
        out[remat] = dict(
            peak=peak, loss=loss, checkpoints=text.count("remat2"),
            model=request.param.__name__,
            params=[np.asarray(p.data()._data)
                    for p in trainer._diff_params])
    return out


def test_recomputation_gives_the_same_step_bit_for_bit(with_and_without):
    plain, remat = with_and_without[False], with_and_without[True]
    assert remat["loss"] == plain["loss"]
    for a, b in zip(plain["params"], remat["params"]):
        np.testing.assert_array_equal(a, b)


def test_recomputation_lowers_the_estimated_peak(with_and_without):
    plain, remat = with_and_without[False], with_and_without[True]
    if plain["model"] == "_kimi":
        # at toy widths the peak lies inside the KDA op, which forms its
        # chunks again in the backward pass with or without the trainer
        assert remat["peak"] <= plain["peak"]
    else:
        assert remat["peak"] < plain["peak"]


def test_recomputation_is_per_unit_not_around_the_whole_forward(
        with_and_without):
    plain, remat = with_and_without[False], with_and_without[True]
    if plain["model"] != "_kimi":       # (the KDA op has one of its own)
        assert plain["checkpoints"] == 0
    assert remat["checkpoints"] - plain["checkpoints"] >= 3   # a unit each


def test_remat_scope_outside_a_trace_just_runs():
    from mxtpu.gluon.block import remat_scope

    net = _kimi()
    net.initialize(mx.init.Xavier())
    x = mx.nd.array(np.arange(32).reshape(2, 16) % 64, dtype="int32")
    plain = net(x).asnumpy()
    with remat_scope():
        np.testing.assert_array_equal(net(x).asnumpy(), plain)
    loads = kimi_linear.expert_loads()
    assert any(sum(v["held"]) + v["elsewhere"] == 2 * 16 * 2
               for v in loads.values())


def test_from_config_takes_the_layers_it_has_from_the_published_lists():
    net = kimi_linear.kimi_linear_from_config(
        dict(CFG, num_hidden_layers=4), held=(4, 4), num_experts_total=16,
        kda_gate_rank=8)
    assert net.layer_kinds == [("kda", "dense"), ("kda", "moe"),
                               ("kda", "moe"), ("mla", "moe")]
    with pytest.raises(ValueError, match="neither"):
        kimi_linear.kimi_linear_from_config(dict(
            CFG, linear_attn_config=dict(CFG["linear_attn_config"],
                                         kda_layers=[1, 2])))


# --------------------------------- what a unit keeps beside its input
#
# ops/remat.py: the KDA mixer's output and flash attention's output and
# logsumexp are marked, and the units' checkpoint keeps them; so the
# backward pass runs those kernels' forward twice (KDA: the forward and
# the groups' own recomputation) or once (flash), where a unit that kept
# its input alone — ``jax.checkpoint(fn, policy=None)``, the program
# before the marks — ran them once more.

KERNEL = r"name=(kda_\w+|flash_attention_\w+)"


def _toy_steps(policy):
    """Three Adam steps of the toy configuration with ``policy`` as the
    units': the step program's kernels by name, what its units kept,
    losses, weights and Adam means."""
    import collections
    import re

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(kept, "policy", policy)
        trainer, named, _, tokens, labels = _toy_trainer()
        tokens, labels = (mx.nd.array(a, dtype="int32")
                          for a in (tokens, labels))
        jitted, args = trainer.step_program(tokens, labels)
        text = str(jax.make_jaxpr(jitted)(*args))
        snap = get_registry().snapshot()
        losses = [float(trainer.step(tokens, labels)._data)
                  for _ in range(STEPS)]
    params, mean = models.trainer_state(trainer, named)
    return dict(kernels=collections.Counter(re.findall(KERNEL, text)),
                kept=(snap["remat.kept_outputs"], snap["remat.kept_bytes"]),
                losses=[x.hex() for x in losses],
                params={k: np.asarray(v) for k, v in params.items()},
                mean={k: np.asarray(v) for k, v in mean.items()})


@pytest.fixture(scope="module")
def toy_steps():
    """With the units' policy, and with none: the program before the
    marks.  (``with_and_without`` has recomputation against none.)"""
    return {"kept": _toy_steps(kept.policy), "input_alone": _toy_steps(None)}


@pytest.mark.parametrize("unit, names, kept_runs, alone_runs", [
    # of the chunks' forward, one run is the groups' own, which also
    # writes the inverses for the backward kernel, under a name of its own
    ("kda", (("kda_chunk_fwd", "kda_chunk_fwd_inverse"),
             ("kda_state_fwd",)), 2, 3),
    ("mla", (("flash_attention_fwd",),), 1, 2)])
def test_the_backward_pass_runs_a_marked_kernels_forward_once_less(
        toy_steps, unit, names, kept_runs, alone_runs):
    layers = sum(mixer == unit for mixer, _ in ref.layer_kinds(CFG))
    for forms in names:
        runs = {which: sum(toy_steps[which]["kernels"][name]
                           for name in forms)
                for which in ("kept", "input_alone")}
        assert runs == {"kept": layers * kept_runs,
                        "input_alone": layers * alone_runs}
    if unit == "kda":
        for which in ("kept", "input_alone"):
            assert toy_steps[which]["kernels"]["kda_chunk_fwd_inverse"] == \
                layers
    for name in ("kda_chunk_bwd", "kda_state_bwd", "kda_state_fwd_states",
                 "flash_attention_bwd"):
        assert toy_steps["kept"]["kernels"][name] == \
            toy_steps["input_alone"]["kernels"][name]


@pytest.mark.parametrize("what", ["losses", "mean", "params"])
def test_keeping_the_marked_values_changes_no_bit(toy_steps, what):
    ours, theirs = toy_steps["kept"][what], toy_steps["input_alone"][what]
    if what == "losses":
        assert ours == theirs
    else:
        assert sorted(ours) == sorted(theirs)
        for name in ours:
            np.testing.assert_array_equal(ours[name], theirs[name],
                                          err_msg=name)


def test_the_counters_read_what_the_shapes_say(toy_steps):
    kda, heads = CFG["linear_attn_config"], CFG["num_attention_heads"]
    kinds = [mixer for mixer, _ in ref.layer_kinds(CFG)]
    mixer_out = B * T * kda["num_heads"] * kda["head_dim"] * 4
    flash_out = B * heads * T * CFG["v_head_dim"] * 4
    flash_lse = B * heads * T * 4
    assert toy_steps["kept"]["kept"] == (
        kinds.count("kda") + 2 * kinds.count("mla"),
        kinds.count("kda") * mixer_out
        + kinds.count("mla") * (flash_out + flash_lse))
    assert toy_steps["input_alone"]["kept"] == (0, 0)


def test_the_count_starts_anew_with_every_scope():
    from jax._src.ad_checkpoint import name_p
    from mxtpu.gluon.block import remat_scope

    value = jax.ShapeDtypeStruct((3, 5), jnp.float32)
    one = {"kept_outputs": 1, "kept_bytes": 60}
    with remat_scope():
        assert kept.policy(name_p, value, name=kept.KEPT)
        assert not kept.policy(name_p, value, name="another")
        assert not kept.policy(jax.lax.mul_p, value, value)
        assert kept.counts() == one
        with remat_scope(False):            # a unit's own forward
            assert kept.counts() == one
        with remat_scope():                 # no new program
            assert kept.counts() == one
    assert get_registry().snapshot()["remat.kept_bytes"] == 60
    with remat_scope():
        assert kept.counts() == {"kept_outputs": 0, "kept_bytes": 0}


def test_tracing_the_step_again_reads_the_same_counts():
    """As a jaxpr and lowered, each time traced anew: the policy rules
    once on a marked equation in each trace, and each trace starts its
    count with its scope.  A trace that jit finds in its cache runs
    nothing and leaves the counts as they are."""
    trainer, _, _, tokens, labels = _toy_trainer()
    tokens, labels = (mx.nd.array(a, dtype="int32")
                      for a in (tokens, labels))
    jitted, args = trainer.step_program(tokens, labels)
    step = jitted.__wrapped__
    read = []
    for trace in (jax.make_jaxpr(jitted),
                  jax.jit(lambda *a: step(*a)).lower,
                  jax.make_jaxpr(lambda *a: step(*a))):
        trace(*args)
        read.append(kept.counts())
    assert read[0]["kept_outputs"] > 0
    assert read[1] == read[0] and read[2] == read[0]
    kept.reset()
    jitted.lower(*args)                     # traced above
    assert kept.counts() == {"kept_outputs": 0, "kept_bytes": 0}


def test_each_thread_counts_its_own_programs():
    import threading
    from jax._src.ad_checkpoint import name_p
    from mxtpu.gluon.block import remat_scope

    value = jax.ShapeDtypeStruct((3, 5), jnp.float32)
    read = {}

    def trace(who, times, go, wait):
        with remat_scope():
            for _ in range(times):
                kept.policy(name_p, value, name=kept.KEPT)
            go.set()
            wait.wait(10)                   # both scopes open at once
            read[who] = kept.counts()

    first, second = threading.Event(), threading.Event()
    threads = [threading.Thread(target=trace, args=("a", 1, first, second)),
               threading.Thread(target=trace, args=("b", 2, second, first))]
    kept.reset()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert read == {"a": {"kept_outputs": 1, "kept_bytes": 60},
                    "b": {"kept_outputs": 2, "kept_bytes": 120}}
    assert kept.counts() == {"kept_outputs": 0, "kept_bytes": 0}


def test_a_unit_whose_ops_mark_nothing_lowers_to_the_program_it_had():
    """Eight positions: attention takes its dense path, which marks
    nothing."""
    tokens = mx.nd.array(np.random.RandomState(0).randint(0, 64, (2, 8)),
                         dtype="int32")
    texts = {}
    for policy in (kept.policy, None):
        mx.random.seed(0)
        net = _llama()
        net.initialize(mx.init.Xavier())
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(kept, "policy", policy)
            trainer = SPMDTrainer(
                net, _LMLoss(), "sgd",
                make_mesh(dp=1, devices=jax.devices()[:1]),
                optimizer_params={"learning_rate": 0.1}, remat=True)
            texts[policy] = trainer.lower_step(tokens, tokens).as_text()
        assert get_registry().snapshot()["remat.kept_outputs"] == 0
    assert texts[kept.policy].count("func.func") > 3     # units, wrapped
    assert texts[kept.policy] == texts[None]
