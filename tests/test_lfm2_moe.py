"""Lfm2MoeLM through SPMDTrainer.step against its plain reference
(chipbench/references/lfm2_moe.py: the gated short convolution written
out tap by tap, dense causal attention a block of query rows at a time
with a key head for each group of query heads, every held expert on
every token, the embedding as the head): logits, loss, every leaf's
first gradient and three Adam steps, float32, at toy widths on seeded
weights, with and without recomputation.  And the parts one by one: the
op against ``lax.conv_general_dilated``, its causality, the attention's
head norms and rotation, what ``from_config`` builds, the counter, the
benchmark's patterns for the kernels' events."""

import importlib
import json
import os
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import mxtpu as mx
from mxtpu.models import lfm2_moe, parts
from mxtpu.models.parts import ExpertShare, GatedMLP
from mxtpu.observability.metrics import get_registry
from mxtpu.ops.pallas import counters
from mxtpu.ops.short_conv import gated_short_conv

from chipbench import flops_lfm2_moe, harness, models, models_lfm2

ref = importlib.import_module("chipbench.references.lfm2_moe")
CFG = harness.load_json(harness.HERE, "tests", "configs", "lfm2-tiny.json")
CELL = harness.load_json(harness.HERE, "configs", "lfm2-8b-a1b.json")
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
    kinds = CFG["layer_types"]
    assert {"conv", "full_attention"} == set(kinds)
    assert 0 < CFG["num_dense_layers"] < len(kinds)     # dense and experts
    assert CFG["num_experts"] < CFG["num_experts_total"]        # a share
    assert CFG["held_experts_first"] > 0
    assert CFG["num_key_value_heads"] < CFG["num_attention_heads"]
    assert CFG["use_expert_bias"] and CFG["router_bias"]["std"] > 0


# -------------------------------------------------- the model, forward

@pytest.fixture(scope="module")
def forward():
    tokens, _ = _batch()
    weights, w = _weights()
    net, _ = models_lfm2.lfm2_moe_lm(CFG, weights, ref.selection_bias(CFG))
    before = lfm2_moe.conv_counts()
    logits = net(*_as_nd(tokens))
    return logits.asnumpy(), np.asarray(ref.logits_of(CFG, w, tokens)), \
        before, lfm2_moe.conv_counts(), net


def test_logits_match_the_reference(forward):
    got, want, *_ = forward
    assert got.shape == (B, T, CFG["vocab_size"])
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


def test_the_counter_counts_the_positions_of_every_convolution(forward):
    _, _, before, after, net = forward
    convs = CFG["layer_types"].count("conv")
    assert after["positions"] - before.get("positions", 0) == convs * B * T
    assert get_registry().snapshot()["conv.positions"] >= convs * B * T
    # a second pass adds as much again, layer by layer
    net(*_as_nd(_batch()[0]))
    for i, kind in enumerate(CFG["layer_types"]):
        mixer = net.decoder_layer(i)[0].inner
        if kind == "conv":
            assert int(mixer.positions.data().asnumpy()[0]) == 2 * B * T
        else:
            assert not hasattr(mixer, "positions")


def test_the_head_is_the_embedding():
    """One leaf serves both uses: no parameter of the model is a head's,
    and moving the embedding moves the logits of every position."""
    weights, _ = _weights()
    net, named = models_lfm2.lfm2_moe_lm(CFG, weights,
                                         ref.selection_bias(CFG))
    assert not [n for n in net.collect_params() if "head" in n]
    assert set(named) == set(ref.weight_shapes(CFG))
    tokens = _as_nd(_batch()[0])
    before = net(*tokens).asnumpy()
    row = min(set(range(CFG["vocab_size"]))
              - set(_batch()[0].ravel().tolist()))     # an id not in it
    moved = np.array(weights["embed"])
    moved[row] += 1.0
    named["embed"].set_data(mx.nd.array(moved))
    after = net(*tokens).asnumpy()
    changed = np.abs(after - before).max(axis=(0, 1)) > 1e-3
    assert changed[row] and changed.sum() == 1


# ------------------------------------------ the model through the trainer

@pytest.fixture(scope="module", params=[True, False],
                ids=["remat", "no-remat"])
def trained(request):
    """Three steps of the trainer and of the reference on the same
    batches: losses, the first gradient's leaf norms, the weights."""
    weights, w = _weights()
    train = dict(dtype="float32", optimizer="adam", learning_rate=LR,
                 remat=request.param)
    trainer, named = models_lfm2.lfm2_moe_trainer(
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
    # this model's own counters (``conv_counts`` sums every live model's)
    counted = sum(int(half.inner.positions.data().asnumpy()[0])
                  for half in trainer._block.layers
                  if isinstance(half.inner, lfm2_moe.ShortConv))
    return losses, ref_losses, grads, \
        {k: np.asarray(v) for k, v in params.items()}, \
        {k: np.asarray(v) for k, v in w.items()}, weights, counted


def test_the_loss_matches_the_reference(trained):
    losses, ref_losses, *_ = trained
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-6)


@pytest.mark.parametrize("name", sorted(ref.weight_shapes(CFG)))
def test_every_leafs_first_gradient_matches_the_reference(trained, name):
    got, want = trained[2]
    assert want[name] > 0
    np.testing.assert_allclose(got[name], want[name], rtol=2e-5)


def test_three_adam_steps_match_the_reference(trained):
    _, _, _, params, w, start, _ = trained
    for name in sorted(w):
        moved = np.abs(w[name] - start[name]).max()
        assert moved > 0, name
        np.testing.assert_allclose(params[name], w[name], rtol=0,
                                   atol=0.02 * moved, err_msg=name)


def test_a_step_counts_its_positions_once(trained):
    """Recomputed or not, a step's forward pass is counted once."""
    assert trained[-1] == STEPS * CFG["layer_types"].count("conv") * B * T


# ------------------------------------------------------------ the parts

def _conv_inputs(C=6, W=3, T=17, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return (jax.random.normal(ks[0], (2, T, 3 * C), jnp.float32),
            jax.random.normal(ks[1], (C, W), jnp.float32))


def _by_lax(bcx, filt):
    """``c * conv(b * x)`` with the convolution by
    ``lax.conv_general_dilated``: depthwise, left-padded, a
    cross-correlation (no flip)."""
    C, W = filt.shape
    b, c, x = jnp.split(bcx, 3, axis=-1)
    y = jax.lax.conv_general_dilated(
        b * x, filt.T[:, None, :], (1,), [(W - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=C,
        precision=jax.lax.Precision.HIGHEST)
    return c * y


@pytest.mark.parametrize("W", [1, 3, 4])
def test_the_op_is_a_left_padded_depthwise_convolution_between_gates(W):
    bcx, filt = _conv_inputs(W=W)
    np.testing.assert_allclose(np.asarray(gated_short_conv(bcx, filt)),
                               np.asarray(_by_lax(bcx, filt)), rtol=1e-5,
                               atol=1e-6)
    # and the reference's own spelling
    np.testing.assert_allclose(
        np.asarray(gated_short_conv(bcx, filt)),
        np.asarray(ref.gated_short_conv({}, bcx, filt)), rtol=1e-6,
        atol=1e-6)


def test_the_ops_gradient_matches_the_convolutions():
    bcx, filt = _conv_inputs()
    g = jax.random.normal(jax.random.PRNGKey(3), (2, 17, 6))
    got = jax.vjp(gated_short_conv, bcx, filt)[1](g)
    want = jax.vjp(_by_lax, bcx, filt)[1](g)
    for a, b, name in zip(got, want, ("bcx", "filt")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5, err_msg=name)


def test_the_op_through_the_nd_namespace_and_in_bfloat16():
    bcx, filt = _conv_inputs()
    got = mx.nd.gated_short_conv(mx.nd.array(bcx), mx.nd.array(filt))
    np.testing.assert_array_equal(got.asnumpy(),
                                  np.asarray(gated_short_conv(bcx, filt)))
    low = gated_short_conv(bcx.astype(jnp.bfloat16), filt)
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(low, np.float32),
                               np.asarray(gated_short_conv(bcx, filt)),
                               rtol=0.05, atol=0.05)
    with pytest.raises(ValueError, match="3 x"):
        gated_short_conv(bcx[..., :-1], filt)


@pytest.mark.parametrize("W", [1, 3])
def test_output_t_moves_with_inputs_t_minus_w_plus_1_to_t_and_no_other(W):
    """The Jacobian's support: d y[t] / d bcx[s] is nothing outside
    t - (W - 1) <= s <= t, something at every s inside (the b and x
    chunks reach back, the c chunk sits on the diagonal), and there are
    zeros before the sequence's start."""
    C, T_ = 4, 9
    bcx, filt = _conv_inputs(C=C, W=W, T=T_, seed=2)
    jac = jax.jacobian(lambda a: gated_short_conv(a[None], filt)[0])(bcx[0])
    reach = np.abs(np.asarray(jac)).max(axis=(1, 3))        # (t, s)
    for t in range(T_):
        for s in range(T_):
            assert (reach[t, s] > 0) == (t - (W - 1) <= s <= t), (t, s)
    # a position's output does not move with the batch's other row
    other = gated_short_conv(bcx.at[1].set(0.0), filt)
    np.testing.assert_array_equal(np.asarray(other[0]),
                                  np.asarray(gated_short_conv(bcx, filt)[0]))


def test_the_attentions_head_norms_and_rotation_match_the_reference():
    """``GroupedQueryAttention`` alone against the reference's layer,
    with gains that are not 1 and at positions where the rotation at
    theta = 1e6 has turned: a head norm without its gain, a rotation
    before the norm, or another pairing, would each read otherwise."""
    C, A, G, D = 48, 4, 2, 12
    cfg = dict(CFG, hidden_size=C, num_attention_heads=A,
               num_key_value_heads=G)
    ks = jax.random.split(jax.random.PRNGKey(4), 8)
    shapes = {"q": (A * D, C), "k": (G * D, C), "v": (G * D, C),
              "out": (C, A * D), "q_norm": (D,), "k_norm": (D,)}
    w = {"l." + n: (1.0 + 0.5 * jax.random.normal(k, s)
                    if n.endswith("norm") else 0.2 * jax.random.normal(k, s))
         for k, (n, s) in zip(ks, shapes.items())}
    u = jax.random.normal(ks[6], (2, 33, C))
    want = ref._attention(cfg, w, "l.", u, "highest")
    block = parts.GroupedQueryAttention(
        C, A, G, D, rope_base=cfg["rope_theta"], eps=cfg["norm_eps"])
    block.initialize(mx.init.Zero())
    for name in shapes:
        param = getattr(block, name if name.endswith("norm")
                        else name + "_proj").weight
        param.set_data(mx.nd.array(w["l." + name]))
    got = block(mx.nd.array(u)).asnumpy()
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-5, atol=2e-6)
    # the gains are in it, and so is the rotation
    flat = dict(w, **{"l.q_norm": jnp.ones(D), "l.k_norm": jnp.ones(D)})
    assert np.abs(np.asarray(ref._attention(cfg, flat, "l.", u, "highest"))
                  - got).max() > 1e-3
    still = dict(cfg, rope_theta=1.0)          # every frequency is 1
    assert np.abs(np.asarray(ref._attention(still, w, "l.", u, "highest"))
                  - got).max() > 1e-3
    with pytest.raises(ValueError, match="no multiple"):
        parts.GroupedQueryAttention(C, 4, 3, D)


def test_from_config_builds_the_kinds_from_the_lists():
    """The mixers from ``layer_types``, the dense layers from
    ``num_dense_layers``, experts after them: at the cell's own cut and
    at the published lists."""
    row = dict(CELL, hidden_size=16, intermediate_size=24,
               moe_intermediate_size=8, num_attention_heads=4,
               num_key_value_heads=2, vocab_size=32)
    published = dict(row, **CELL["published"])
    for cfg in (row, published):
        net = lfm2_moe.lfm2_moe_from_config(cfg)
        assert net.num_layers == cfg["num_hidden_layers"]
        for i, kind in enumerate(cfg["layer_types"]):
            mix, ff = (half.inner for half in net.decoder_layer(i))
            assert isinstance(mix, lfm2_moe.ShortConv if kind == "conv"
                              else parts.GroupedQueryAttention)
            assert isinstance(ff, GatedMLP if i < cfg["num_dense_layers"]
                              else ExpertShare)
            if isinstance(ff, ExpertShare):
                assert ff.shared is None and ff._score == "sigmoid"
                assert ff._renorm_eps == 1e-6 and ff._k == 4
                assert ff.experts_gate.shape[0] == cfg["num_experts"]
            if kind == "conv":
                assert mix.conv.shape == (16, cfg["conv_L_cache"])
    assert published["layer_types"].count("full_attention") == 6
    assert published["num_hidden_layers"] == 24
    share = lfm2_moe.lfm2_moe_from_config(row, held=(8, 8),
                                          num_experts_total=32)
    moe = share.decoder_layer(1)[1].inner
    assert moe.router.weight.shape == (32, 16) and moe._first == 8
    with pytest.raises(ValueError, match="unknown layer type"):
        lfm2_moe.lfm2_moe_from_config(dict(row, layer_types=["conv"] * 4
                                           + ["sliding"]))
    with pytest.raises(ValueError, match="names 5 layers"):
        lfm2_moe.lfm2_moe_from_config(dict(row, num_hidden_layers=6))
    with pytest.raises(ValueError, match="conv_bias"):
        lfm2_moe.lfm2_moe_from_config(dict(row, conv_bias=True))


def test_the_cells_file_holds_the_published_widths_and_the_cut():
    rows = [json.loads(line) for line in open(os.path.join(
        "/opt/skills/guides/model-configs", "architectures.jsonl"))] \
        if os.path.exists("/opt/skills/guides/model-configs") else []
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2-8b-a1b")
    assert sorted(entry["reduced"]) == sorted(CELL["reduced"]) == sorted(
        CELL["published"])
    for row in rows:
        if row["name"] == "LFM2-8B-A1B":
            assert entry["source"] == CELL["source"] == row["source_url"]
            for key, value in row["config"].items():
                if key in entry["reduced"]:
                    assert CELL["published"][key] == value, key
                else:
                    assert CELL[key] == value, key
    shapes = ref.weight_shapes(CELL)
    assert sum(int(np.prod(s)) for s, _ in shapes.values()) \
        == CELL["trained_parameters"] == 507820160
    assert CELL["layer_types"] == [CELL["published"]["layer_types"][i]
                                   for i in (0, 2, 3, 4, 5)]


# ------------------------------------------------- the benchmark's metrics

# heads of event names as the traced runs have them (an event's name is
# its whole HLO instruction, cut at 120 characters)
FLASH_EVENTS = [
    "%jvp_flash_attention_fwd_.9 = (f32[32,8192,64]{2,1,0:T(8,128)}, "
    "f32[32,16,512]{2,1,0:T(8,128)S(1)}) custom-ca",
    "%flash_attention_fwd.4 = (f32[32,8192,64]{2,1,0:T(8,128)}, "
    "f32[32,16,512]{2,1,0:T(8,128)S(1)}) custom-call(",
    "%flash_attention_bwd.6 = (f32[32,8192,64]{2,1,0:T(8,128)}, "
    "f32[32,8192,64]{2,1,0:T(8,128)}, f32[32,8192,64]"]
GROUPED_EVENTS = [
    "%ragged-dot-none.69 = f32[8,2048,1792]{2,1,0:T(8,128)} custom-call("
    "s32[1]{0:T(128)} %get-tuple-element.6131, s",
    "%ragged-dot.3 = f32[5120,1792]{1,0:T(8,128)} ragged-dot("
    "f32[5120,2048]{1,0:T(8,128)} %select.1, f32[8,2048,1792]"]
OTHER_EVENTS = [
    "%ragged-dot-metadata.4 = (s32[9]{0:T(128)S(1)}, s32[17]{0:T(128)S(1)}, "
    "s32[17]{0:T(128)S(1)}, s32[1]",
    "%while.103 = (s32[]{:T(128)}, f32[8192,2048]{1,0:T(8,128)}, "
    "f32[65536]{0:T(1024)}, f32[8,2048,1792]{2,1,0:T(8,128)}, ...",
    "%multiply_add_fusion.12 = f32[1,8192,2048]{2,1,0:T(8,128)} fusion("
    "f32[1,8192,6144]{2,1,0:T(8,128)} %ragged-dot-none.69, f32[",
    "%select_fusion.3 = f32[32,8192,64]{2,1,0:T(8,128)} fusion("
    "f32[32,8192,64]{2,1,0:T(8,128)} %flash_attention_bwd.6, f32[",
    "%convolution_add_fusion.7 = f32[8192,6144]{1,0:T(8,128)} fusion("
    "f32[8192,2048]{1,0:T(8,128)} %get-tuple-element.77"]


def _metric(name):
    return harness.load_json(harness.HERE, "metrics", name + ".json")


@pytest.mark.parametrize("name, events", [
    ("flash_roofline.lfm2", FLASH_EVENTS),
    ("flash_time_share.lfm2", FLASH_EVENTS),
    ("expert_products_time_share.lfm2", GROUPED_EVENTS)])
def test_a_metrics_patterns_read_their_kernels_events_and_no_other(name,
                                                                   events):
    args = _metric(name)["args"]
    patterns = [re.compile(p) for p in
                list(args.get("patterns", []))
                + list(args.get("kernels", {}).values())]
    hit = lambda event: sum(bool(p.search(event)) for p in patterns)
    assert all(hit(event) == 1 for event in events)     # once: no double
    others = [e for e in FLASH_EVENTS + GROUPED_EVENTS + OTHER_EVENTS
              if e not in events]
    assert not any(hit(event) for event in others)
    if "kernels" in args:       # forward and backward are priced apart
        assert re.search(args["kernels"]["fwd"], events[0])
        assert re.search(args["kernels"]["bwd"], events[-1])
        assert args["kernels"]["fwd"].split("fwd")[0] \
            == args["kernels"]["bwd"].split("bwd")[0]


def test_the_flash_kernels_names_are_what_the_patterns_name():
    fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
    source = open(fa.__file__).read()
    for kernel in ("flash_attention_fwd", "flash_attention_bwd"):
        assert 'name="%s"' % kernel in source
    # the traced calls count under the op's one name, forward and
    # backward alike
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 32, 16))
    before = counters.count(fa.KERNEL_NAME)
    fa.flash_attention(q, q, q, causal=True)
    assert counters.count(fa.KERNEL_NAME) > before
    assert "kernel_invocations.flash_attention" in get_registry().snapshot()


def test_every_lfm2_metric_names_the_cell_a_reader_and_its_entry():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = "lfm2-8b-a1b.pretrain-seq8192"
    declared = {m["name"]: m for m in bench["per_layer"]}
    folder = os.path.join(harness.HERE, "metrics")
    mine = sorted(f[:-5] for f in os.listdir(folder)
                  if f.endswith(".lfm2.json"))
    assert mine == sorted([
        "train_mfu.lfm2", "flash_roofline.lfm2", "flash_time_share.lfm2",
        "expert_products_time_share.lfm2", "expert_load_peak.lfm2",
        "device_idle_share.lfm2", "step_dispatch_ms.lfm2", "stage_s.lfm2",
        "first_step_s.lfm2", "xla_compile_s.lfm2",
        # PR 37's split of setup_s, whose own files list four cells
        "preimport_s.lfm2", "import_s.lfm2", "initialize_s.lfm2",
        "backend_compile_s.lfm2", "setup_outside_s.lfm2"])
    for name in mine:
        spec = _metric(name)
        assert spec["workloads"] == [cell]
        assert os.path.exists(os.path.join(harness.HERE, "readers",
                                           spec["reader"] + ".py"))
        assert declared[name] == {
            key: spec[key] for key in ("name", "unit", "better", "source",
                                       "layer", "moves", "workloads")}
        assert re.match(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$", name)
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", spec["unit"])
        assert 1 <= len(spec["layer"]) <= 200
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    assert entry == dict(entry, config="lfm2-8b-a1b", chips=1,
                         traffic="pretrain-seq8192")
    assert 1 <= len(entry["why"]) <= 200
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "train_tokens_per_s")
    assert cell in rate["workloads"] and rate["bound"] == 0.01


def test_the_mfu_is_priced_from_the_counted_positions_and_pairs():
    """``train_mfu.lfm2`` falls when the program counts no convolution
    and follows the pairs the experts were sent."""
    reader = harness.load_module("readers", "train_mfu_lfm2")

    class Device:
        device_kind = "TPU v5 lite"

    class Cell:
        config, devices = CELL, [Device()]

    tokens = 50 * 8192
    seen = {"steps": 50, "tokens_per_step": 8192, "elapsed_s": 30.0,
            "seq": 8192, "batch": 1, "held_pairs_per_token": 1.0,
            "counted": {"positions": 4 * tokens}}
    full = reader.read(Cell, {}, seen, None)
    per_token = flops_lfm2_moe.train_flops_per_token(CELL, 8192)
    assert abs(per_token - 1.2976e9) < 1e6              # the issue's sum
    np.testing.assert_allclose(full, 100 * per_token * tokens / 30.0
                               / 197e12, rtol=1e-12)
    none = reader.read(Cell, {}, dict(seen, counted={"positions": 0}), None)
    conv = 3 * 4 * flops_lfm2_moe.conv_mixer_flops_per_position(CELL)
    np.testing.assert_allclose(none / full, 1 - conv / per_token, rtol=1e-9)
    assert 0.30 < conv / per_token < 0.32
    more = reader.read(Cell, {}, dict(seen, held_pairs_per_token=2.0), None)
    assert more > full
    assert reader.read(Cell, {}, {"steps": 0}, None) is None
