"""The LFM2 training runner at a size the CPU holds: a whole run of the
toy cell through the harness (``need_chip=False``), ``correct`` true on
the sound path and false for the lower-precision control and for each
fault the configuration lists; the new readers and the operation counts
on made-up observations and against counts made by hand (the cases
``test_flops.py`` would hold: that file may not be edited).
"""

import io
import json
import os

import pytest

from chipbench import compare, flops, flops_lfm2_moe, harness

BASE = os.path.join(harness.HERE, "tests")
BENCH = harness.load_json(BASE, "BENCHMARK-lfm2.json")
CELL = "lfm2-tiny.pretrain-lm-tiny"


def load(kind, name):
    return harness.load_json(BASE, kind, name + ".json")


def test_sound_run_is_correct_and_reports_its_observations(monkeypatch):
    runner = harness.load_module("runners", "train_from_config")
    seen = {}
    real = runner.run
    monkeypatch.setattr(runner, "run", lambda cell: seen.setdefault(
        "ran", real(cell)))
    out = io.StringIO()
    result = harness.run_cell(BENCH, CELL, 2 ** 31 + 5, 0.3, 0, base=BASE,
                              need_chip=False, out=out, err=io.StringIO())
    assert json.loads(out.getvalue().splitlines()[-1]) == result
    assert result["correct"] is True, result["compared"]
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    observed = seen["ran"]["observed"]
    config = load("configs", "lfm2-tiny")
    convs = config["layer_types"].count("conv")
    # every convolution layer saw every token of the window's steps once
    assert observed["counted"] == {
        "positions": convs * observed["steps"] * observed["tokens_per_step"]}
    experts = config["num_hidden_layers"] - config["num_dense_layers"]
    assert len(observed["expert_loads"]) == experts
    assert all(sum(held) > 0 for held in observed["expert_loads"].values())
    assert 0 < observed["held_pairs_per_token"] < 4


def test_a_program_without_the_model_ends_at_the_import(monkeypatch):
    """The parent commit, this PR's benchmark files laid over it: the
    run ends at the import of the program's module, before any weight
    is made."""
    import jax

    config = dict(load("configs", "lfm2-tiny"))
    config["program"] = dict(config["program"],
                             module="mxtpu.models.not_in_this_program")
    cell = harness.Cell("readings", {"chips": 1}, config,
                        load("traffic", "pretrain-lm-tiny"), BASE, 0, 0.1,
                        False, jax.devices()[:1])
    runner = harness.load_module("runners", "train_from_config")
    reference = harness.load_module("references", "lfm2_moe")
    monkeypatch.setattr(reference, "init_weights", lambda *a: pytest.fail(
        "weights were made before the import"))
    with pytest.raises(ImportError):
        runner.run(cell)


@pytest.fixture(scope="module")
def sides():
    import jax

    config, traffic = load("configs", "lfm2-tiny"), load(
        "traffic", "pretrain-lm-tiny")
    cell = harness.Cell("readings", {"chips": 1}, config, traffic, BASE, 0,
                        0.1, False, jax.devices()[:1])
    runner = harness.load_module("runners", "train_from_config")
    got = runner.readings(cell, 2 ** 31 + 3,
                          ["program", "control"] + config["correct"]["faults"])
    limits = config["correct"]["limits"]
    return {side: [c["name"] for c in compare.checks(r["numbers"], limits)
                   if not c["value"] <= c["limit"]]
            for side, r in got.items()}


def test_the_program_reads_correct(sides):
    assert sides["program"] == []


@pytest.mark.parametrize("side", ["control", "conv_taps_left_out",
                                  "gates_left_out", "experts_left_out"])
def test_the_control_and_each_fault_read_not_correct(sides, side):
    assert sides[side], side


def test_an_unknown_side_is_refused():
    import jax

    cell = harness.Cell("readings", {"chips": 1},
                        load("configs", "lfm2-tiny"),
                        load("traffic", "pretrain-lm-tiny"), BASE, 0, 0.1,
                        False, jax.devices()[:1])
    with pytest.raises(ValueError, match="unknown side"):
        harness.load_module("runners", "train_from_config").readings(
            cell, 1, ["half_batch"])


# ---------------------------------------------------------------- counting

CFG = harness.load_json(harness.HERE, "configs", "lfm2-8b-a1b.json")
CHIP = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_cut_configuration_holds_what_its_file_says():
    ref = harness.load_module("references", "lfm2_moe")
    sizes = {}
    for name, (shape, _) in ref.weight_shapes(CFG).items():
        size = 1
        for n in shape:
            size *= n
        sizes[name] = size
    layer = lambda i: sum(v for k, v in sizes.items()
                          if k.startswith("layer%d." % i))
    assert layer(0) == 60827648                 # convolution, dense MLP
    assert layer(1) == 98635904                 # attention, experts
    assert layer(2) == layer(3) == layer(4) == 104933376
    assert sizes["embed"] + sizes["norm"] == 33556480
    assert sum(sizes.values()) == CFG["trained_parameters"] == 507820160
    assert CFG["published"] == {
        "num_experts": 32, "vocab_size": 65536, "num_hidden_layers": 24,
        "num_dense_layers": 2,
        "layer_types": (["conv", "conv", "full_attention"]
                        + ["conv", "conv", "conv", "full_attention"] * 4
                        + ["conv", "conv", "full_attention", "conv",
                           "conv"])[:24]}
    assert CFG["vocab_size"] * 4 == 65536
    assert CFG["num_experts"] * 4 == CFG["num_experts_total"] == 32
    # one range for every matrix, one for the convolutions' filters
    assert CFG["initializer_range"] == 0.02
    assert CFG["conv_filter_range"] == pytest.approx(1 / 3)
    assert "embedding_range" not in CFG
    assert "residual_projection_range" not in CFG
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == CFG["name"])
    assert sorted(entry["reduced"]) == sorted(CFG["reduced"])
    assert entry["file"] == "chipbench/configs/lfm2-8b-a1b.json"


def test_every_published_key_is_at_its_published_value():
    """The catalog's ``config`` of the row, key for key, except the five
    under ``reduced`` (model-configs guide: architectures.jsonl)."""
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "num_experts_per_tok": 4, "rope_theta": 1000000,
        "routed_scaling_factor": 1, "use_expert_bias": True}
    for key, value in published.items():
        assert CFG[key] == value and key not in CFG["reduced"], key
    assert set(CFG["reduced"]) == {"num_experts", "vocab_size",
                                   "num_hidden_layers", "num_dense_layers",
                                   "layer_types"}
    # no width is among the cut keys
    for key in CFG["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")) \
            or key == "vocab_size"


def test_model_operations_of_the_cut_configuration():
    per_token = flops_lfm2_moe.train_flops_per_token(CFG, 8192)
    conv = 2 * 2048 * 6144 + 2 * 2048 * 2048               # a position
    attention = 2 * 2048 * (32 + 16) * 64 + 2 * 2048 * 2048 \
        + 4 * 32 * 64 * 33558528 / 8192
    experts = 2 * 2048 * 32 + 1.0 * 3 * 2 * 2048 * 1792     # a pair a token
    dense, head = 3 * 2 * 2048 * 7168, 2 * 2048 * 16384
    assert flops_lfm2_moe.conv_mixer_flops_per_position(CFG) == conv
    assert flops_lfm2_moe.attention_flops_per_token(CFG, 8192) \
        == pytest.approx(attention)
    assert flops_lfm2_moe.expert_layer_flops_per_token(CFG) == experts
    assert per_token == pytest.approx(
        3 * (4 * conv + attention + dense + 4 * experts + head))
    # the issue's shares of the forward products, to the percent
    forward = per_token / 3
    assert [round(100 * part / forward) for part in (
        4 * conv, dense, 4 * experts, head, attention)] \
        == [31, 20, 20, 16, 13]
    assert per_token == pytest.approx(1.2976e9, rel=1e-4)
    # a program that drops the convolution counts no position
    none = flops_lfm2_moe.train_flops_per_token(CFG, 8192, None, 0.0)
    assert per_token - none == pytest.approx(3 * 4 * conv)
    more = flops_lfm2_moe.train_flops_per_token(CFG, 8192, 2.0)
    assert more - per_token == pytest.approx(4 * 3 * 3 * 2 * 2048 * 1792)
    # the published lists count too: 18 convolutions, 6 attentions
    whole = dict(CFG, **CFG["published"])
    assert flops_lfm2_moe.train_flops_per_token(whole, 8192, 4.0) \
        == pytest.approx(3 * (18 * conv + 6 * attention + 2 * dense
                              + 22 * (experts + 3 * 3 * 2 * 2048 * 1792)
                              + 2 * 2048 * 65536))


def test_the_flash_kernels_cost_by_hand():
    pairs = 8192 * 8193 // 2
    ops, moved = flops_lfm2_moe.flash_cost("fwd", 1, CFG, 8192, 4)
    assert ops == 32 * 2 * 2 * pairs * 64 == 274911461376
    # q and o at 32 heads, k and v at 8, lse a float32 a row and head
    assert moved == 2 * 32 * 8192 * 64 * 4 + 2 * 8 * 8192 * 64 * 4 \
        + 32 * 8192 * 4 == 168820736
    # product-bound: 1.40 ms against 0.21 ms of bytes
    assert flops.least_time(ops, moved, CHIP) == ops / 197e12
    back = flops_lfm2_moe.flash_cost("bwd", 1, CFG, 8192, 4)
    assert back[0] == ops * 5 // 2
    assert back[1] == 4 * 32 * 8192 * 64 * 4 + 4 * 8 * 8192 * 64 * 4 \
        + 2 * 32 * 8192 * 4
    assert flops_lfm2_moe.flash_cost("fwd", 3, CFG, 8192, 2)[0] == 3 * ops
    with pytest.raises(KeyError):
        flops_lfm2_moe.flash_cost("dq", 1, CFG, 8192, 4)


class _Device:
    device_kind = "TPU v5 lite"


class _Cell:
    config = CFG
    devices = [_Device()]


def _trace(events):
    return {"devices": {"/device:TPU:0": events},
            "spans": [("chipbench.window", 0, 10 ** 9)]}


OBSERVED = {"steps": 10, "tokens_per_step": 8192, "elapsed_s": 10.0,
            "seq": 8192, "batch": 1}


def test_train_mfu_lfm2_counts_the_positions_the_program_counted():
    reader = harness.load_module("readers", "train_mfu_lfm2")
    whole = reader.read(_Cell, {}, OBSERVED, None)
    assert whole == pytest.approx(
        100 * flops_lfm2_moe.train_flops_per_token(CFG, 8192) * 8192
        / 197e12)
    assert 0 < whole < 100
    counted = reader.read(_Cell, {}, dict(
        OBSERVED, counted={"positions": 4 * 10 * 8192}), None)
    assert counted == pytest.approx(whole)
    # a program that drops the convolution counts no position: its step
    # is not credited with the mixers' products
    dropped = reader.read(_Cell, {}, dict(
        OBSERVED, counted={"positions": 0}), None)
    assert dropped == pytest.approx(whole * (1 - 0.3103), rel=1e-3)
    assert reader.read(_Cell, {}, {}, None) is None


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_the_roofline_prices_its_kernels_events(kernel):
    reader = harness.load_module("readers", "flash_roofline_lfm2")
    spec = harness.load_json(harness.HERE, "metrics",
                             "flash_roofline.lfm2.json")
    least = flops.least_time(
        *flops_lfm2_moe.flash_cost(kernel, 1, CFG, 8192, 4), CHIP)
    name = "flash_attention_" + kernel
    # an event's name is its whole instruction: a fusion that reads the
    # kernel's result names it too, and is not the kernel
    events = [("%%%s.3 = (f32[32,8192,64]) custom-call(...)" % name, 1000,
               int(10 * least * 1e9)),
              ("%%fusion.7 = f32[8] fusion(%%%s.3)" % name, 2000, 5000)]
    assert reader.read(_Cell, spec, OBSERVED, _trace(events)) \
        == pytest.approx(10.0, rel=1e-3)
    assert reader.read(_Cell, spec, OBSERVED,
                       _trace([("fusion.7", 0, 5000)])) is None


def test_the_time_shares_count_kernels_and_not_what_reads_them():
    reader = harness.load_module("readers", "kernel_time_share")
    events = [("%ragged-dot-none.69 = f32[8,2048,1792]{2,1,0} custom-call("
               "s32[1]{0} %get-tuple-element.6131, s", 0, 300),
              ("%flash_attention_bwd.6 = (f32[32,8192,64]{2,1,0}, "
               "f32[32,8192,64]", 300, 100),
              ("%jvp_flash_attention_fwd_.9 = (f32[32,8192,64]{2,1,0}, "
               "f32[32,16,512]", 400, 100),
              ("%multiply_fusion.4 = f32[5120,2048] fusion(f32[5120,2048] "
               "%ragged-dot-none.69, %flash_attention_bwd.6)", 500, 500)]
    for name, share in (("expert_products_time_share.lfm2", 30.0),
                        ("flash_time_share.lfm2", 20.0)):
        spec = harness.load_json(harness.HERE, "metrics", name + ".json")
        assert spec["reader"] == "kernel_time_share"
        assert reader.read(_Cell, spec, OBSERVED, _trace(events)) \
            == pytest.approx(share)
        assert reader.read(_Cell, spec, OBSERVED,
                           _trace(events[3:])) is None
