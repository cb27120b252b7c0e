"""The GLM-4.7-Flash training runner at a size the CPU holds: a whole
run of the toy cell through the harness (``need_chip=False``), ``correct``
true on the sound path and false for the lower-precision control and for
each fault the configuration lists; the new readers and the operation
counts on made-up observations.
"""

import io
import json
import os

import pytest

from chipbench import compare, flops_glm4_moe_lite, flops_kimi_linear, harness

BASE = os.path.join(harness.HERE, "tests")
BENCH = harness.load_json(BASE, "BENCHMARK-glm.json")
CELL = "glm-tiny.pretrain-lm-tiny"


def load(kind, name):
    return harness.load_json(BASE, kind, name + ".json")


def test_sound_run_is_correct_and_reports_its_observations(monkeypatch):
    runner = harness.load_module("runners", "train_glm")
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
    steps, tokens = observed["steps"], observed["tokens_per_step"]
    # the module's loss left one position a row out
    assert observed["mtp_positions"] == steps * (tokens - observed["batch"])
    # three expert layers, the module's among them, each got its pairs
    assert len(observed["expert_loads"]) == 3
    assert all(sum(held) > 0 for held in observed["expert_loads"].values())
    assert 0 < observed["held_pairs_per_token"] < 4


@pytest.fixture(scope="module")
def sides():
    import jax

    config, traffic = load("configs", "glm-tiny"), load(
        "traffic", "pretrain-lm-tiny")
    cell = harness.Cell("readings", {"chips": 1}, config, traffic, BASE, 0,
                        0.1, False, jax.devices()[:1])
    runner = harness.load_module("runners", "train_glm")
    got = runner.readings(cell, 2 ** 31 + 3,
                          ["program", "control"] + config["correct"]["faults"])
    limits = config["correct"]["limits"]
    return {side: [c["name"] for c in compare.checks(r["numbers"], limits)
                   if not c["value"] <= c["limit"]]
            for side, r in got.items()}


def test_the_program_reads_correct(sides):
    assert sides["program"] == []


@pytest.mark.parametrize("side", ["control", "experts_left_out",
                                  "mtp_left_out", "rope_left_out"])
def test_the_control_and_each_fault_read_not_correct(sides, side):
    assert sides[side], side


def test_an_unknown_side_is_refused():
    import jax

    cell = harness.Cell("readings", {"chips": 1}, load("configs", "glm-tiny"),
                        load("traffic", "pretrain-lm-tiny"), BASE, 0, 0.1,
                        False, jax.devices()[:1])
    with pytest.raises(ValueError, match="unknown side"):
        harness.load_module("runners", "train_glm").readings(
            cell, 1, ["half_batch"])


# ---------------------------------------------------------------- counting

CFG = harness.load_json(harness.HERE, "configs", "glm-4.7-flash.json")


def test_the_cut_configuration_holds_what_its_file_says():
    ref = harness.load_module("references", "glm4_moe_lite")
    count = 0
    for shape, _ in ref.weight_shapes(CFG).values():
        size = 1
        for n in shape:
            size *= n
        count += size
    assert count == CFG["trained_parameters"] == 706518528
    assert sorted(ref.selection_bias(CFG)) == [1, 2, 3, 4, 5]


def test_model_operations_of_the_cut_configuration():
    per_token = flops_glm4_moe_lite.train_flops_per_token(CFG, 8192)
    # six latent-attention calls at 8k (the module's among them), the
    # dense MLP, five expert layers at half a held expert a token, two
    # heads over a slice: 3.63 GFLOP a token, 41.6% of it scores and values
    assert per_token == pytest.approx(3.6255e9, rel=1e-3)
    attention = 3 * 6 * 2 * 20 * (256 + 256) * 8193 / 2
    assert attention / per_token == pytest.approx(0.4165, rel=1e-3)
    more = flops_glm4_moe_lite.train_flops_per_token(CFG, 8192, 1.0)
    # five expert layers, the module's at the share of positions counted
    assert more - per_token == pytest.approx(
        3 * (4 + 8191 / 8192) * 0.5 * 3 * 2 * 2048 * 1536)
    none = flops_glm4_moe_lite.train_flops_per_token(CFG, 8192, None, 0.0)
    assert per_token - none == pytest.approx(
        3 * 8191 / 8192 * flops_glm4_moe_lite.mtp_flops_per_position(
            CFG, 8192))


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


def test_train_mfu_glm_counts_the_modules_positions():
    reader = harness.load_module("readers", "train_mfu_glm")
    whole = reader.read(_Cell, {}, OBSERVED, None)
    assert whole == pytest.approx(
        100 * flops_glm4_moe_lite.train_flops_per_token(CFG, 8192) * 8192
        / 197e12)
    assert 0 < whole < 100
    # a program that drops the second loss counts no positions: its step
    # is credited without the module's products
    dropped = reader.read(_Cell, {}, dict(OBSERVED, mtp_positions=0), None)
    assert dropped < whole
    assert reader.read(_Cell, {}, {}, None) is None


def test_flash_roofline_glm_prices_heads_of_256():
    reader = harness.load_module("readers", "kernel_roofline")
    spec = harness.load_json(harness.HERE, "metrics",
                             "flash_roofline.glm.json")
    from chipbench import flops
    ops, moved = flops_kimi_linear.flash_cost("bwd", 20, 8192, 256, 256, 4)
    assert ops == 20 * 2 * (8192 * 8192 / 2) * 5 * 256
    least = flops.least_time(ops, moved, {"bf16_flops": 197e12,
                                          "hbm_bytes_per_s": 819e9})
    events = [("flash_attention_bwd", 1000, int(10 * least * 1e9)),
              ("fusion.7", 2000, 5000)]
    assert reader.read(_Cell, spec, OBSERVED, _trace(events)) \
        == pytest.approx(10.0, rel=1e-3)


def test_kernel_time_share_is_the_named_events_over_the_busy_time():
    reader = harness.load_module("readers", "kernel_time_share")
    spec = harness.load_json(harness.HERE, "metrics",
                             "flash_time_share.glm.json")
    events = [("flash_attention_fwd.3", 0, 2 * 10 ** 8),
              ("fusion.7", 2 * 10 ** 8, 5 * 10 ** 8),
              ("flash_attention_bwd.1", 7 * 10 ** 8, 10 ** 8),
              # an idle gap to the window's end, and an event past it
              ("flash_attention_bwd.1", 2 * 10 ** 9, 10 ** 8)]
    assert reader.read(_Cell, spec, OBSERVED, _trace(events)) \
        == pytest.approx(100 * 3 / 8)
    assert reader.read(_Cell, spec, OBSERVED,
                       _trace([("fusion.7", 0, 5000)])) is None


def test_every_glm_metric_names_the_cell_and_a_reader_that_exists():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    mine = [m for m in bench["per_layer"] if m["name"].endswith(".glm")]
    assert len(mine) == 9
    for m in mine:
        assert m["workloads"] == ["glm-4.7-flash.pretrain-seq8192"]
        spec = harness.load_json(harness.HERE, "metrics",
                                 m["name"] + ".json")
        assert os.path.exists(os.path.join(harness.HERE, "readers",
                                           spec["reader"] + ".py"))
