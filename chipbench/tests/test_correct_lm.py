"""The language-model training runner at a size the CPU holds: a whole
run of the toy cell through the harness (``need_chip=False``), ``correct``
true on the sound path and false for the lower-precision control and for
each fault the configuration lists; the new readers and the operation
counts on made-up observations.
"""

import io
import json
import os

import pytest

from chipbench import compare, flops, flops_kimi_linear, harness

BASE = os.path.join(harness.HERE, "tests")
BENCH = harness.load_json(BASE, "BENCHMARK-lm.json")
CELL = "kimi-linear-tiny.pretrain-lm-tiny"


def load(kind, name):
    return harness.load_json(BASE, kind, name + ".json")


def test_sound_run_is_correct_and_reports_the_experts_load():
    out = io.StringIO()
    result = harness.run_cell(BENCH, CELL, 2 ** 31 + 5, 0.3, 0, base=BASE,
                              need_chip=False, out=out, err=io.StringIO())
    assert json.loads(out.getvalue().splitlines()[-1]) == result
    assert result["correct"] is True, result["compared"]
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0


@pytest.fixture(scope="module")
def sides():
    import jax

    config, traffic = load("configs", "kimi-linear-tiny"), load(
        "traffic", "pretrain-lm-tiny")
    cell = harness.Cell("readings", {"chips": 1}, config, traffic, BASE, 0,
                        0.1, False, jax.devices()[:1])
    runner = harness.load_module("runners", "train_lm")
    got = runner.readings(cell, 2 ** 31 + 3,
                          ["program", "control"] + config["correct"]["faults"])
    limits = config["correct"]["limits"]
    return {side: [c["name"] for c in compare.checks(r["numbers"], limits)
                   if not c["value"] <= c["limit"]]
            for side, r in got.items()}


def test_the_program_reads_correct(sides):
    assert sides["program"] == []


@pytest.mark.parametrize("side", ["control", "experts_left_out",
                                  "decay_is_one"])
def test_the_control_and_each_fault_read_not_correct(sides, side):
    assert sides[side], side


def test_an_unknown_side_is_refused():
    import jax

    cell = harness.Cell("readings", {"chips": 1},
                        load("configs", "kimi-linear-tiny"),
                        load("traffic", "pretrain-lm-tiny"), BASE, 0, 0.1,
                        False, jax.devices()[:1])
    with pytest.raises(ValueError, match="unknown side"):
        harness.load_module("runners", "train_lm").readings(
            cell, 1, ["half_batch"])


# ---------------------------------------------------------------- counting

CFG = harness.load_json(harness.HERE, "configs", "kimi-linear-48b-a3b.json")


def test_model_operations_of_the_cut_configuration():
    per_token = flops_kimi_linear.train_flops_per_token(CFG, 8192)
    # four KDA layers, one latent layer at 8k, the dense MLP, four expert
    # layers at a quarter of a held expert a token, the head over a slice
    assert 2.2e9 < per_token < 2.7e9
    more = flops_kimi_linear.train_flops_per_token(CFG, 8192, 1.0)
    assert more - per_token == pytest.approx(
        3 * 4 * 0.75 * 3 * 2 * 2304 * 1024)


def test_flash_cost_counts_a_narrower_value_and_five_backward_products():
    ops_f, moved_f = flops_kimi_linear.flash_cost("fwd", 32, 8192, 192, 128, 4)
    ops_b, moved_b = flops_kimi_linear.flash_cost("bwd", 32, 8192, 192, 128, 4)
    pairs = 8192 * 8192 / 2
    assert ops_f == 32 * 2 * pairs * (192 + 128)
    assert ops_b == 32 * 2 * pairs * (3 * 192 + 2 * 128)
    assert moved_f == 32 * (8192 * 4 * (2 * 192 + 2 * 128) + 8192 * 4)
    assert moved_b > moved_f
    # at one width for keys and values it is the accepted count
    same, _ = flops_kimi_linear.flash_cost("fwd", 8, 512, 64, 64, 4,
                                           causal=False)
    assert same == flops.flash_attention_cost("fwd", 8, 512, 512, 64, 4)[0]


def test_kda_state_cost_is_linear_in_heads_and_chunks():
    one = flops_kimi_linear.kda_state_cost("fwd", 1, 64, 128, 128, 64)
    many = flops_kimi_linear.kda_state_cost("fwd", 8, 8192, 128, 128, 64)
    assert many == (one[0] * 8 * 128, one[1] * 8 * 128)
    assert one[0] == 6 * 64 * 128 * 128 + 2 * 64 * 64 * 128
    states = flops_kimi_linear.kda_state_cost("fwd_states", 1, 64, 128, 128,
                                              64)
    assert states[1] - one[1] == 128 * 128 * 4


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


def test_kernel_roofline_reads_named_events_and_nothing_else():
    reader = harness.load_module("readers", "kernel_roofline")
    spec = harness.load_json(harness.HERE, "metrics",
                             "flash_roofline.seq8192.json")
    ops, moved = flops_kimi_linear.flash_cost("fwd", 32, 8192, 192, 128, 4)
    least = flops.least_time(ops, moved, {"bf16_flops": 197e12,
                                          "hbm_bytes_per_s": 819e9})
    events = [("flash_attention_fwd", 1000, int(4 * least * 1e9)),
              ("fusion.7", 2000, 5000)]
    assert reader.read(_Cell, spec, OBSERVED, _trace(events)) \
        == pytest.approx(25.0, rel=1e-3)
    assert reader.read(_Cell, spec, OBSERVED,
                       _trace([("fusion.7", 2000, 5000)])) is None


def test_kda_roofline_tells_the_forward_that_writes_states():
    reader = harness.load_module("readers", "kernel_roofline")
    spec = harness.load_json(harness.HERE, "metrics",
                             "kda_roofline.seq8192.json")
    kernels = spec["args"]["kernels"]
    import re
    assert re.search(kernels["state.fwd"], "kda_state_fwd.3")
    assert not re.search(kernels["state.fwd"], "kda_state_fwd_states.3")
    assert re.search(kernels["state.fwd_states"], "kda_state_fwd_states.3")
    assert not re.search(kernels["state.bwd"], "kda_chunk_bwd.1")
    got = reader.read(_Cell, spec, OBSERVED,
                      _trace([("kda_state_bwd", 0, 10 ** 7),
                              ("kda_chunk_fwd", 10 ** 7, 10 ** 7)]))
    assert 0 < got < 100


def test_kda_chunk_cost_counts_the_halvings():
    ops, moved = flops_kimi_linear.kda_chunk_cost("fwd", 1, 64, 128, 128, 64)
    C, K = 64, 128
    assert ops == 2 * C * C * K + 6 * (6 * C * C * K + 4 * C ** 3) \
        + 6 * C * C * K + 2 * C * C * K
    back, more = flops_kimi_linear.kda_chunk_cost("bwd", 1, 64, 128, 128, 64)
    assert back == 3 * ops and more > moved


def test_train_mfu_lm_and_expert_load_peak():
    mfu = harness.load_module("readers", "train_mfu_lm").read(
        _Cell, {}, OBSERVED, None)
    assert mfu == pytest.approx(
        100 * flops_kimi_linear.train_flops_per_token(CFG, 8192) * 8192
        / 197e12)
    peak = harness.load_module("readers", "expert_load_peak")
    loads = {"layer1": [10, 10, 10, 10], "layer2": [30, 10, 10, 10]}
    assert peak.read(_Cell, {}, dict(OBSERVED, expert_loads=loads), None) \
        == pytest.approx(2.0)
    assert peak.read(_Cell, {}, OBSERVED, None) is None
