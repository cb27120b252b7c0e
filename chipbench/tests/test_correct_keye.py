"""The Keye-VL-2.0 training runner at a size the CPU holds: a whole run
of the toy cell through the harness (``need_chip=False``), ``correct``
true on the sound path and false for the lower-precision control and for
each fault the configuration lists; the new readers and the operation
counts on made-up observations.
"""

import io
import json
import os

import pytest

from chipbench import compare, flops, flops_keye_vl, harness

BASE = os.path.join(harness.HERE, "tests")
BENCH = harness.load_json(BASE, "BENCHMARK-keye.json")
CELL = "keye-tiny.pretrain-lm-tiny"


def load(kind, name):
    return harness.load_json(BASE, kind, name + ".json")


def test_sound_run_is_correct_and_reports_its_observations(monkeypatch):
    runner = harness.load_module("runners", "train_keye")
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
    config, seq = load("configs", "keye-tiny"), observed["seq"]
    topk = config["sa_config"]["topk"]
    # every query kept its top-k (all its keys while it has no more);
    # ties at a threshold are kept too
    least = flops_keye_vl.kept_pairs(seq, topk) / seq
    assert least <= observed["kept_pairs_per_token"] < 1.1 * least
    assert observed["selected_pairs"] == pytest.approx(
        observed["kept_pairs_per_token"] * config["num_hidden_layers"]
        * observed["steps"] * observed["tokens_per_step"])
    assert len(observed["kept_mean"]) == config["num_hidden_layers"]
    assert observed["index_loss_mean"] > 0
    assert len(observed["expert_loads"]) == config["num_hidden_layers"]
    assert all(sum(held) > 0 for held in observed["expert_loads"].values())
    assert 0 < observed["held_pairs_per_token"] < 4


@pytest.fixture(scope="module")
def sides():
    import jax

    config, traffic = load("configs", "keye-tiny"), load(
        "traffic", "pretrain-lm-tiny")
    cell = harness.Cell("readings", {"chips": 1}, config, traffic, BASE, 0,
                        0.1, False, jax.devices()[:1])
    runner = harness.load_module("runners", "train_keye")
    got = runner.readings(cell, 2 ** 31 + 3,
                          ["program", "control"] + config["correct"]["faults"])
    limits = config["correct"]["limits"]
    return {side: [c["name"] for c in compare.checks(r["numbers"], limits)
                   if not c["value"] <= c["limit"]]
            for side, r in got.items()}


def test_the_program_reads_correct(sides):
    assert sides["program"] == []


@pytest.mark.parametrize("side", ["control", "selection_left_out",
                                  "indexer_loss_left_out",
                                  "experts_left_out"])
def test_the_control_and_each_fault_read_not_correct(sides, side):
    assert sides[side], side


def test_an_unknown_side_is_refused():
    import jax

    cell = harness.Cell("readings", {"chips": 1},
                        load("configs", "keye-tiny"),
                        load("traffic", "pretrain-lm-tiny"), BASE, 0, 0.1,
                        False, jax.devices()[:1])
    with pytest.raises(ValueError, match="unknown side"):
        harness.load_module("runners", "train_keye").readings(
            cell, 1, ["half_batch"])


# ---------------------------------------------------------------- counting

CFG = harness.load_json(harness.HERE, "configs", "keye-vl-2.0-30b-a3b.json")
CHIP = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_cut_configuration_holds_what_its_file_says():
    ref = harness.load_module("references", "keye_vl")
    sizes = {}
    for name, (shape, _) in ref.weight_shapes(CFG).items():
        size = 1
        for n in shape:
            size *= n
        sizes[name] = size
    assert sum(sizes.values()) == CFG["trained_parameters"] == 659190016
    layer = sum(v for k, v in sizes.items() if k.startswith("layer0."))
    assert layer == 96899456
    assert CFG["published"] == {"num_experts": 128, "vocab_size": 151936,
                                "num_hidden_layers": 48}
    assert CFG["vocab_size"] * 8 == 151936
    assert CFG["num_experts"] * 8 == CFG["num_experts_total"] \
        == CFG["num_local_experts"] == 128
    # GPT-2's scaled initialisation by the published depth
    assert CFG["residual_projection_range"] == pytest.approx(
        CFG["initializer_range"] / (2 * 48) ** 0.5)
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == CFG["name"])
    assert sorted(entry["reduced"]) == sorted(CFG["reduced"])


def test_every_published_key_is_at_its_published_value():
    """The catalog's ``config`` of the row, key for key, except the three
    under ``reduced`` (model-configs guide: architectures.jsonl)."""
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 262144, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "num_local_experts": 128,
        "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    for key, value in published.items():
        if key in CFG["reduced"]:
            assert CFG[key] != value and CFG["published"][key] == value
        else:
            assert CFG[key] == value, key
    for key in ("head_norms", "mrope", "indexer", "selection_ties",
                "chunk_sizes", "indexer_loss", "no_auxiliary_loss",
                "intermediate_size", "embedding_range",
                "residual_projection_range"):
        assert key in CFG["assumed"], key


def test_pairs_by_hand():
    # rows 0..2047 keep t + 1 keys, the 6,144 after them 2,048 each
    assert flops_keye_vl.kept_pairs(8192, 2048) \
        == 2048 * 2049 // 2 + 6144 * 2048 == 14681088
    assert flops_keye_vl.causal_pairs(8192) == 33558528
    assert flops_keye_vl.kept_pairs(1000, 2048) \
        == flops_keye_vl.causal_pairs(1000)


def test_model_operations_of_the_cut_configuration():
    per_token = flops_keye_vl.train_flops_per_token(CFG, 8192)
    # a layer's doubled products a token: projections 2*2048*(32+8)*128
    # + 2*4096*2048, Q K^T and P V over 1,792.1 kept keys 4*32*128*that,
    # the indexer's scores over 4,096.5 causal keys 2*16*64*that
    kept, causal = 14681088 / 8192, 33558528 / 8192
    doubled = 20971520 + 16777216 + 16384 * kept + 2048 * causal
    once = 2 * 2048 * (1024 + 64 + 16)
    experts = 2 * 2048 * 128 + 1.0 * 3 * 2 * 2048 * 768
    assert flops_keye_vl.attention_flops_per_token(CFG, 8192) \
        == pytest.approx((doubled, once))
    assert per_token == pytest.approx(
        6 * (3 * (doubled + experts) + 2 * once) + 3 * 2 * 2048 * 18992)
    assert per_token == pytest.approx(1.82595e9, rel=1e-4)
    # a program that drops the selection counts the causal pairs
    dense = flops_keye_vl.train_flops_per_token(CFG, 8192, None, causal)
    assert dense - per_token == pytest.approx(
        6 * 3 * 16384 * (causal - kept))
    more = flops_keye_vl.train_flops_per_token(CFG, 8192, 2.0)
    assert more - per_token == pytest.approx(6 * 3 * 3 * 2 * 2048 * 768)


def test_kernels_by_hand():
    ops, moved = flops_keye_vl.sparse_attention_cost("fwd", 1, CFG, 8192, 4)
    assert ops == 32 * 2 * 2 * 14681088 * 128
    assert moved == 2 * 32 * 8192 * 128 * 4 + 2 * 4 * 8192 * 128 * 4 \
        + 32 * 8192 * 4
    assert flops.least_time(ops, moved, CHIP) == ops / 197e12   # 1.22 ms
    assert flops_keye_vl.sparse_attention_cost("bwd", 1, CFG, 8192, 4)[0] \
        == ops * 5 // 2
    index = flops_keye_vl.indexer_cost("scores_fwd", 1, CFG, 8192, 4)
    assert index[0] == 16 * 2 * 33558528 * 64
    assert index[1] == 33558528 * 4 + (16 * 8192 * 64 + 8192 * 64
                                       + 16 * 8192) * 4
    assert flops_keye_vl.indexer_cost("scores_bwd_q", 1, CFG, 8192, 4)[0] \
        == 2 * index[0]
    assert flops_keye_vl.indexer_cost("scores_bwd_k", 1, CFG, 8192, 4)[0] \
        == index[0]
    assert flops_keye_vl.indexer_cost("probs", 1, CFG, 8192, 4)[0] \
        == 32 * 2 * 14681088 * 128
    with pytest.raises(KeyError):
        flops_keye_vl.indexer_cost("select", 1, CFG, 8192, 4)


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


def test_train_mfu_keye_counts_the_pairs_the_program_kept():
    reader = harness.load_module("readers", "train_mfu_keye")
    whole = reader.read(_Cell, {}, OBSERVED, None)
    assert whole == pytest.approx(
        100 * flops_keye_vl.train_flops_per_token(CFG, 8192) * 8192
        / 197e12)
    assert 0 < whole < 100
    # a program that drops the selection counts every causal pair: its
    # step is credited with dense attention's products
    dense = reader.read(_Cell, {}, dict(
        OBSERVED, kept_pairs_per_token=33558528 / 8192), None)
    assert dense > 1.3 * whole
    assert reader.read(_Cell, {}, {}, None) is None


@pytest.mark.parametrize("metric,kernel,family", [
    ("sparse_attn_roofline.keye", "bwd", "attention"),
    ("indexer_roofline.keye", "scores_bwd_q", "indexer")])
def test_the_rooflines_price_their_kernels_events(metric, kernel, family):
    reader = harness.load_module("readers", "sparse_attention_roofline")
    spec = harness.load_json(harness.HERE, "metrics", metric + ".json")
    assert spec["args"]["family"] == family
    cost = reader.COST[family](kernel, 1, CFG, 8192, 4)
    least = flops.least_time(*cost, CHIP)
    name = {"bwd": "flash_attention_bwd",
            "scores_bwd_q": "indexer_scores_bwd_q"}[kernel]
    # an event's name is its whole instruction: a fusion that reads the
    # kernel's result names it too, and is not the kernel
    events = [("%%%s.3 = (f32[1,8192,64]) custom-call(...)" % name, 1000,
               int(10 * least * 1e9)),
              ("%%fusion.7 = f32[8] fusion(%%%s.3)" % name, 2000, 5000)]
    assert reader.read(_Cell, spec, OBSERVED, _trace(events)) \
        == pytest.approx(10.0, rel=1e-3)
    assert reader.read(_Cell, spec, OBSERVED,
                       _trace([("fusion.7", 0, 5000)])) is None


def test_every_keye_metric_names_the_cell_and_a_reader_that_exists():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    mine = [m for m in bench["per_layer"] if m["name"].endswith(".keye")]
    assert len(mine) == 11
    for m in mine:
        assert m["workloads"] == ["keye-vl.pretrain-seq8192"]
        spec = harness.load_json(harness.HERE, "metrics",
                                 m["name"] + ".json")
        assert (spec["unit"], spec["better"], spec["moves"], spec["layer"]) \
            == (m["unit"], m["better"], m["moves"], m["layer"])
        assert os.path.exists(os.path.join(harness.HERE, "readers",
                                           spec["reader"] + ".py"))
    cells = next(m for m in bench["end_to_end"]
                 if m["name"] == "train_tokens_per_s")["workloads"]
    assert cells[-1] == "keye-vl.pretrain-seq8192" and len(cells) == 4


# the heads of the selection's events as the final traced run had them
# (chiprun_out/pr35/final/traced.json: while.108-113, one a layer), of the
# experts' loop and of the counting pass inside the selection's loop
SELECTION = ("%%while.%d = (s32[]{:T(128)}, u32[1,8192]{1,0:T(1,128)S(1)}, "
             "u32[1,8192,8192]{2,1,0:T(8,128)}, s32[]{:T(128)}, s32[]{...")
EXPERTS = ("%while.103 = (s32[]{:T(128)}, f32[8192,2048]{1,0:T(8,128)}, "
           "f32[65536]{0:T(1024)}, f32[16,2048,768]{2,1,0:T(8,128)}, ...")
COUNTING = ("%convert_reduce_fusion.34 = s32[8192]{0:T(1024)S(1)} fusion("
            "u32[1,8192,8192]{2,1,0:T(8,128)} %get-tuple-element.6620,...")


def _selection_events(names, steps=2):
    """``steps`` steps of one kernel event, the experts' loop, the
    counting pass and the loops ``names`` each: 1e7 ns an event."""
    events = []
    for _ in range(steps):
        for name in ["%jvp_indexer_probs_.4 = f32[1,8192,8192] custom-call()",
                     EXPERTS, COUNTING] + list(names):
            events.append((name, len(events) * 10 ** 7, 10 ** 7))
    return events


@pytest.mark.parametrize("case,names,share", [
    # six loops a step, one a layer: read, with the kernel's event
    ("as_traced", [SELECTION % n for n in range(108, 114)], 7 / 9),
    # the carry in another order is still the selection
    ("carry_reordered",
     ["%%while.%d = (s32[]{:T(128)}, s32[]{:T(128)}, u32[1,8192,8192]{2,1,0:"
      "T(8,128)}, u32[1,8192]{1,0:T(1,128)S(1)}) while(...)" % n
      for n in range(6)], 7 / 9),
    # a loop that carries unsigned vectors (a generator's) is not
    ("another_u32_loop",
     [SELECTION % n for n in range(108, 114)]
     + ["%while.7 = (s32[]{:T(128)}, u32[2]{0}, u32[8192]{0:T(1024)}) "
        "while(...)"], 7 / 10),
    # a layer's loop gone, or one more that looks the same: nothing is
    # read rather than a share that has silently moved
    ("one_loop_short", [SELECTION % n for n in range(108, 113)], None),
    ("one_loop_more", [SELECTION % n for n in range(108, 115)], None),
    # a program without the selection's loop reads its kernels alone
    ("no_loop", [], 1 / 3)])
def test_the_selections_loop_is_told_by_its_carry_and_counted(
        case, names, share, capsys):
    reader = harness.load_module("readers", "counted_time_share")
    spec = harness.load_json(harness.HERE, "metrics",
                             "indexer_time_share.keye.json")
    assert spec["reader"] == "counted_time_share"
    events = _selection_events(names)
    got = reader.read(_Cell, spec, dict(OBSERVED, steps=2), _trace(events))
    if share is None:
        assert got is None
        assert "expected" in capsys.readouterr().err
    else:
        assert got == pytest.approx(100 * share)


def test_the_time_shares_count_kernels_and_not_the_fusions_that_read_them():
    reader = harness.load_module("readers", "counted_time_share")
    spec = harness.load_json(harness.HERE, "metrics",
                             "indexer_time_share.keye.json")
    events = [
        ("%jvp_indexer_probs_.4 = f32[1,8192,8192] custom-call()", 0, 10 ** 8),
        ("%indexer_scores_bwd_k.2 = f32[1,8192,64] custom-call()", 10 ** 8,
         10 ** 8),
        # the experts' loop, and a fusion that reads a kernel's result
        ("%while.102 = (s32[]{:T(128)}, f32[8192,2048]{1,0}) while(...)",
         3 * 10 ** 8, 10 ** 8),
        ("%fusion.9 = f32[8192] fusion(%jvp_indexer_probs_.4)", 4 * 10 ** 8,
         10 ** 8)]
    assert reader.read(_Cell, spec, OBSERVED, _trace(events)) \
        == pytest.approx(100 * 2 / 4)
    reader = harness.load_module("readers", "kernel_time_share")
    attn = harness.load_json(harness.HERE, "metrics",
                             "attn_time_share.keye.json")
    events = [("%jvp_flash_attention_fwd_.6 = (f32[32,8192,128]) call()", 0,
               10 ** 8),
              ("%flash_attention_bwd.8 = (f32[32,8192,128]) call()", 10 ** 8,
               10 ** 8),
              ("%fusion.3 = f32[8] fusion(%flash_attention_bwd.8)",
               2 * 10 ** 8, 2 * 10 ** 8)]
    assert reader.read(_Cell, attn, OBSERVED, _trace(events)) \
        == pytest.approx(50.0)
