"""``correct`` at a size the CPU holds: true on the sound path, false
for the lower-precision control and for each fault a training cell can
have.  These skip the harness's look for a chip (``need_chip=False``)
and drive everything else of a run, with the timed path (``issue``)
broken underneath.
"""

import io
import json
import os

import jax.numpy as jnp
import pytest

from chipbench import compare, harness

BASE = os.path.join(harness.HERE, "tests")
BENCH = harness.load_json(BASE, "BENCHMARK.json")
CELL = "bert-tiny.pretrain-tiny"
TRAIN = harness.load_module("runners", "train")


def run(seed=3, trace=0):
    out = io.StringIO()
    result = harness.run_cell(BENCH, CELL, seed, 0.3, trace, base=BASE,
                              need_chip=False, out=out, err=io.StringIO())
    assert json.loads(out.getvalue().splitlines()[-1]) == result
    return result


def test_sound_run_is_correct_and_prints_the_contracts_line():
    result = run(seed=2 ** 31 + 11)
    assert result["correct"] is True
    assert list(result)[-1] == "compared"          # last, as the record keeps
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    for value, limit in result["compared"].values():
        assert value <= limit


def failed_numbers(result):
    return {name for name, (value, limit) in result["compared"].items()
            if not value <= limit}


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    real = TRAIN.issue

    def unchanged(trainer, tokens, labels):
        trainer._ensure_staged(__import__("mxtpu").nd.array(
            tokens, dtype="int32"))
        params = [jnp.copy(p.data()._data) for p in trainer._diff_params]
        states = [tuple(jnp.copy(s) for s in st)
                  for st in trainer._opt_states]
        count = trainer._num_update
        loss = real(trainer, tokens, labels)
        for p, saved in zip(trainer._diff_params, params):
            p.data()._rebind(saved)
        trainer._opt_states = states
        trainer._num_update = count
        return loss

    monkeypatch.setattr(TRAIN, "issue", unchanged)
    result = run()
    assert result["correct"] is False
    assert {"grad_norm_gap", "change_norm_gap"} <= failed_numbers(result)


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    real = TRAIN.issue

    def half(trainer, tokens, labels):
        keep = len(tokens) // 2
        return real(trainer, tokens[:keep], labels[:keep])

    monkeypatch.setattr(TRAIN, "issue", half)
    result = run()
    assert result["correct"] is False
    assert "grad_norm_gap" in failed_numbers(result)


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_the_lower_precision_control_is_not_correct(seed):
    import jax

    config = harness.load_json(BASE, "configs", "bert-tiny.json")
    traffic = harness.load_json(BASE, "traffic", "pretrain-tiny.json")
    cell = harness.Cell("control", {"chips": 1}, config, traffic, BASE, seed,
                        0.1, False, jax.devices()[:1])
    sides = TRAIN.readings(cell, seed, ["program", "control", "half_batch"])
    limits = config["correct"]["limits"]

    def fails(side):
        return [c["name"] for c in compare.checks(sides[side]["numbers"],
                                                  limits)
                if not c["value"] <= c["limit"]]

    assert fails("program") == []
    assert fails("control"), sides["control"]
    assert "grad_norm_gap" in fails("half_batch")


# ----------------------------------------------------------------- serving

SERVE_CELL = "decoder-tiny.batch-tiny"


def run_serving(seed=5):
    out = io.StringIO()
    return harness.run_cell(BENCH, SERVE_CELL, seed, 1.0, 0, base=BASE,
                            need_chip=False, out=out, err=io.StringIO())


def test_sound_serving_run_is_correct():
    result = run_serving(seed=2 ** 31 + 21)
    assert result["correct"] is True, result["compared"]
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from mxtpu.serving import Gateway

    real = Gateway.streamed

    def altered(self, rid):
        tokens = real(self, rid)
        return [(t + 1) % 256 if i == 2 else t for i, t in enumerate(tokens)]

    monkeypatch.setattr(Gateway, "streamed", altered)
    result = run_serving()
    assert result["correct"] is False
    assert "served_logit_gap" in failed_numbers(result)


def test_an_answer_cut_short_is_not_correct(monkeypatch):
    from mxtpu.serving import Gateway

    real = Gateway.streamed
    monkeypatch.setattr(Gateway, "streamed",
                        lambda self, rid: real(self, rid)[:3])
    result = run_serving()
    assert result["correct"] is False
    assert "wrong_length_or_failed" in failed_numbers(result)
    assert result["failed"] == result["attempted"] > 0


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_the_int8_control_of_serving_is_not_correct(seed):
    import jax

    config = harness.load_json(BASE, "configs", "decoder-tiny.json")
    traffic = harness.load_json(BASE, "traffic", "batch-tiny.json")
    cell = harness.Cell("control", {"chips": 1}, config, traffic, BASE, seed,
                        0.5, False, jax.devices()[:1])
    serve = harness.load_module("runners", "serve")
    sides = serve.readings(cell, seed, ["program", "control",
                                        "altered_token"])
    limit = config["correct"]["limits"]["served_logit_gap"]
    assert sides["program"]["numbers"]["served_logit_gap"] <= limit
    assert sides["control"]["numbers"]["served_logit_gap"] > 3 * limit
    assert sides["altered_token"]["numbers"]["served_logit_gap"] > 3 * limit
