"""readers/program_span.py on the program's own ring: the toy trainer
of ``bert-tiny`` stepped on the CPU inside a hand-made ``Cell`` window,
then ``read`` called directly with each of the four specs of the
training cell (a traced ``run_cell`` needs a TPU plane, which no
rehearsal has); and on made-up rings for the serving metrics that wait
under ``unproven/``."""

import os
from collections import namedtuple

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness

BASE = os.path.join(harness.HERE, "tests")
TRAIN = harness.load_module("runners", "train")
READER = harness.load_module("readers", "program_span")
TRAIN_METRICS = ["step_dispatch_ms.train", "stage_s.train",
                 "first_step_s.train", "xla_compile_s.train"]
STEPS = 4


def spec_of(name, folder="metrics"):
    return harness.load_json(harness.HERE, *folder.split("/"),
                             name + ".json")


@pytest.fixture(scope="module")
def stepped():
    """A cell whose trainer made its compared steps, then ``STEPS`` more
    inside the window; the tracer is off throughout."""
    from mxtpu.observability import get_tracer

    tracer = get_tracer()
    assert not tracer.enabled
    tracer.reset()
    config = harness.load_json(BASE, "configs", "bert-tiny.json")
    traffic = harness.load_json(BASE, "traffic", "pretrain-tiny.json")
    cell = harness.Cell("bert-tiny.pretrain-tiny", {"chips": 1}, config,
                        traffic, BASE, 2 ** 31 + 5, 0.1, False,
                        jax.devices()[:1])
    reference = cell.module("references", config["reference"])
    generator = cell.module("generators", traffic["generator"])
    trainer, named = TRAIN.build(cell, reference, cell.seed)
    jax.block_until_ready(TRAIN.first_steps(
        cell, reference, generator, trainer, named, cell.seed))
    with cell.window():
        for n in range(STEPS):
            loss = TRAIN.issue(trainer, *generator.train_batch(
                traffic, config, config["train"]["batch"], cell.seed,
                TRAIN.CHECK_STEPS + n))
        loss.block_until_ready()
    assert tracer.events() == []
    return cell


@pytest.mark.parametrize("name", TRAIN_METRICS)
def test_each_training_metric_reads_a_number(stepped, name):
    spec = spec_of(name)
    assert spec["reader"] == "program_span"
    assert spec["source"] == "program_span"
    value = READER.read(cell=stepped, spec=spec, observed={}, trace=None)
    assert isinstance(value, float) and value > 0.0


def test_what_the_four_select(stepped):
    spans = READER.ring_spans()
    lo, hi = READER.window_ns(stepped)
    args = {n: spec_of(n)["args"] for n in TRAIN_METRICS}
    inside = READER.select(spans, args["step_dispatch_ms.train"], lo, hi)
    assert [s.fields["step"] for s in inside] == [
        TRAIN.CHECK_STEPS + 1 + n for n in range(STEPS)]
    assert not any(s.fields["first"] for s in inside)
    first = READER.select(spans, args["first_step_s.train"], lo, hi)
    assert [s.fields["step"] for s in first] == [1]
    stage = READER.select(spans, args["stage_s.train"], lo, hi)
    assert len(stage) == 1 and stage[0].parent == first[0].tick
    # the first step less the staging it held is what is left of it
    under = READER.children_of(spans)
    left = READER.measure(first[0], args["first_step_s.train"], under)
    assert 0 < left == (first[0].end_ns - first[0].start_ns) - (
        stage[0].end_ns - stage[0].start_ns)
    compiles = READER.select(spans, args["xla_compile_s.train"], lo, hi)
    assert {s.fields["kind"] for s in compiles} >= {"trace", "lower",
                                                    "compile"}
    # nested and overlapping compilation spans are counted once
    assert READER.covered_ns(compiles) <= sum(
        s.end_ns - s.start_ns for s in compiles)
    assert READER.covered_ns(compiles) <= lo - min(
        s.start_ns for s in compiles)


def test_compilations_after_the_window_are_left_out(stepped):
    spec = spec_of("xla_compile_s.train")
    before = READER.read(cell=stepped, spec=spec, observed={}, trace=None)
    held = len(READER.ring_spans())
    # what a reference does once the window has closed: a fresh program
    jax.jit(lambda x: jnp.tanh(x) * 3.0 + 1.0)(jnp.ones((3, 5)))
    later = [s for s in READER.ring_spans()[held:]
             if s.etype == "xla.compile"]
    assert later and all(s.start_ns > READER.window_ns(stepped)[1]
                         for s in later)
    assert READER.read(cell=stepped, spec=spec, observed={},
                       trace=None) == before


def test_nothing_to_read(stepped, monkeypatch):
    spec = spec_of("stage_s.train")
    # a ring that holds no such span
    absent = dict(spec, args=dict(spec["args"], span="engine.iteration"))
    assert READER.read(cell=stepped, spec=absent, observed={},
                       trace=None) is None
    # a program that keeps no ring (the parent commit), an empty ring,
    # and a cell whose window never opened
    for ring in (None, []):
        monkeypatch.setattr(READER, "ring_spans", lambda ring=ring: ring)
        assert READER.read(cell=stepped, spec=spec, observed={},
                           trace=None) is None
    monkeypatch.undo()
    unopened = harness.Cell("x", {"chips": 1}, {}, {}, BASE, 1, 0.1, False,
                            jax.devices()[:1])
    assert READER.read(cell=unopened, spec=spec, observed={},
                       trace=None) is None


# ------------------------------------------- the serving metrics, made up

Span = namedtuple("Span", "etype tick parent start_ns end_ns rid fields")
MS = 1_000_000


def made_up_ring():
    """Two iterations inside a window of [0, 100 ms] on the ring's
    clock: schedule 1 ms, decode_step 6 ms holding a 4 ms host read
    (and, in the second, a prefill of 2 ms holding a 1 ms read)."""
    def it(tick, t0, prefill):
        t = t0 * MS
        out = [Span("engine.schedule", tick + 1, tick, t, t + MS, None, {})]
        at = t + MS
        if prefill:
            out += [Span("engine.host_read", tick + 5, tick + 4, at,
                         at + MS, None, {}),
                    Span("engine.prefill", tick + 4, tick, at, at + 2 * MS,
                         None, {})]
            at += 2 * MS
        out += [Span("engine.host_read", tick + 3, tick + 2, at + MS,
                     at + 5 * MS, None, {}),
                Span("engine.decode_step", tick + 2, tick, at, at + 6 * MS,
                     None, {}),
                Span("engine.iteration", tick, None, t, at + 8 * MS, None,
                     {"decoding": 2})]
        return out
    return it(1, 10, False) + it(11, 40, True)


class FakeCell:
    spans = [("window", 0.0, 0.1)]


def test_engine_self_and_host_read_on_a_made_up_ring(monkeypatch):
    monkeypatch.setattr(READER, "ring_spans", made_up_ring)
    self_ms = READER.read(cell=FakeCell, observed={}, trace=None, spec=spec_of(
        "engine_self_ms.batch", "unproven/metrics"))
    # 9 - (1 + 6) and 11 - (1 + 2 + 6): 2 ms each
    assert self_ms == pytest.approx(2.0)
    read_ms = READER.read(cell=FakeCell, observed={}, trace=None, spec=spec_of(
        "host_read_ms.batch", "unproven/metrics"))
    assert read_ms == pytest.approx((4.0 + 5.0) / 2)


def test_idle_in_span_share_on_a_made_up_trace(monkeypatch, capsys):
    monkeypatch.setattr(READER, "ring_spans", made_up_ring)
    # the trace's clock is 7 s ahead of the ring's; the device is busy
    # but for [12, 15] ms (inside the first host read, 11-15, and the
    # decode step that holds it), [30, 34] ms (outside every span), and
    # a gap of 1 ms at [60, 61]
    shift = 7e9
    busy = [("op", shift + a * MS, (b - a) * MS) for a, b in
            [(0, 12), (15, 30), (34, 60), (61, 100)]]
    trace = {"devices": {"/device:TPU:0": busy},
             "spans": [("chipbench.window", shift, 100.0 * MS)]}
    value = READER.read(cell=FakeCell, observed={}, trace=trace, spec=spec_of(
        "idle_in_span_share.batch", "unproven/metrics"))
    assert value == pytest.approx(100.0 * 3 / 8)
    said = capsys.readouterr().err
    assert "engine.host_read 37.50" in said and "none 50.00" in said
    assert "short 12.50" in said
