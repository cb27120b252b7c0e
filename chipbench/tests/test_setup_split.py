"""The five metrics that tell ``setup_s`` from inside (PR 37):
``preimport_s``, ``import_s``, ``initialize_s``, ``backend_compile_s`` and
``setup_outside_s`` each read a number on the toy training cell, stepped
on the CPU inside a hand-made ``Cell`` window as ``test_program_span.py``
does; then ``compile_missed`` and ``setup_outside`` on made-up rings."""

import os
from collections import namedtuple

import jax
import pytest

from chipbench import harness

BASE = os.path.join(harness.HERE, "tests")
TRAIN = harness.load_module("runners", "train")
RING = harness.load_module("readers", "program_span")
METRICS = ["preimport_s", "import_s", "initialize_s", "backend_compile_s",
           "setup_outside_s"]
CELLS = [w["name"] for w in harness.load_json(
    harness.ROOT, "BENCHMARK.json")["workloads"]]


def spec_of(name):
    return harness.load_json(harness.HERE, "metrics", name + ".json")


def read(name, cell):
    spec = spec_of(name)
    reader = harness.load_module("readers", spec["reader"])
    return reader.read(cell=cell, spec=spec, observed={}, trace=None)


@pytest.fixture(scope="module")
def stepped():
    """A cell whose trainer was built and made its compared steps, then
    one more inside the window; the tracer is off, and reset first."""
    from mxtpu.observability import get_tracer

    tracer = get_tracer()
    assert not tracer.enabled
    tracer.reset()
    config = harness.load_json(BASE, "configs", "bert-tiny.json")
    traffic = harness.load_json(BASE, "traffic", "pretrain-tiny.json")
    cell = harness.Cell("bert-tiny.pretrain-tiny", {"chips": 1}, config,
                        traffic, BASE, 2 ** 31 + 7, 0.1, False,
                        jax.devices()[:1])
    reference = cell.module("references", config["reference"])
    generator = cell.module("generators", traffic["generator"])
    trainer, named = TRAIN.build(cell, reference, cell.seed)
    jax.block_until_ready(TRAIN.first_steps(
        cell, reference, generator, trainer, named, cell.seed))
    with cell.window():
        TRAIN.issue(trainer, *generator.train_batch(
            traffic, config, config["train"]["batch"], cell.seed,
            TRAIN.CHECK_STEPS)).block_until_ready()
    assert tracer.events() == []
    return cell


@pytest.mark.parametrize("name", METRICS)
def test_each_metric_lists_the_four_cells_and_reads_a_number(stepped, name):
    spec = spec_of(name)
    assert spec["workloads"] == CELLS and spec["source"] == "program_span"
    assert (spec["unit"], spec["better"], spec["moves"]) == (
        "s", "lower", "setup_s")
    value = read(name, stepped)
    assert isinstance(value, float) and value >= 0.0
    if name != "setup_outside_s":   # this process compiled: nothing cached
        assert value > 0.0


def test_the_parts_lie_inside_setup_s_and_add_up(stepped):
    spans = RING.ring_spans()
    lo = RING.window_ns(stepped)[0]
    start = [s for s in spans if s.etype == "process.start"]
    assert len(start) == 1          # the fixture's reset kept it
    # both ends agree: the kernel's clock and perf_counter, 10 ms ticks
    assert abs((lo - start[0].start_ns) / 1e9 - stepped.setup_s) < 0.2
    outside = read("setup_outside_s", stepped)
    parts = [read(n, stepped) for n in ("preimport_s", "import_s",
                                        "initialize_s")]
    assert 0.0 <= outside < stepped.setup_s
    assert sum(parts) + outside < stepped.setup_s + 0.2
    # the union of the ring before the window and what lies outside it
    # are the whole of setup_s, by construction and to the clocks' ticks
    covered = RING.covered_ns([s for s in spans if s.end_ns <= lo]) / 1e9
    assert covered + outside == pytest.approx(stepped.setup_s, abs=0.2)
    # this process compiled every program: the missed seconds are the
    # union of the compile spans, all of them said so
    compiles = [s for s in spans if s.etype == "xla.compile"
                and s.fields["kind"] == "compile" and s.end_ns <= lo]
    assert compiles and not any(s.fields["fetched"] for s in compiles)
    assert read("backend_compile_s", stepped) == pytest.approx(
        RING.covered_ns(compiles) / 1e9)


# ----------------------------------------------------------- made-up rings

Span = namedtuple("Span", "etype tick parent start_ns end_ns rid fields")
S = 1_000_000_000


class FakeCell:
    """Set-up of 20 s on a ring whose clock reads 100 s where the window
    opens: the process started at 80 s."""
    spans = [("window", 100.0, 130.0)]
    setup_s = 20.0

    @staticmethod
    def module(kind, name):
        return harness.load_module(kind, name)


def compile_span(t0, t1, **fields):
    return Span("xla.compile", None, None, t0 * S, t1 * S, None,
                dict(fields, kind=fields.get("kind", "compile"),
                     seconds=t1 - t0))


def made_up_ring():
    """process.start 80-84; the import 84-86 with a lazy one inside;
    initialize 88-90 holding a fetched compilation; a first step 92-99
    holding an 80 ms fetch inside its compile and, 93-98, a compilation
    the cache did not hold, with a second one overlapping it (another
    thread's, 97-98.5); a step that straddles the window's opening
    (99.5-100.5) and one inside the window."""
    return [
        Span("process.start", None, None, 80 * S, 84 * S, None,
             {"jax_imported": True, "backend_up": True}),
        Span("mxtpu.import", -2, -1, 85 * S, int(85.5 * S), None,
             {"module": "mxtpu.gluon"}),
        Span("mxtpu.import", -1, None, 84 * S, 86 * S, None,
             {"module": "mxtpu"}),
        compile_span(88.5, 89.0, kind="cache_fetch", name="jit(fill)"),
        compile_span(88.4, 89.1, name="jit(fill)", fetched=True),
        Span("block.initialize", 1, None, 88 * S, 90 * S, None,
             {"params": 3, "bytes": 48}),
        compile_span(92.5, 92.58, kind="cache_fetch", name="jit(norms)"),
        compile_span(92.4, 92.6, name="jit(norms)", fetched=True),
        compile_span(93.0, 98.0, name="jit(step)", fetched=False),
        compile_span(97.0, 98.5, name="jit(other)", fetched=False),
        Span("trainer.step", 2, None, 92 * S, 99 * S, None,
             {"step": 1, "first": True, "tokens": 8}),
        Span("trainer.step", 3, None, int(99.5 * S), int(100.5 * S), None,
             {"step": 2, "first": False, "tokens": 8}),
        Span("trainer.step", 4, None, 101 * S, 102 * S, None,
             {"step": 3, "first": False, "tokens": 8}),
    ]


def test_compile_missed_on_a_made_up_ring(monkeypatch):
    monkeypatch.setattr(RING, "ring_spans", made_up_ring)
    # 93-98 and 97-98.5 overlap: 5.5 s, counted once; the fetched ones
    # and the fetches themselves are not compilations
    assert read("backend_compile_s", FakeCell) == pytest.approx(5.5)
    warm = [s for s in made_up_ring() if s.fields.get("fetched") is not False]
    monkeypatch.setattr(RING, "ring_spans", lambda: warm)
    assert read("backend_compile_s", FakeCell) == 0.0   # a reading, not None
    # a program from before the field: its compile spans say nothing
    old = [s._replace(fields={k: v for k, v in s.fields.items()
                              if k not in ("fetched", "name")})
           for s in made_up_ring()]
    monkeypatch.setattr(RING, "ring_spans", lambda: old)
    assert read("backend_compile_s", FakeCell) is None
    # a compilation after the window opened is not set-up
    late = warm + [compile_span(110.0, 115.0, name="jit(ref)", fetched=False)]
    monkeypatch.setattr(RING, "ring_spans", lambda: late)
    assert read("backend_compile_s", FakeCell) == 0.0


def test_setup_outside_on_a_made_up_ring(monkeypatch):
    monkeypatch.setattr(RING, "ring_spans", made_up_ring)
    # covered: 80-86, 88-90, 92-99 and, of the straddling step, 99.5-100:
    # 15.5 s of the 20; overlapping and nested spans count once
    assert read("setup_outside_s", FakeCell) == pytest.approx(4.5)
    assert read("preimport_s", FakeCell) == pytest.approx(4.0)
    assert read("import_s", FakeCell) == pytest.approx(2.0)
    assert read("initialize_s", FakeCell) == pytest.approx(2.0)
    # a process.start that claims to begin before the kernel's record is
    # clipped to it; a span wholly after the opening counts for nothing
    early = made_up_ring()
    early[0] = early[0]._replace(start_ns=70 * S)
    monkeypatch.setattr(RING, "ring_spans", lambda: early)
    assert read("setup_outside_s", FakeCell) == pytest.approx(4.5)


def test_nothing_to_read_without_process_start(monkeypatch):
    ring = [s for s in made_up_ring() if s.etype != "process.start"]
    monkeypatch.setattr(RING, "ring_spans", lambda: ring)
    assert read("setup_outside_s", FakeCell) is None
    assert read("preimport_s", FakeCell) is None
    # a program that keeps no ring, and a cell whose window never opened
    monkeypatch.setattr(RING, "ring_spans", lambda: None)
    for name in METRICS:
        assert read(name, FakeCell) is None
    monkeypatch.setattr(RING, "ring_spans", made_up_ring)

    class Unopened(FakeCell):
        spans = []
        setup_s = None

    for name in METRICS:
        assert read(name, Unopened) is None
