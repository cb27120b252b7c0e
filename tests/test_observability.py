"""Unified observability (ISSUE 15): deterministic request tracing,
failure flight recorder, and the one metrics registry.

The contract under test is the house discipline itself — counter
clocks, never wall clocks — so the assertions are BYTE equality:

- same seed + same fault plan (after a reset) ⇒ byte-identical
  ``Tracer.to_json()`` AND byte-identical flight-recorder JSON, across
  reruns — including the acceptance drill: a 2-replica routed run
  under a ``replica.health`` death plan whose postmortem names the
  dead replica, the requeued requests, and their reset/re-dispatch
  events;
- tracing DISABLED ⇒ zero spans and engine streams bit-identical to
  the traced run (observability never perturbs streams);
- tracing adds ZERO compiled programs (compile-ledger delta);
- every declared fault site fires its registered ``fault.<site>``
  event (the matrix over ``faults.SITES``), and the O001 ``obs_check``
  pass red-teams the coverage cross-check.

Tiny single-purpose engines (1-layer LM, single-device mesh) keep the
matrix cheap; the invariants live in event streams and counters, not
model size."""

import json
import os
import tempfile

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import nd
from mxtpu.analysis import check_observability, get_ledger
from mxtpu.models.transformer import TransformerLM, \
    transformer_lm_sharding_rules
from mxtpu.observability import (EVENT_TYPES, MetricsRegistry,
                                 export_chrome_trace, flight_recording,
                                 get_flight, get_registry, get_tracer,
                                 tracing)
from mxtpu.parallel import ContinuousBatchingEngine, \
    PagedContinuousBatchingEngine
from mxtpu.parallel.mesh import DeviceMesh
from mxtpu.resilience import fault_plan
from mxtpu.resilience.faults import SITES, inject


@pytest.fixture(scope="module")
def micro_lm():
    mx.random.seed(7)
    lm = TransformerLM(32, units=16, hidden_size=32, num_layers=1,
                       num_heads=2, num_kv_heads=2)
    lm.initialize()
    return lm


@pytest.fixture(scope="module")
def mesh():
    return DeviceMesh(dp=1)


@pytest.fixture(scope="module")
def rules():
    return transformer_lm_sharding_rules()


def _paged_engine(lm, mesh, rules, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_length", 32)
    kw.setdefault("block_size", 8)
    kw.setdefault("prefill_chunk", 8)
    return PagedContinuousBatchingEngine(lm, mesh, rules, **kw)


def _prompts():
    rng = np.random.RandomState(0)
    shared = rng.randint(0, 32, (1, 11))
    pa = np.concatenate([shared, rng.randint(0, 32, (1, 6))], axis=1)
    pb = np.concatenate([shared, rng.randint(0, 32, (1, 4))], axis=1)
    return pa, pb


# ---------------------------------------------------------------- tracer


def test_tracer_off_by_default_emit_is_noop():
    tr = get_tracer()
    assert not tr.enabled
    assert tr.emit("engine.decode", rid="eng:0", pos=1) is None
    assert tr.events() == []


def test_emit_unknown_event_type_raises():
    with tracing() as tr:
        with pytest.raises(ValueError, match="unregistered trace event"):
            tr.emit("engine.decoed", rid="eng:0")


def test_tracing_context_restores_prior_state():
    assert not get_tracer().enabled
    with tracing() as tr:
        assert tr.enabled
        with tracing():         # nested: stays enabled afterwards
            pass
        assert tr.enabled
    assert not get_tracer().enabled


def test_span_pairs_and_alias_resolution():
    with tracing() as tr:
        tr.alias("eng:0", "gw:5")
        with tr.span("engine.iteration", tag="eng"):
            tr.emit("engine.decode", rid="eng:0", pos=3)
        evs = tr.events()
        assert [e.phase for e in evs] == ["B", "I", "E"]
        assert [e.tick for e in evs] == [1, 2, 3]
        # the aliased rid resolved at record time
        assert evs[1].rid == "gw:5"
        assert tr.timeline("gw:5") == [evs[1]]
        assert tr.timeline("eng:0") == [evs[1]]   # query resolves too
        assert tr.span_count() == 1


def test_fault_site_event_matrix():
    """Every DECLARED site's firing lands in the trace under its
    registered ``fault.<site>`` type — raise and delay actions alike
    (the satellite matrix over ``faults.SITES``)."""
    for site in SITES:
        etype = "fault." + site
        assert etype in EVENT_TYPES     # the O001 invariant, directly
        with tracing() as tr:
            with fault_plan("%s@1:raise" % site):
                with pytest.raises(Exception):
                    inject(site, key=1)
            evs = tr.events(types=etype)
            assert len(evs) == 1, site
            assert evs[0].fields["site"] == site
            assert evs[0].fields["action"] == "raise"
            assert evs[0].fields["key"] == "1"
    # delay action, one representative site (no real sleep)
    with tracing() as tr:
        with fault_plan("serving.step@1:delay=0.5", sleep=lambda s: None):
            inject("serving.step", key=9)
        (ev,) = tr.events(types="fault.serving.step")
        assert ev.fields["action"] == "delay"


def test_fault_event_unregistered_site_downgrades():
    with tracing() as tr:
        with fault_plan("tests.private.site@1:raise"):
            with pytest.raises(Exception):
                inject("tests.private.site")
        (ev,) = tr.events(types="fault.unregistered")
        assert ev.fields["site"] == "tests.private.site"


# -------------------------------------------------------- flight recorder


def test_flight_ring_buffer_bounds():
    with tracing(), flight_recording(buffer=4) as fl:
        tr = get_tracer()
        for i in range(10):
            tr.emit("engine.decode", rid="eng:0", pos=i)
        tl = fl.timeline("eng:0")
        assert len(tl) == 4
        assert [e.fields["pos"] for e in tl] == [6, 7, 8, 9]


def test_flight_failure_inactive_is_noop():
    fl = get_flight()
    assert not fl.active
    assert fl.failure("quarantine", rids=("eng:0",)) is None


def test_flight_recording_restores_ambient_state():
    """A scoped flight_recording() inside a process running with the
    ambient recorder (MXTPU_FLIGHT_BUFFER) must restore BOTH the
    attached state and the buffer size on exit — not switch the
    always-on postmortem capture off for the rest of the process."""
    fl = get_flight()
    assert not fl.active
    prev_buffer = fl.buffer
    try:
        fl.enable(buffer=96, reset=True)      # simulate ambient
        with flight_recording(buffer=8) as scoped:
            assert scoped is fl and fl.buffer == 8
        assert fl.active and fl.buffer == 96
    finally:
        fl.disable()
        fl._buffer = prev_buffer
    # and when it was off, it stays off with its size untouched
    fl2_buffer = fl.buffer
    with flight_recording(buffer=8):
        pass
    assert not fl.active and fl.buffer == fl2_buffer


def test_ambient_flight_buffer_import_order(tmp_path):
    """MXTPU_FLIGHT_BUFFER arms the recorder at import regardless of
    which package is imported first: the module-level construction
    takes its counters baseline without importing mxtpu.resilience
    (which imports this module back — the circular-import regression),
    and a later failure still reads a correct counters delta."""
    import subprocess
    import sys as _sys
    code = (
        "from mxtpu.observability import get_flight\n"
        "fl = get_flight()\n"
        "assert fl.active and fl.buffer == 48, (fl.active, fl.buffer)\n"
        "from mxtpu.resilience.counters import bump\n"
        "bump('probe_counter', 3)\n"
        "pm = fl.failure('shed', context='bootstrap-probe')\n"
        "assert pm.counters == {'probe_counter': 3}, pm.counters\n"
    )
    env = dict(os.environ, MXTPU_FLIGHT_BUFFER="48",
               JAX_PLATFORMS="cpu")
    r = subprocess.run([_sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]


def test_alias_map_bounded():
    """One alias lands per submitted request; in the always-on posture
    (ambient flight recorder, tracer never reset) the map must stay
    bounded — oldest-registered evicted past MAX_ALIASES."""
    from mxtpu.observability.trace import MAX_ALIASES
    with tracing() as tr:
        for i in range(MAX_ALIASES + 7):
            tr.alias("eng:%d" % i, "gw:%d" % i)
        assert len(tr._alias) == MAX_ALIASES
        assert tr.resolve("eng:0") == "eng:0"           # evicted
        newest = MAX_ALIASES + 6
        assert tr.resolve("eng:%d" % newest) == "gw:%d" % newest
        # re-registering an existing child never evicts
        tr.alias("eng:%d" % newest, "gw:%d" % newest)
        assert len(tr._alias) == MAX_ALIASES


def test_ckpt_corruption_flight_postmortem(tmp_path):
    from mxtpu.resilience import checkpoint as ckpt

    with flight_recording(buffer=16) as fl:
        cs = ckpt.CheckpointSet(str(tmp_path), keep=3)
        cs.save(0, b"good-0")
        cs.save(1, b"good-1")
        buf = bytearray(open(cs.path(1), "rb").read())
        buf[0] ^= 0xFF
        open(cs.path(1), "wb").write(bytes(buf))
        assert cs.latest_verified() == (0, b"good-0")
        (pm,) = fl.postmortems
        assert pm.kind == "ckpt_corruption"
        assert pm.context["step"] == 1
        assert pm.context["file"] == os.path.basename(cs.path(1))
        # counters delta carries the detection
        assert pm.counters.get("ckpt_corruptions") == 1


# --------------------------------------------------------- engine traces


def test_timeline_covers_request_path(micro_lm, mesh, rules):
    """One shared-prefix pair on the paged engine: the second request's
    timeline carries admission → prefix hit → COW → prefill chunk →
    decode → finish, in tick order."""
    pa, pb = _prompts()
    eng = _paged_engine(micro_lm, mesh, rules)
    with tracing() as tr:
        eng.submit(nd.array(pa, dtype="int32"), 3)
        for _ in range(3):
            eng.step()          # register A's pages
        rb = eng.submit(nd.array(pb, dtype="int32"), 3)
        eng.run()
        tl = tr.timeline("eng:%d" % rb)
        kinds = [e.etype for e in tl]
        for k in ("engine.admit", "engine.prefix_hit", "engine.cow",
                  "engine.prefill_chunk", "engine.decode",
                  "engine.finish"):
            assert k in kinds, kinds
        assert [e.tick for e in tl] == sorted(e.tick for e in tl)
        hit = next(e for e in tl if e.etype == "engine.prefix_hit")
        # 8 tokens from the full shared page + 3 via the COW donor edge
        assert hit.fields["tokens"] == 11
        assert hit.fields["pages"] == 1
        fin = next(e for e in tl if e.etype == "engine.finish")
        assert fin.fields["status"] == "ok"
        # spans recorded around every scheduler iteration
        assert tr.span_count() > 0


def test_trace_and_flight_deterministic_bytes(micro_lm, mesh, rules):
    """Same seed + same fault plan ⇒ byte-identical trace JSON and
    flight JSON across reruns (the tick clock, alias map, and counter
    baselines all reset with the contexts)."""
    pa, pb = _prompts()

    def run_once():
        eng = _paged_engine(micro_lm, mesh, rules)
        with tracing() as tr, flight_recording(64) as fl:
            with fault_plan("serving.step@3:raise=RuntimeError(boom)"):
                eng.submit(nd.array(pa, dtype="int32"), 3, seed=5,
                           temperature=0.7)
                eng.submit(nd.array(pb, dtype="int32"), 3, retries=1)
                eng.run()
            return tr.to_json(), fl.to_json()

    t1, f1 = run_once()
    t2, f2 = run_once()
    assert t1 == t2
    assert f1 == f2
    rec = json.loads(f1)
    assert any(p["kind"] == "quarantine" for p in rec["postmortems"])


def test_tracer_off_streams_bit_exact_and_zero_extra_programs(
        micro_lm, mesh, rules):
    """The no-perturbation acceptance: the SAME engine serves the same
    workload untraced and traced — outputs bit-identical, zero new
    compiled programs while traced, zero events while untraced."""
    pa, pb = _prompts()
    eng = _paged_engine(micro_lm, mesh, rules)

    def run_once():
        r0 = eng.submit(nd.array(pa, dtype="int32"), 4, seed=3,
                        temperature=0.8)
        r1 = eng.submit(nd.array(pb, dtype="int32"), 4)
        out = eng.run()
        return out[r0].asnumpy(), out[r1].asnumpy()

    run_once()                          # compile warmup
    get_tracer().reset()                # drop prior tests' events
    base = run_once()                   # tracer OFF
    assert get_tracer().events() == []
    led = get_ledger()
    seq = led.sequence()
    with tracing() as tr:
        traced = run_once()             # tracer ON, same engine
        assert len(tr.events()) > 0
    assert len(led.misses_after(seq, sites=("serving.*",))) == 0
    assert np.array_equal(base[0], traced[0])
    assert np.array_equal(base[1], traced[1])


def test_chrome_export_golden_shape(micro_lm, mesh, rules):
    pa, _ = _prompts()
    eng = _paged_engine(micro_lm, mesh, rules)
    from mxtpu import profiler
    with tracing() as tr:
        eng.submit(nd.array(pa, dtype="int32"), 2)
        eng.run()
        profiler.Marker("golden_marker").mark()
        text = export_chrome_trace()
    doc = json.loads(text)
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and evs
    for ev in evs:
        assert set(("name", "ph", "ts", "pid", "tid")) <= set(ev)
        assert ev["ph"] in ("B", "E", "i", "X", "C")
        if ev["ph"] == "i":
            assert ev["s"] == "t"
    # span begin/end balanced per (name, tid)
    opens = {}
    for ev in evs:
        key = (ev["name"], ev["tid"])
        if ev["ph"] == "B":
            opens[key] = opens.get(key, 0) + 1
        elif ev["ph"] == "E":
            opens[key] -= 1
    assert all(v == 0 for v in opens.values()), opens
    # the profiler Marker rode the same writer
    assert any(e["name"] == "golden_marker" for e in evs)
    # file form writes the identical bytes
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "t.json")
        export_chrome_trace(p, tracer=tr)
        assert json.loads(open(p).read())["traceEvents"] == evs


# ----------------------------------------------- acceptance: replica death


def test_replica_death_postmortem_deterministic_and_complete(
        micro_lm, mesh, rules):
    """ISSUE 15 acceptance: a faulted 2-replica routed run (1-in-N
    ``replica.health`` death plan, probation revival — the
    ``_bench_router`` shape) produces a flight postmortem that is
    byte-identical across reruns, names the dead replica and the
    requeued requests, whose timelines carry the requeue ("reset") and
    re-dispatch events — and tracing adds ZERO compiled programs vs
    the identical untraced run."""
    from mxtpu.serving import Gateway, replica_pool

    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 32, (1, 9)) for _ in range(3)]
    led = get_ledger()

    def build():
        return Gateway(replica_pool(
            lambda i: _paged_engine(micro_lm, mesh, rules), n=2),
            fail_threshold=1, revive_after_ticks=8,
            hedge_fraction=None)

    def drive(gw):
        rids = [gw.submit(nd.array(p, dtype="int32"), 4, seed=i,
                          temperature=0.6)
                for i, p in enumerate(prompts)]
        return rids, gw.run()

    plan = "replica.health#r0@3:raise=OSError(drill)"

    # arm 0: untraced (the compile-count and stream reference)
    seq = led.sequence()
    gw0 = build()
    with fault_plan(plan):
        rids0, res0 = drive(gw0)
    untraced = len(led.misses_after(seq, sites=("serving.*",)))

    def run_traced():
        gw = build()
        seq = led.sequence()
        with tracing() as tr, flight_recording(128) as fl:
            with fault_plan(plan):
                rids, res = drive(gw)
            compiles = len(led.misses_after(seq, sites=("serving.*",)))
            pms = [p for p in fl.postmortems
                   if p.kind == "replica_death"]
            assert len(pms) == 1
            pm = pms[0]
            record = fl.postmortem_record(pm)
            return (gw, rids, res, pm, record, fl.to_json(),
                    compiles)

    gw1, rids1, res1, pm, record, fjson1, compiles1 = run_traced()
    # deaths happened and streams survived identical to the untraced arm
    assert gw1.stats["supervisor"]["deaths"] == 1
    for ra, rb in zip(rids0, rids1):
        assert np.array_equal(res0[ra].asnumpy(), res1[rb].asnumpy())
    # tracing compiled NOTHING beyond what the untraced arm compiled
    assert compiles1 == untraced

    # the postmortem names the dead replica and the drained requests
    assert pm.context["replica"] == "r0"
    assert len(pm.rids) >= 1
    for rid in pm.rids:
        tl = record["requests"][rid]
        kinds = [e["type"] for e in tl]
        # the death tick splits history from recovery: the requeue
        # (stream reset) and the re-dispatch both present
        assert "gateway.requeue" in kinds
        redispatch = [e for e in tl
                      if e["type"] == "gateway.dispatch"
                      and e["tick"] > pm.tick]
        assert redispatch, kinds

    # rerun: byte-identical flight record
    _, _, _, _, _, fjson2, _ = run_traced()
    assert fjson1 == fjson2


# --------------------------------------------------------------- guardian


def test_guardian_events_and_rollback_postmortem(tmp_path):
    from mxtpu import gluon
    from mxtpu.gluon import nn
    from mxtpu.parallel import SPMDTrainer
    from mxtpu.resilience.guardian import Guardian

    mx.random.seed(3)
    net = nn.Dense(4, in_units=8, prefix="obs_g_")
    net.initialize()
    tr_ = SPMDTrainer(net, gluon.loss.L2Loss(), "sgd", DeviceMesh(dp=1),
                      optimizer_params={"learning_rate": 1e-2},
                      guard=True)
    R = np.random.RandomState(0)
    data = [(R.randn(4, 8).astype(np.float32),
             R.randn(4, 4).astype(np.float32)) for _ in range(6)]

    def data_fn(step):
        d, l = data[step % len(data)]
        return mx.nd.array(d), mx.nd.array(l)

    g = Guardian(str(tmp_path), max_skips=1, checkpoint_every=100)
    with tracing() as trc, flight_recording(64) as fl:
        with fault_plan("guardian.check#3@1:raise"):
            g.run(tr_, data_fn, num_steps=6)
        kinds = [e.etype for e in trc.timeline("train")]
        assert "guardian.checkpoint" in kinds    # the baseline save
        assert "guardian.rollback" in kinds
        assert "fault.guardian.check" in [e.etype for e in trc.events()]
        pms = [p for p in fl.postmortems if p.kind == "guardian_rollback"]
        assert len(pms) == 1
        assert pms[0].context["restored_step"] == 0
        assert pms[0].counters.get("guardian_rollbacks") == 1


# ------------------------------------------------------- metrics registry


def test_registry_flatten_snapshot_and_delta():
    reg = MetricsRegistry()
    reg.register_source("a", lambda: {"x": 1, "nested": {"y": 2.5,
                                                         "flag": True},
                                      "skip": "str",
                                      "bad": {3: 4}})
    snap = reg.snapshot()
    assert snap == {"a.x": 1, "a.nested.y": 2.5, "a.nested.flag": 1}
    reg.register_source("a", lambda: {"x": 4, "nested": {"y": 2.5}},
                        replace=True)
    assert reg.delta(snap) == {"a.x": 3}
    assert reg.delta(snap, include_zero=True)["a.nested.y"] == 0


def test_registry_register_stats_and_prometheus(micro_lm, mesh, rules):
    pa, _ = _prompts()
    eng = _paged_engine(micro_lm, mesh, rules)
    reg = MetricsRegistry()
    reg.register_stats("engine0", eng)
    before = reg.snapshot()
    eng.submit(nd.array(pa, dtype="int32"), 3)
    eng.run()
    d = reg.delta(before)
    # 2 decode-step tokens: the first of the 3 emitted tokens samples
    # at prefill completion (generated_tokens counts decode steps)
    assert d["engine0.generated_tokens"] == 2
    assert d["engine0.steps"] > 0
    prom = reg.to_prometheus()
    assert "# TYPE mxtpu_engine0_generated_tokens gauge" in prom
    assert "mxtpu_engine0_generated_tokens 2" in prom
    parsed = json.loads(reg.to_json())
    assert parsed["engine0.generated_tokens"] == 2
    reg.unregister("engine0")
    assert reg.sources() == []


def test_registry_source_errors_and_misuse():
    reg = MetricsRegistry()
    reg.register_source("boom", lambda: 1 / 0)
    assert reg.snapshot() == {"boom.source_error": 1}
    with pytest.raises(ValueError, match="already registered"):
        reg.register_source("boom", dict)
    with pytest.raises(TypeError):
        reg.register_source("x", 42)
    with pytest.raises(TypeError):
        reg.register_stats("y", object())
    with pytest.raises(KeyError):
        reg.snapshot(sources=("nope",))


def test_process_registry_builtin_sources():
    reg = get_registry()
    assert {"resilience", "compile_ledger", "engine_bulk", "profiler",
            "tracer", "flight"} <= set(reg.sources())
    snap = reg.snapshot(sources=("resilience", "tracer", "flight"))
    assert "resilience.quarantined_slots" in snap
    assert "tracer.events" in snap
    assert "flight.postmortems" in snap
    # ledger sites flatten to <site>.programs (the O001 key shape)
    led_snap = reg.snapshot(sources=("compile_ledger",))
    for site in get_ledger().sites():
        assert "compile_ledger.%s.programs" % site in led_snap


# ----------------------------------------------- stats key normalization


def test_engine_and_gateway_stats_key_normalization(micro_lm, mesh,
                                                    rules):
    """The deprecated alias spellings are gone for good: every stats
    surface exposes ONLY the canonical ``*_requests``/``*_blocks``
    names, so no first-party reader can silently keep leaning on a
    removed key."""
    from mxtpu.serving import Gateway, replica_pool

    eng = ContinuousBatchingEngine(micro_lm, mesh, rules, num_slots=2,
                                   max_length=32)
    st = eng.stats
    for old, new in (("tokens_generated", "generated_tokens"),
                     ("quarantined", "quarantined_requests"),
                     ("retries", "retried_requests"),
                     ("deadline_evictions", "expired_requests"),
                     ("shed", "shed_requests")):
        assert old not in st, old
        assert new in st, new
    pst = _paged_engine(micro_lm, mesh, rules).stats
    for old, new in (("prefix_hits", "prefix_hit_requests"),
                     ("cow_copies", "cow_copied_blocks"),
                     ("swap_ins", "swapped_in_blocks"),
                     ("swap_outs", "swapped_out_blocks"),
                     ("deferred_swap_ins", "deferred_swap_in_requests"),
                     ("session_hits", "session_hit_requests")):
        assert old not in pst, old
        assert new in pst, new
    gw = Gateway(replica_pool(
        lambda i: _paged_engine(micro_lm, mesh, rules), n=1))
    gst = gw.stats
    for old, new in (("qos_sheds", "qos_shed_requests"),
                     ("engine_sheds", "engine_shed_requests"),
                     ("hedges", "hedged_requests")):
        assert old not in gst, old
        assert new in gst, new


# ----------------------------------------------------------- obs_check


def test_obs_check_clean_on_live_state():
    rep = check_observability()
    assert len(rep.filter(code="O001")) == 0, str(rep)
    assert rep.ok


def test_obs_check_red_team_unregistered_site():
    rep = check_observability(sites=("made.up.site",))
    o1 = rep.filter(code="O001").diagnostics
    assert len(o1) == 1
    assert o1[0].subject == "made.up.site"
    assert "fault.made.up.site" in o1[0].message


def test_obs_check_red_team_registry_losses():
    # a registry stripped of the compile_ledger source entirely
    rep = check_observability(registry=MetricsRegistry())
    assert any(d.subject == "compile_ledger"
               for d in rep.filter(code="O001"))
    # a filtering replacement that drops a recorded site
    led = get_ledger()
    if led.sites():
        lost = led.sites()[0]
        reg = MetricsRegistry()
        reg.register_source(
            "compile_ledger",
            lambda: {s: {"programs": 1}
                     for s in led.sites() if s != lost})
        rep = check_observability(registry=reg)
        assert any(d.subject == lost for d in rep.filter(code="O001"))


def test_obs_check_registered_in_cli_gate():
    from mxtpu.analysis import list_passes
    from mxtpu.analysis.__main__ import _SELF_APPLY

    assert "obs_check" in list_passes()
    assert "obs_check" in _SELF_APPLY


# ------------------------------------------------------- profiler parity


def test_profiler_set_config_warns_on_unknown_key():
    from mxtpu import profiler

    with pytest.warns(UserWarning, match="profile_al"):
        profiler.set_config(profile_al=True)
    with pytest.warns(UserWarning, match="did you mean"):
        profiler.set_config(agregate_stats=True)
    # known keys configure silently (and typos did NOT land)
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        profiler.set_config(aggregate_stats=True)
    assert "profile_al" not in profiler._config


def test_profiler_counters_markers_serve_through_registry():
    from mxtpu import profiler

    c = profiler.Counter("obs_test_counter", value=2)
    c.increment(3)
    assert profiler.counter_values()["obs_test_counter"] == 5
    snap = get_registry().snapshot(sources=("profiler",))
    assert snap["profiler.obs_test_counter"] == 5
    # dumps() aggregates through the registry + the tracer channel
    with profiler.Event("obs_test_scope"):
        pass
    text = profiler.dumps(reset=True)
    assert "obs_test_counter" in text
    assert "obs_test_scope" in text
    assert get_tracer().profiler_events() == []     # reset consumed them
    # with tracing active, Counter/Marker land in the structured trace
    with tracing() as tr:
        c.increment()
        profiler.Marker("obs_test_marker").mark()
        types = [e.etype for e in tr.events()]
        assert "profiler.counter" in types
        assert "profiler.marker" in types


# ------------------------------- the wall clock and the boundary ring (PR 27)


def test_t_ns_on_every_event_monotone_and_out_of_the_bytes(micro_lm, mesh,
                                                           rules):
    """Every event carries ``t_ns`` (perf_counter_ns, so it never runs
    backwards in tick order); the deterministic serialization holds
    neither it nor ``parent``, and byte identity under a fault plan
    holds with both recorded."""
    pa, pb = _prompts()

    def run_once():
        eng = _paged_engine(micro_lm, mesh, rules)
        with tracing() as tr:
            with fault_plan("serving.step@3:raise=RuntimeError(boom)"):
                eng.submit(nd.array(pa, dtype="int32"), 3)
                eng.submit(nd.array(pb, dtype="int32"), 3, retries=1)
                eng.run()
            return tr.events(), tr.to_json(), tr.to_json(include_noise=True)

    evs, plain, noisy = run_once()
    assert evs and all(e.t_ns > 0 for e in evs)
    assert [e.t_ns for e in evs] == sorted(e.t_ns for e in evs)
    assert "t_ns" not in plain and '"parent"' not in plain
    recs = json.loads(noisy)["events"]
    assert all("t_ns" in r for r in recs)
    assert any("parent" in r for r in recs)
    assert run_once()[1] == plain           # bytes: same seeds, same plan
    # an instant's parent is the span open on its thread
    begins = {e.tick for e in evs if e.phase == "B"}
    inner = [e for e in evs if e.etype == "engine.decode"]
    assert inner and all(e.parent in begins for e in inner)


def test_boundary_ring_fills_with_the_tracer_off(micro_lm, mesh, rules):
    """Boundary spans are kept with the tracer off (``events()`` stays
    empty), parents link host_read -> decode_step -> iteration, and the
    iteration ends carry counts that add up to ``engine.stats``."""
    from mxtpu.observability.trace import BOUNDARY_TYPES

    assert BOUNDARY_TYPES <= set(EVENT_TYPES)
    pa, pb = _prompts()
    tr = get_tracer()
    tr.reset()
    assert not tr.active
    eng = _paged_engine(micro_lm, mesh, rules)
    # eos ids that never match: every decoding slot pays its host read
    eng.submit(nd.array(pa, dtype="int32"), 4, eos_id=10 ** 6)
    eng.submit(nd.array(pb, dtype="int32"), 3, eos_id=10 ** 6)
    eng.run()
    assert tr.events() == []
    ring = tr.boundary_spans()
    by_tick = {s.tick: s for s in ring}
    its = [s for s in ring if s.etype == "engine.iteration"]
    assert its and all(s.parent is None and s.end_ns >= s.start_ns
                       for s in its)
    assert tr.boundary_spans("engine.iteration") == its
    reads = [s for s in ring if s.etype == "engine.host_read"]
    assert reads
    for r in reads:     # under the phase that asked (a prompt's last
        phase = by_tick[r.parent]   # chunk checks eos too)
        assert phase.etype in ("engine.decode_step", "engine.prefill")
        assert phase.start_ns <= r.start_ns and r.end_ns <= phase.end_ns
    in_decode = [by_tick[r.parent] for r in reads
                 if by_tick[r.parent].etype == "engine.decode_step"]
    assert in_decode and all(by_tick[p.parent].etype == "engine.iteration"
                             for p in in_decode)
    kinds = {s.etype for s in ring if by_tick.get(s.parent) in its}
    assert kinds == {"engine.schedule", "engine.prefill",
                     "engine.decode_step"}
    # an admission's first chunk is prefill work inside the schedule
    assert any(s.etype == "engine.prefill"
               and by_tick[s.parent].etype == "engine.schedule"
               for s in ring)
    st = eng.stats
    assert sum(s.fields["tokens"] for s in its) == st["generated_tokens"]
    assert sum(s.fields["decoding"] for s in its) == st["slot_iterations"]
    assert sum(s.fields["prefill_tokens"] for s in its) \
        == st["prefill_tokens"] == pa.shape[1] + pb.shape[1] \
        - st["prefill_tokens_avoided"]
    assert sum(1 for s in its if s.fields["decoding"]) == st["steps"]
    assert its[-1].fields["waiting"] == 0
    assert all(s.fields["tag"] == "eng" for s in its)
    assert {"decoding", "prefilling", "waiting", "tokens",
            "prefill_tokens", "step"} <= set(its[0].fields)


def test_iteration_end_event_carries_the_counts(micro_lm, mesh, rules):
    """With the tracer on the same counts ride on the end event, at the
    same boundary, and the ring holds the same spans."""
    pa, _ = _prompts()
    eng = _paged_engine(micro_lm, mesh, rules)
    with tracing() as tr:
        eng.submit(nd.array(pa, dtype="int32"), 3)
        eng.run()
        ends = [e for e in tr.events(types="engine.iteration")
                if e.phase == "E"]
        its = tr.boundary_spans("engine.iteration")
    assert len(ends) == len(its) > 0
    for e, s in zip(ends, its):
        assert e.fields == {k: v for k, v in s.fields.items()
                            if k not in ("tag", "step")}
        assert e.t_ns == s.end_ns
    assert sum(e.fields["tokens"] for e in ends) \
        == eng.stats["generated_tokens"]


def test_gateway_pump_is_a_boundary_span(micro_lm, mesh, rules):
    from mxtpu.serving import Gateway, replica_pool

    pa, _ = _prompts()
    tr = get_tracer()
    tr.reset()
    gw = Gateway(replica_pool(
        lambda i: _paged_engine(micro_lm, mesh, rules), n=1))
    gw.submit(nd.array(pa, dtype="int32"), 2)
    gw.run()
    assert tr.events() == []
    pumps = tr.boundary_spans("gateway.pump")
    assert len(pumps) == gw.stats["ticks"] > 0
    ticks = {p.tick for p in pumps}
    its = tr.boundary_spans("engine.iteration")
    assert its and all(s.parent in ticks for s in its)


def _toy_trainer():
    from mxtpu import gluon
    from mxtpu.gluon import nn
    from mxtpu.parallel import SPMDTrainer

    mx.random.seed(11)
    net = nn.Dense(16, in_units=8)
    net.initialize()
    return SPMDTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                       DeviceMesh(dp=1), optimizer_params={
                           "learning_rate": 0.1}, guard=False)


def _toy_batch(rows):
    rng = np.random.RandomState(rows)
    return (mx.nd.array(rng.rand(rows, 8).astype(np.float32)),
            mx.nd.array(rng.randint(0, 16, (rows,)).astype(np.float32)))


def test_trainer_spans_stage_first_and_steps():
    """A toy ``SPMDTrainer`` on the CPU: one ``trainer.stage``, inside
    the first ``trainer.step``; ``first`` true once per batch signature;
    N steps give N spans; the step that compiled is the parent of its
    ``xla.compile`` spans — all with the tracer off."""
    tr = get_tracer()
    tr.reset()
    trainer = _toy_trainer()
    for rows in (8, 8, 8, 4, 8, 4):
        trainer.step(*_toy_batch(rows))
    assert tr.events() == []
    steps = tr.boundary_spans("trainer.step")
    assert [s.fields["step"] for s in steps] == [1, 2, 3, 4, 5, 6]
    assert [s.fields["first"] for s in steps] == [True, False, False, True,
                                                  False, False]
    assert [s.fields["tokens"] for s in steps] == [64, 64, 64, 32, 64, 32]
    stage = tr.boundary_spans("trainer.stage")
    assert len(stage) == 1 and stage[0].parent == steps[0].tick
    assert steps[0].start_ns <= stage[0].start_ns <= stage[0].end_ns \
        <= steps[0].end_ns
    compiled = {}
    for s in tr.boundary_spans("xla.compile"):
        assert s.tick is None and s.fields["kind"] in (
            "trace", "lower", "compile", "cache_fetch")
        assert s.seconds == pytest.approx(s.fields["seconds"], abs=1e-6)
        compiled.setdefault(s.parent, set()).add(s.fields["kind"])
    # the two signatures' steps traced, lowered and compiled; no other did
    for s in steps:
        kinds = compiled.get(s.tick, set())
        if s.fields["first"]:
            assert {"trace", "lower", "compile"} <= kinds
        else:
            assert not kinds
    assert compiled.get(stage[0].tick)      # the eager forward's programs


def test_step_window_is_one_trainer_step_span():
    tr = get_tracer()
    trainer = _toy_trainer()
    x, y = _toy_batch(8)
    trainer.step(x, y)
    tr.reset()
    xs = mx.nd.array(np.stack([x.asnumpy()] * 3))
    ys = mx.nd.array(np.stack([y.asnumpy()] * 3))
    trainer.step_window(xs, ys)
    trainer.step_window(xs, ys)
    spans = tr.boundary_spans("trainer.step")
    assert [(s.fields["first"], s.fields["steps"], s.fields["step"],
             s.fields["tokens"]) for s in spans] == [
        (True, 3, 4, 192), (False, 3, 7, 192)]
    assert tr.boundary_spans("trainer.stage") == []


def test_fresh_jit_is_an_xla_compile_under_the_open_span():
    import jax
    import jax.numpy as jnp

    tr = get_tracer()
    tr.reset()
    with tr.span("trainer.step") as sp:
        jax.jit(lambda v: jnp.cos(v) * 5.0 - 2.0)(jnp.ones((7, 3)))
    jax.jit(lambda v: jnp.sin(v) * 7.0 - 3.0)(jnp.ones((7, 3)))
    spans = tr.boundary_spans()
    step = spans[[s.etype for s in spans].index("trainer.step")]
    mine = [s for s in spans if s.etype == "xla.compile"
            and s.parent == step.tick]
    assert {s.fields["kind"] for s in mine} >= {"trace", "lower", "compile"}
    assert all(step.start_ns <= s.start_ns and s.end_ns <= step.end_ns
               for s in mine if s.fields["kind"] != "trace")
    # one outside any span has no parent; none of them took a tick
    loose = [s for s in spans if s.etype == "xla.compile"
             and s.parent is None]
    assert {s.fields["kind"] for s in loose} >= {"trace", "lower", "compile"}
    assert tr.ticks == 1 and tr.events() == []


def test_ring_is_bounded_and_non_boundary_spans_stay_off():
    from mxtpu.observability.trace import MAX_BOUNDARY_SPANS, Tracer

    tr = Tracer(enabled=False)
    for _ in range(MAX_BOUNDARY_SPANS + 10):
        with tr.span("engine.host_read"):
            pass
    ring = tr.boundary_spans()
    assert len(ring) == MAX_BOUNDARY_SPANS
    assert ring[-1].tick == MAX_BOUNDARY_SPANS + 10     # the oldest went
    with tr.span("guardian.window"):        # not a boundary type: nothing
        pass
    assert len(tr.boundary_spans()) == MAX_BOUNDARY_SPANS
    assert tr.ticks == MAX_BOUNDARY_SPANS + 10
    assert tr.stats()["boundary_spans"] == MAX_BOUNDARY_SPANS


def test_boundary_span_lands_in_any_profiler_session(tmp_path):
    """A session started with ``jax.profiler.start_trace`` directly (the
    benchmark's way, not ``mxtpu.profiler``) holds the boundary spans as
    ``mxtpu.<type>`` annotations."""
    import glob

    import jax
    from jax.profiler import ProfileData

    tr = get_tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("trainer.step"):
            with tr.span("trainer.stage"):
                pass
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert paths
    names = {e.name for plane in ProfileData.from_file(paths[0]).planes
             for line in plane.lines for e in line.events}
    assert {"mxtpu.trainer.step", "mxtpu.trainer.stage"} <= names


def test_chrome_export_on_the_wall_clock_and_on_ticks(micro_lm, mesh, rules):
    """One writer, two timelines: ``t_ns`` in microseconds with
    ``include_noise``, ticks without (and then deterministic)."""
    pa, _ = _prompts()

    def run_once():
        eng = _paged_engine(micro_lm, mesh, rules)
        with tracing() as tr:
            eng.submit(nd.array(pa, dtype="int32"), 2)
            eng.run()
            return (tr.events(), export_chrome_trace(),
                    export_chrome_trace(include_noise=False))

    evs, wall, ticks = run_once()
    wall_ts = [e["ts"] for e in json.loads(wall)["traceEvents"]
               if e["cat"] == "mxtpu"]
    assert wall_ts == [e.t_ns / 1e3 for e in evs]
    tick_evs = [e for e in json.loads(ticks)["traceEvents"]
                if e["cat"] == "mxtpu"]
    assert [e["ts"] for e in tick_evs] == [e.tick for e in evs]
    assert "tick" in json.loads(ticks)["otherData"]["clock"]
    assert "perf_counter" in json.loads(wall)["otherData"]["clock"]


def test_flash_kernels_carry_their_names():
    """The lowered text (with debug info) of a flash forward and
    backward holds the two kernels' names, and not those of the two
    backward kernels the one replaced."""
    import importlib

    import jax
    import jax.numpy as jnp

    fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
    x = jnp.ones((1, 2, 128, 64), jnp.float32)
    text = jax.jit(jax.grad(
        lambda q, k, v: fa.flash_attention(q, k, v).sum(),
        argnums=(0, 1, 2))).lower(x, x, x).as_text(debug_info=True)
    for name in ("flash_attention_fwd", "flash_attention_bwd"):
        assert name in text, name
    for name in ("flash_attention_dq", "flash_attention_dkv"):
        assert name not in text, name


@pytest.mark.parametrize("module, name", [
    ("paged_attention", "paged_attention_decode"),
    ("prefill_attention", "paged_prefill_chunk"),
])
def test_paged_kernels_pass_their_names(module, name):
    import importlib
    import inspect

    src = inspect.getsource(importlib.import_module(
        "mxtpu.ops.pallas." + module))
    assert 'name="%s"' % name in src


# ------------------------------------------- set-up, told from inside (PR 37)

_FRESH = """
import json, sys
{before}
import mxtpu as mx
from mxtpu.observability import get_tracer
tr = get_tracer()
mx.gluon
first = [[s.etype, s.tick, s.parent, s.start_ns, s.end_ns, s.fields]
         for s in tr.boundary_spans()]
tr.reset()
kept = [[s.etype, s.fields] for s in tr.boundary_spans()]
print(json.dumps({{"first": first, "kept": kept, "ticks": tr.ticks}}))
"""


@pytest.mark.parametrize("before, jax_imported, backend_up", [
    ("", False, False),
    ("import jax; jax.devices()", True, True),
])
def test_process_start_and_imports_in_a_fresh_interpreter(
        before, jax_imported, backend_up):
    """One ``process.start`` that ends where the package's
    ``mxtpu.import`` begins (the first line of ``mxtpu/__init__.py``),
    saying what the caller had done by then; a lazy subpackage's import
    is a span with its ``module``, and what it imports lazily itself is
    its child; none takes a tick, and a reset keeps them all."""
    import subprocess
    import sys as _sys

    r = subprocess.run(
        [_sys.executable, "-c", _FRESH.format(before=before)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    said = json.loads(r.stdout.splitlines()[-1])
    spans = [s for s in said["first"] if s[0] != "xla.compile"]
    assert [s[0] for s in spans] == ["process.start"] + ["mxtpu.import"] * (
        len(spans) - 1)
    start, package = spans[0], spans[1]
    assert start[5] == {"jax_imported": jax_imported,
                        "backend_up": backend_up}
    assert package[5] == {"module": "mxtpu"}
    assert start[1] is None and start[2] is None and package[2] is None
    assert start[4] == package[3]               # one ends, the other begins
    assert start[3] < start[4] and package[3] < package[4]
    by_module = {s[5]["module"]: s for s in spans[1:]}
    gluon = by_module["mxtpu.gluon"]
    assert gluon[3] >= package[4] and gluon[4] > gluon[3]
    ids = [s[1] for s in spans[1:]]
    assert all(i < 0 for i in ids) and len(set(ids)) == len(ids)
    inner = [s for s in spans[1:] if s[2] == gluon[1]]
    assert inner and all(gluon[3] <= s[3] and s[4] <= gluon[4]
                         for s in inner)
    assert said["ticks"] == 0
    assert said["kept"] == [[s[0], s[5]] for s in spans]


def test_reset_keeps_the_spans_of_the_process_and_no_others():
    tr = get_tracer()
    with tr.span("trainer.step"):
        pass
    tr.reset()
    kinds = [s.etype for s in tr.boundary_spans()]
    assert kinds[0] == "process.start" and set(kinds[1:]) == {"mxtpu.import"}
    assert tr.boundary_spans("mxtpu.import")[0].fields == {"module": "mxtpu"}


def test_initialize_of_a_nested_block_is_one_span():
    from mxtpu.gluon import nn

    tr = get_tracer()
    tr.reset()
    net = nn.HybridSequential()
    inner = nn.HybridSequential()
    inner.add(nn.Dense(8, in_units=4), nn.Dense(2, in_units=8))
    net.add(inner, nn.Dense(3))         # the last one's weight is deferred
    net.initialize()
    spans = tr.boundary_spans("block.initialize")
    assert len(spans) == 1 and spans[0].parent is None
    # 4 x 8 + 8, 8 x 2 + 2 and the last layer's bias of 3, in float32
    assert spans[0].fields == {"params": 5, "bytes": 4 * (40 + 18 + 3)}
    # the eager programs it ran are its children
    assert any(s.parent == spans[0].tick
               for s in tr.boundary_spans("xla.compile"))
    assert tr.events() == []
    # a parameter at a time (the serving builder's way): a span each
    for p in net.collect_params().values():
        p.initialize(force_reinit=True)
    later = tr.boundary_spans("block.initialize")[1:]
    assert [s.fields["params"] for s in later] == [1, 1, 1, 1, 0, 1]
    # nothing open is left behind on this thread
    net.collect_params().initialize(force_reinit=True)
    assert tr.boundary_spans("block.initialize")[-1].fields["params"] == 5


def test_compile_spans_carry_the_programs_name_and_whether_fetched():
    import jax
    import jax.numpy as jnp

    from mxtpu.observability.trace import Tracer

    tr = get_tracer()
    tr.reset()

    def pr37_probe(v):
        return jnp.cos(v) * 5.0 - 37.0

    jax.jit(pr37_probe)(jnp.ones((7, 3)))
    mine = [s for s in tr.boundary_spans("xla.compile")
            if "pr37_probe" in (s.fields["name"] or "")]
    assert {s.fields["kind"] for s in mine} == {"trace", "lower", "compile"}
    compiled = [s for s in mine if s.fields["kind"] == "compile"]
    assert [s.fields["name"] for s in compiled] == ["jit(pr37_probe)"]
    assert [s.fields["name"] for s in mine if s.fields["kind"] == "lower"] \
        == ["jit(pr37_probe)"]
    assert compiled[0].fields["fetched"] is False
    assert all("name" in s.fields
               for s in tr.boundary_spans("xla.compile"))
    assert all("fetched" not in s.fields for s in mine
               if s.fields["kind"] != "compile")
    # the CPU tests keep no persistent cache: jax's own events, made up
    tr = Tracer(enabled=False)
    fetch = "/jax/compilation_cache/cache_retrieval_time_sec"
    compile_ = "/jax/core/compile/backend_compile_duration"
    tr.compile_seen(fetch, 0.001)               # jax hands a fetch no name
    tr.compile_seen(compile_, 0.002, fun_name="jit(step)")
    tr.compile_seen(compile_, 0.0005, fun_name="jit(other)")
    tr.compile_seen("/jax/compilation_cache/compile_time_saved_sec", 9.0)
    got = [(s.fields["kind"], s.fields["name"], s.fields.get("fetched"))
           for s in tr.boundary_spans()]
    assert got == [("cache_fetch", "jit(step)", None),
                   ("compile", "jit(step)", True),
                   ("compile", "jit(other)", False)]
    # another thread's fetch is not this thread's
    import threading
    t = threading.Thread(target=tr.compile_seen, args=(fetch, 0.001))
    t.start()
    t.join()
    tr.compile_seen(compile_, 10.0, fun_name="jit(mine)")
    assert tr.boundary_spans()[-1].fields["fetched"] is False


class _CountedMemory:
    """In ``SPMDTrainer._device_memory``'s place: counts its calls and
    answers as a chip's allocator would."""

    def __init__(self):
        self.calls = 0

    def __call__(self):
        self.calls += 1
        return {"bytes_in_use": 1000 + self.calls,
                "peak_bytes_in_use": 2000 + self.calls}


def test_memory_fields_after_staging_and_first_steps_only(monkeypatch):
    from mxtpu.parallel import SPMDTrainer

    # what the platform gives: two fields of a chip's, nothing of the CPU's
    class Chip:
        def memory_stats(self):
            return {"bytes_in_use": 5, "peak_bytes_in_use": 7,
                    "bytes_limit": 9}

    def on(device):
        mesh = type("M", (), {})()
        mesh.jax_mesh = type("J", (), {})()
        mesh.jax_mesh.local_devices = [device]
        return type("T", (), {"_mesh": mesh})()

    assert SPMDTrainer._device_memory(on(Chip())) == {
        "bytes_in_use": 5, "peak_bytes_in_use": 7}
    tr = get_tracer()
    tr.reset()
    trainer = _toy_trainer()
    for rows in (8, 8, 4):
        trainer.step(*_toy_batch(rows))
    spans = tr.boundary_spans(("trainer.stage", "trainer.step"))
    assert spans and not any("bytes_in_use" in s.fields for s in spans)

    memory = _CountedMemory()
    monkeypatch.setattr(SPMDTrainer, "_device_memory", lambda self: memory())
    with tracing() as tr:
        trainer = _toy_trainer()
        for rows in (8, 8, 4, 8):
            trainer.step(*_toy_batch(rows))
        steps = tr.boundary_spans("trainer.step")
        stage = tr.boundary_spans("trainer.stage")[0]
        assert memory.calls == 3            # staging and two first steps
        assert stage.fields == {"bytes_in_use": 1001,
                                "peak_bytes_in_use": 2001}
        assert [s.fields.get("bytes_in_use") for s in steps] == [
            1002, None, 1003, None]
        # noise by nature: on the end events beside the fields, and out
        # of the deterministic bytes
        ends = [e for e in tr.events(types="trainer.step")
                if e.phase == "E"]
        assert [e.noise.get("peak_bytes_in_use") for e in ends] == [
            2002, None, 2003, None]
        assert all("bytes_in_use" not in e.fields for e in ends)
        assert "bytes_in_use" not in tr.to_json()
        assert "bytes_in_use" in tr.to_json(include_noise=True)


def test_a_later_step_records_what_it_recorded_before(monkeypatch):
    """A ``trainer.step`` that is not ``first`` carries ``step``,
    ``first``, ``tokens`` (and ``steps`` of a window) and nothing else,
    and reads no memory."""
    from mxtpu.parallel import SPMDTrainer

    memory = _CountedMemory()
    monkeypatch.setattr(SPMDTrainer, "_device_memory", lambda self: memory())
    tr = get_tracer()
    trainer = _toy_trainer()
    x, y = _toy_batch(8)
    xs = mx.nd.array(np.stack([x.asnumpy()] * 3))
    ys = mx.nd.array(np.stack([y.asnumpy()] * 3))
    trainer.step(x, y)
    trainer.step_window(xs, ys)
    tr.reset()
    before = memory.calls
    trainer.step(x, y)
    trainer.step_window(xs, ys)
    trainer.step(x, y)
    assert memory.calls == before
    assert [s.fields for s in tr.boundary_spans("trainer.step")] == [
        {"first": False, "tokens": 64, "step": 5},
        {"first": False, "tokens": 192, "steps": 3, "step": 8},
        {"first": False, "tokens": 64, "step": 9}]


def test_the_event_cap_is_a_constant_and_no_knob(monkeypatch):
    from mxtpu.observability.trace import MAX_EVENTS, Tracer

    monkeypatch.setenv("MXTPU_TRACE_EVENTS", "3")
    tr = Tracer(enabled=True)
    for _ in range(5):
        tr.emit("engine.decode")
    assert len(tr.events()) == 5 and tr.dropped_events == 0
    assert tr._max_events == MAX_EVENTS == 200000
    tr = Tracer(max_events=3, enabled=True)
    for _ in range(5):
        tr.emit("engine.decode")
    assert len(tr.events()) == 3 and tr.dropped_events == 2
