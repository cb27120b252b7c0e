"""Cross-process replica serving: SubprocessReplica over real OS worker
processes (ISSUE 19 tentpole).

Every test here drives REAL spawned workers (one engine per process,
length-prefixed pipe RPC), so the whole module rides a probe-once skip:
the first test spawns the shared 2-worker pool and decodes one token;
if THAT fails (a host that cannot spawn Python subprocesses, or a
jaxlib that cannot initialize in a child), every test skips with the
probe's real failure detail instead of failing five times
(tests/test_dist_multiproc.py discipline).

Ordering matters and is relied on (tier-1 runs with ``-p no:randomly
-p no:xdist``, so file order holds): non-destructive tests run first
against the shared pool, then the SIGKILL kill-drain acceptance test
(which permanently kills worker r1), then graceful shutdown on r0 LAST.

The acceptance anchor: a mid-decode worker SIGKILL must drain, requeue
and complete every affected stream BIT-IDENTICAL to an isolated
``ShardedDecoder.generate`` with the same seed, with zero leaked pages
on the dead replica — the same contract tests/test_serving_router.py
proves for in-process replicas, now across a real process boundary.
"""

import atexit
import os

import numpy as np
import pytest

import mxtpu as mx
from mxtpu.models.transformer import (llama_tiny,
                                      transformer_lm_sharding_rules)
from mxtpu.observability.flight import flight_recording, get_flight
from mxtpu.observability.trace import get_tracer, tracing
from mxtpu.parallel import ShardedDecoder, make_mesh
from mxtpu.resilience import (InjectedFault, TransportError,
                              TransportTimeoutError, WorkerDiedError,
                              fault_plan)
from mxtpu.serving import (Gateway, InProcessReplica, ReplicaSupervisor,
                           SubprocessReplica, replica_pool, request_spec)

FACTORY = "mxtpu.serving.worker:demo_paged_engine"
# worker engines: seed 77, llama_tiny(vocab_size=50), num_slots=2,
# max_length=32, block_size=8, prefill_chunk=8 (demo_paged_engine
# defaults) — the parent-side reference below must match.
VOCAB = 50
MAX_LEN = 32


# --------------------------------------------------------------------------
# probe-once shared pool (satellite: spawn-capability skip discipline)
# --------------------------------------------------------------------------

_verdict = None          # (ok: bool, detail: str) once probed
_pool = None             # the shared 2-worker pool when the probe passed


def _spawn_pool():
    return replica_pool(FACTORY, n=2, transport="subprocess",
                        kwargs=lambda i: {"ledger_tag": "r%d" % i})


def _close_pool():
    global _pool
    if _pool is not None:
        for rep in _pool:
            try:
                rep.close()
            except Exception:
                pass
        _pool = None


def _probe_once():
    """Spawn the shared pool and decode ONE token end-to-end through a
    worker; cache the verdict.  One retry on failure (a transient spawn
    hiccup must not skip the whole module)."""
    global _verdict, _pool
    if _verdict is not None:
        return _verdict
    detail = "unprobed"
    for _attempt in range(2):
        reps = None
        try:
            reps = _spawn_pool()
            prompt = np.array([[1, 2, 3]], dtype=np.int32)
            rid = reps[0].submit(request_spec(prompt, 1),
                                 ("probe", 0))
            assert isinstance(rid, int)
            got = None
            for _ in range(64):
                reps[0].step()
                _toks, fins, _re = reps[0].poll()
                if fins:
                    got = fins[0]
                    break
            assert got is not None, "probe decode never finished"
            assert got[1] == "ok", "probe decode status %r" % (got[1],)
            _pool = reps
            atexit.register(_close_pool)
            _verdict = (True, "")
            return _verdict
        except Exception as exc:  # noqa: BLE001 — the probe reports,
            # never raises: its failure detail becomes the skip reason
            detail = "%s: %s" % (type(exc).__name__, exc)
            if reps is not None:
                for rep in reps:
                    try:
                        rep.close()
                    except Exception:
                        pass
    _verdict = (False, detail)
    return _verdict


@pytest.fixture
def pool():
    ok, detail = _probe_once()
    if not ok:
        pytest.skip("cannot run subprocess workers here: %s" % detail)
    return _pool


@pytest.fixture(autouse=True)
def _clean_tracer():
    """The process-wide tracer buffer survives ``tracing()`` exits by
    design (to_json after the block); scrub it so this module leaves no
    events behind for test files that assert the off-by-default state."""
    yield
    get_tracer().reset()


# --------------------------------------------------------------------------
# parent-side bit-exact reference (same seed => same weights anywhere)
# --------------------------------------------------------------------------

_REF = None


def _reference():
    global _REF
    if _REF is None:
        mx.random.seed(77)
        net = llama_tiny(vocab_size=VOCAB)
        net.initialize()
        _REF = ShardedDecoder(net, make_mesh(dp=1),
                              transformer_lm_sharding_rules())
    return _REF


def _prompts(seed, lengths):
    rng = np.random.RandomState(seed)
    return [np.asarray(rng.randint(0, VOCAB, (1, t)), dtype=np.int32)
            for t in lengths]


def _want(prompt, n):
    return _reference().generate(
        mx.nd.array(prompt), max_new_tokens=n,
        max_length=MAX_LEN).asnumpy()


# --------------------------------------------------------------------------
# transport-free tests (run regardless of spawn capability)
# --------------------------------------------------------------------------

def test_replica_pool_transport_selection(monkeypatch):
    with pytest.raises(ValueError, match="module:callable"):
        replica_pool(lambda i: None, n=1, transport="subprocess")
    with pytest.raises(ValueError, match="callable factory"):
        replica_pool("mod:fn", n=1, transport="inprocess")
    with pytest.raises(ValueError, match="unknown replica transport"):
        replica_pool(lambda i: None, n=1, transport="carrier-pigeon")
    # env default steers selection (and its error paths) the same way
    monkeypatch.setenv("MXTPU_REPLICA_TRANSPORT", "subprocess")
    with pytest.raises(ValueError, match="module:callable"):
        replica_pool(lambda i: None, n=1)


class _StubReplica:
    """Minimal ReplicaTransport for supervisor-unit tests: holds one
    request forever, with a scriptable progress() — no engine, no
    process."""

    def __init__(self, replica_id, progress_fn):
        self.replica_id = replica_id
        self.alive = True
        self.capacity = 2
        self._progress_fn = progress_fn
        self.drained = None

    @property
    def load(self):
        return 1

    @property
    def free_slots(self):
        return 1

    def health(self):
        pass

    def step(self):
        pass

    def poll(self):
        return {}, [], []

    def progress(self):
        return self._progress_fn()

    def drain(self):
        self.drained = [("t", 0)]
        return list(self.drained)

    def stats(self):
        return {"blocks_in_use": 0, "pinned_blocks": 0}

    def cancel(self, tag):
        return False

    def prefix_probe(self, prompt):
        return 0

    def submit(self, spec, tag):
        raise AssertionError("stub never accepts work")


def test_supervisor_counts_progress_raise_as_transport_not_stall():
    """A progress RPC that RAISES is a transport failure: the stall
    counter must not move, the transport counter and the consecutive
    failure count must — crossing fail_threshold kills the replica
    with a 'transport failure' reason, never 'stalled'."""
    rep = _StubReplica("r0", progress_fn=lambda: (_ for _ in ()).throw(
        TransportTimeoutError("no answer", method="progress", ticks=4)))
    sup = ReplicaSupervisor([rep], fail_threshold=3, stall_ticks=5)
    requeued = []
    for _ in range(3):
        _toks, _fins, req, _re = sup.tick()
        requeued.extend(req)
    st = sup.stats
    assert st["transport_failures"]["r0"] == 3
    assert st["deaths"] == 1
    assert rep.alive is False
    assert requeued == [("t", 0)]
    assert "transport failure (progress poll" in \
        st["last_errors"]["r0"]["reason"]
    assert "stall" not in st["last_errors"]["r0"]["reason"]
    # the stall counter never advanced: the worker was never OBSERVED
    # to stop decoding, it just could not be asked
    assert sup._stalled_for.get("r0", 0) == 0


def test_supervisor_stall_reason_still_fires_on_readable_no_progress():
    """The split's other half: a READABLE progress tuple that stops
    changing is still a stall (same reason string as before this PR)."""
    rep = _StubReplica("r0", progress_fn=lambda: (1, 1, 0, 1, 0))
    sup = ReplicaSupervisor([rep], fail_threshold=3, stall_ticks=3)
    for _ in range(4):
        sup.tick()
    st = sup.stats
    assert st["deaths"] == 1
    assert st["transport_failures"]["r0"] == 0
    assert st["last_errors"]["r0"]["reason"].startswith("stalled")


# --------------------------------------------------------------------------
# shared-pool tests (non-destructive first; order is load-bearing)
# --------------------------------------------------------------------------

def test_cross_process_parity_and_no_false_stall(pool):
    """Anchor: three streams through the Gateway over two OS-process
    replicas are bit-identical to the isolated single-engine reference.
    One prompt (24 tokens, prefill_chunk=8) needs a long chunked
    prefill; with stall_ticks=3 the supervisor must still see progress
    every tick THROUGH the RPC boundary — chunked prefill over a pipe
    never looks stalled (satellite 2)."""
    prompts = _prompts(11, (5, 24, 4))
    news = (6, 6, 5)
    want = [_want(p, n) for p, n in zip(prompts, news)]
    with tracing() as tr:
        gw = Gateway(pool, stall_ticks=3, fail_threshold=2)
        rids = [gw.submit(mx.nd.array(p), n)
                for p, n in zip(prompts, news)]
        res = gw.run()
        for i, r in enumerate(rids):
            assert gw.status(r) == "ok"
            assert np.array_equal(res[r].asnumpy(), want[i]), \
                "stream %d diverged across the process boundary" % i
        sup = gw.supervisor.stats
        assert sup["deaths"] == 0
        assert sup["transport_failures"] == {"r0": 0, "r1": 0}
        # worker-side engine events crossed the pipe and re-correlated
        # under the gateway rid (satellite 4): each request's timeline
        # holds forwarded decode-side events, not just parent-side ones
        for r in rids:
            tl = tr.events(rid="gw:%s" % r)
            kinds = {e.etype for e in tl}
            assert "transport.submit" in kinds
            assert any(k.startswith("engine.") for k in kinds), \
                "no worker-side events forwarded for gw:%s (%r)" \
                % (r, sorted(kinds))
    for rep in pool:
        st = rep.stats()
        assert st["blocks_in_use"] == st["pinned_blocks"]


def test_rpc_timeout_typed_and_stale_frame_recovery(pool):
    """A response that outlives its tick budget surfaces as a typed
    TransportTimeoutError naming the method and budget — and the late
    frame, when it finally lands, is DISCARDED by id instead of
    desynchronizing the stream: the very next RPC succeeds."""
    rep = pool[0]
    real_waiter, real_ticks = rep._waiter, rep._timeout_ticks
    try:
        rep._waiter = lambda pipe, seconds: False   # data never "ready"
        rep._timeout_ticks = 7
        with tracing() as tr:
            with pytest.raises(TransportTimeoutError) as ei:
                rep.stats()
            assert ei.value.method == "stats"
            assert ei.value.ticks == 7
            assert isinstance(ei.value, TransportError)
            evs = tr.events(types=["transport.rpc_timeout"])
            assert evs and evs[0].fields["method"] == "stats"
    finally:
        rep._waiter, rep._timeout_ticks = real_waiter, real_ticks
    # recovery: the stale response is still sitting in the pipe; the
    # next call must skip it (its id is quarantined) and read its own
    st = rep.stats()
    assert st["blocks_in_use"] == st["pinned_blocks"]
    assert rep.alive
    rep.health()                        # no raise = heartbeat advanced


def test_transport_fault_sites_fire_by_literal_plan(pool):
    """PLAN-TOKEN wiring for the two parent-side sites (satellite 3 /
    R005): the literal grammar below must reach the injector at the
    exact seam — encode before any bytes cross, rpc before the frame is
    written (the worker stays consistent through both)."""
    rep = pool[0]
    prompt = np.array([[4, 5, 6]], dtype=np.int32)
    with tracing() as tr:
        with fault_plan("transport.encode#r0@1:raise="
                        "ValueError(bad-encode)"):
            with pytest.raises(ValueError, match="bad-encode"):
                rep.submit(request_spec(prompt, 2), ("enc", 0))
        with fault_plan("transport.rpc#r0@1:raise=mxtpu.resilience."
                        "TransportTimeoutError(injected-timeout)"):
            with pytest.raises(TransportTimeoutError,
                               match="injected-timeout"):
                rep.stats()
        kinds = [e.etype for e in tr.events()]
        assert "fault.transport.encode" in kinds
        assert "fault.transport.rpc" in kinds
    # neither fault reached the worker: it still answers, no orphan
    # request was mirrored, no page moved
    assert ("enc", 0) not in rep._mirror
    st = rep.stats()
    assert st["blocks_in_use"] == st["pinned_blocks"]


def test_injected_rpc_fault_counts_toward_replica_death(pool):
    """An injected transport.rpc timeout inside the supervisor loop is
    counted on the TRANSPORT ledger (never the stall one) and retires
    the replica at fail_threshold — while the pool keeps serving."""
    sup = ReplicaSupervisor(pool, fail_threshold=2, stall_ticks=None)
    # @1x2: hits 1 and 2 only (the health probes of two ticks) — the
    # drain RPC that follows the death is hit 3 and must go through,
    # proving the fault plan can retire a replica WITHOUT losing its
    # live worker's drain report
    with fault_plan("transport.rpc#r1@1x2:raise=mxtpu.resilience."
                    "TransportTimeoutError(injected-timeout)"):
        for _ in range(2):
            sup.tick()
    st = sup.stats
    assert st["transport_failures"]["r1"] == 2
    assert st["transport_failures"]["r0"] == 0
    assert st["deaths"] == 1
    assert "transport failure (TransportTimeoutError)" == \
        st["last_errors"]["r1"]["reason"]
    # the worker process itself was never harmed: revive and verify it
    # still answers over the same pipe
    sup.revive("r1")
    pool[1].health()                    # no raise = worker unharmed
    assert pool[1].stats()["blocks_in_use"] == 0


def _fault_artifact_run():
    """One fully-planned failure run on a FRESH worker: rpc timeouts
    from hit 9 onward retire the pool's only replica.  Returns the
    (outcome, trace json, flight json) triple for comparison."""
    ok, detail = _probe_once()
    if not ok:
        pytest.skip("cannot run subprocess workers here: %s" % detail)
    rep = SubprocessReplica(FACTORY, kwargs={"ledger_tag": "r0"},
                            replica_id="r0")
    try:
        with flight_recording(32):
            with tracing() as tr:
                gw = Gateway([rep], fail_threshold=1,
                             hedge_fraction=None)
                p = _prompts(9, (6,))[0]
                with fault_plan("transport.rpc#r0@9+:raise="
                                "mxtpu.resilience.TransportTimeoutError"
                                "(injected-timeout)"):
                    rid = gw.submit(mx.nd.array(p), 4)
                    try:
                        gw.run()
                        outcome = "run-ok:%s" % gw.status(rid)
                    except Exception as exc:  # noqa: BLE001 — the
                        # outcome (pool-down) is part of the artifact
                        outcome = "raised:%s:%s" % (
                            type(exc).__name__, exc)
                trace_js = tr.to_json()
            flight_js = get_flight().to_json()
    finally:
        rep.close()
    return outcome, trace_js, flight_js


@pytest.mark.slow
def test_transport_fault_artifacts_byte_identical():
    """Counter-determinism acceptance for the transport failure modes:
    the same seed + plan on two FRESH workers produce byte-identical
    trace and flight serializations — worker pids and wall clocks stay
    on the noise channel, everything else replays exactly."""
    first = _fault_artifact_run()
    second = _fault_artifact_run()
    assert first[0].startswith("raised:MXTPUError"), first[0]
    assert "cannot make progress" in first[0]
    assert first[0] == second[0]
    assert first[1] == second[1], "trace artifacts diverged"
    assert first[2] == second[2], "flight artifacts diverged"
    import json as _json
    pms = _json.loads(first[2])["postmortems"]
    assert [p["kind"] for p in pms] == ["replica_death"]
    assert pms[0]["context"]["replica"] == "r0"


def test_worker_sigkill_mid_decode_drains_bit_exact(pool):
    """THE acceptance test: a counter-planned transport.worker_death
    fault SIGKILLs worker r1 mid-decode; the supervisor sees a typed
    WorkerDiedError (transport ledger), drains r1's in-flight streams
    off the parent-side mirror, requeues them, and every stream —
    survivor and requeued alike — completes bit-identical to the
    isolated reference.  Zero pages survive on the dead replica; the
    flight postmortem names the drained tags, exit code -9, and the
    worker pid (noise channel only)."""
    prompts = _prompts(3, (5, 7, 4))
    news = (6, 5, 4)
    want = [_want(p, n) for p, n in zip(prompts, news)]
    pid_r1 = pool[1].pid
    with flight_recording(64):
        with tracing() as tr:
            gw = Gateway(pool, fail_threshold=1, hedge_fraction=None)
            with fault_plan("transport.worker_death#r1@25:raise="
                            "OSError(planned-kill)"):
                rids = [gw.submit(mx.nd.array(p), n)
                        for p, n in zip(prompts, news)]
                res = gw.run()
            sup = gw.supervisor.stats
            assert sup["deaths"] == 1
            assert sup["requeued_requests"] >= 1
            assert sup["transport_failures"]["r1"] >= 1
            assert "transport failure" in \
                sup["last_errors"]["r1"]["reason"]
            assert sup["last_errors"]["r1"]["type"] == "WorkerDiedError"
            for i, r in enumerate(rids):
                assert gw.status(r) == "ok"
                assert np.array_equal(res[r].asnumpy(), want[i]), \
                    "stream %d not bit-identical after kill-drain" % i
            kinds = [e.etype for e in tr.events()]
            assert "fault.transport.worker_death" in kinds
            assert "transport.worker_exit" in kinds
            assert "replica.death" in kinds
        # the dead replica: really dead, really empty
        dead = pool[1]
        assert dead.alive is False
        assert dead.exit_code == -9
        st = dead.stats()
        assert st["blocks_in_use"] == 0
        assert st["pinned_blocks"] == 0
        assert st["worker"] == "dead"
        # the survivor leaked nothing either
        st0 = pool[0].stats()
        assert st0["blocks_in_use"] == st0["pinned_blocks"]
        # flight postmortem: deterministic context names the replica,
        # exit code and drained tags; the pid rides the noise channel
        fl = get_flight()
        pms = [p for p in fl.postmortems if p.kind == "replica_death"]
        assert len(pms) == 1
        pm = pms[0]
        assert pm.context["replica"] == "r1"
        assert pm.context["exit_code"] == -9
        assert pm.context["drained_tags"], "postmortem lost the drain"
        assert pm.noise == {"pid": pid_r1}
        rec = fl.postmortem_record(pm, include_noise=True)
        assert rec["noise"]["pid"] == pid_r1
        lean = fl.to_json()
        assert '"pid"' not in lean and '"noise"' not in lean, \
            "worker pid leaked into the deterministic serialization"
        assert pm.rids, "postmortem names no drained requests"
        assert all(fl.timeline(r) for r in pm.rids), \
            "drained request timelines empty"


def test_graceful_shutdown_flushes_inflight_cursors(pool):
    """LAST (kills r0): shutdown() sends the shutdown RPC, and the
    worker's final frame flushes tokens already decoded but not yet
    polled — nothing buffered in the child is lost on a clean exit."""
    rep = pool[0]
    prompt = np.array([[7, 8, 9, 10]], dtype=np.int32)
    want = _want(prompt, 3)
    base = rep.progress()[1]            # lifetime generated-token count
    rid = rep.submit(request_spec(prompt, 3), ("bye", 0))
    assert isinstance(rid, int)
    for _ in range(64):
        rep.step()
        if rep.progress()[1] - base >= 3:   # decoded, never polled
            break
    tokens, finished, _restarts = rep.shutdown()
    assert rep.alive is False
    assert rep.exit_code == 0
    got = tokens.get(("bye", 0), [])
    fin = [f for f in finished if f[0] == ("bye", 0)]
    assert fin and fin[0][1] == "ok"
    assert np.array_equal(np.asarray(fin[0][2]), want)
    assert got == want[0, prompt.shape[1]:].tolist()
    # idempotent: a second shutdown of a dead transport is a no-op
    assert rep.shutdown() == ({}, [], [])


# --------------------------------------------------------------------------
# probation revival respawns a dead worker (docs/serving.md
# "Elastic serving" — the revive() fix: flipping alive on a corpse is
# not a revival)
# --------------------------------------------------------------------------

class _DeadWorkerStub(_StubReplica):
    """A stub transport whose worker process can 'die': opts into the
    supervisor's duck-typed respawn protocol via respawn/worker_dead.
    The scriptable failure rides health() (probed every tick), not
    progress() (only read under stall detection)."""

    def __init__(self):
        super().__init__("r0", lambda: (1, 1, 0, 1, 0))
        self.worker_dead = False
        self.respawns = 0
        self.fail_respawn = False
        self.health_exc = None

    def health(self):
        if self.health_exc is not None:
            raise self.health_exc

    def respawn(self):
        if self.fail_respawn:
            raise TransportError("spawn refused")
        self.respawns += 1
        self.worker_dead = False


def test_probation_revive_respawns_dead_worker_stub():
    """revive() must respawn a transport whose worker PROCESS died
    before flipping alive — otherwise probation re-admits a corpse
    that fails every probe and immediately re-dies."""
    rep = _DeadWorkerStub()
    rep.health_exc = TransportTimeoutError("no answer", method="health",
                                           ticks=4)
    sup = ReplicaSupervisor([rep], fail_threshold=1, stall_ticks=None,
                            revive_after_ticks=2)
    sup.tick()                      # health raises -> death + drain
    assert rep.alive is False
    assert sup.stats["transport_failures"]["r0"] == 1
    rep.worker_dead = True          # the corpse: process gone too
    rep.health_exc = None
    sup.tick()                      # probation not yet elapsed
    assert rep.alive is False and rep.respawns == 0
    sup.tick()                      # probation over: respawn + revive
    assert rep.respawns == 1
    assert rep.worker_dead is False
    assert rep.alive is True
    assert sup.stats["revivals"] == 1


def test_probation_revive_retries_after_failed_respawn():
    """A respawn that raises keeps the replica DEAD (its death tick
    stands), records the failure, and probation retries next tick."""
    rep = _DeadWorkerStub()
    rep.fail_respawn = True
    # kill it through the transport-failure path
    rep.health_exc = TransportTimeoutError("no answer", method="health",
                                           ticks=4)
    sup = ReplicaSupervisor([rep], fail_threshold=1, stall_ticks=None,
                            revive_after_ticks=1)
    sup.tick()
    assert rep.alive is False
    rep.worker_dead = True
    rep.health_exc = None
    sup.tick()                      # respawn raises -> stays dead
    assert rep.alive is False
    assert sup.stats["last_errors"]["r0"]["reason"] == \
        "revive/respawn failed"
    rep.fail_respawn = False
    sup.tick()                      # probation retried: revived now
    assert rep.alive is True and rep.respawns == 1
    assert sup.stats["revivals"] == 1


@pytest.mark.slow
def test_kill_revive_respawn_serves_bit_exact(pool):
    """The real thing: SIGKILL a worker, let probation respawn it
    (fresh pipe + handshake + factory re-run), then serve a stream
    through the revived replica bit-identical to the isolated
    reference."""
    rep = SubprocessReplica(FACTORY, kwargs={"ledger_tag": "rv"},
                            replica_id="rv")
    try:
        # respawn refuses to replace a LIVE worker
        with pytest.raises(TransportError, match="DEAD"):
            rep.respawn()
        pid_before = rep.pid
        sup = ReplicaSupervisor([rep], fail_threshold=1,
                                stall_ticks=None, revive_after_ticks=2)
        rep.kill()
        assert rep.worker_dead
        sup.tick()                  # dead pipe -> declared dead
        assert rep.alive is False
        for _ in range(4):
            sup.tick()
            if rep.alive:
                break
        assert rep.alive is True, "probation never revived the worker"
        assert rep.worker_dead is False
        assert rep.pid != pid_before
        assert sup.stats["revivals"] == 1
        # the respawned worker serves, bit-exact
        prompt = np.array([[4, 5, 6, 7]], dtype=np.int32)
        want = _want(prompt, 4)
        rid = rep.submit(request_spec(prompt, 4), ("back", 0))
        assert isinstance(rid, int)
        got = None
        for _ in range(64):
            rep.step()
            _toks, fins, _re = rep.poll()
            if fins:
                got = fins[0]
                break
        assert got is not None and got[1] == "ok"
        assert np.array_equal(np.asarray(got[2]), want)
    finally:
        rep.close()


# --------------------------------------------------------------------------
# live weight hot-swap across the process boundary
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_adopt_and_rollback_across_process_boundary(pool, tmp_path):
    """adopt()/rollback() RPC through the pipe: the checkpoint path
    crosses as a string (same-host shared filesystem), the WORKER
    verifies and stages it, in-flight streams finish on the old
    weights, and a corrupt file surfaces as a typed
    CorruptCheckpointError rebuilt parent-side."""
    import pickle

    from mxtpu.resilience.checkpoint import (CorruptCheckpointError,
                                             write_verified)

    # fresh weights from a DIFFERENT seed, materialized locally
    mx.random.seed(101)
    net = llama_tiny(vocab_size=VOCAB)
    net.initialize()
    dec = ShardedDecoder(net, make_mesh(dp=1),
                         transformer_lm_sharding_rules())
    prompt = np.array([[3, 4, 5, 6]], dtype=np.int32)
    want_old = _want(prompt, 4)
    want_new = dec.generate(mx.nd.array(prompt), max_new_tokens=4,
                            max_length=MAX_LEN).asnumpy()
    named = {p.name: np.asarray(p.data()._data) for p in dec._params}
    ck = str(tmp_path / "step7.ckpt")
    write_verified(ck, pickle.dumps(
        {"step": 7, "num_update": 1, "params": named,
         "opt_states": {}, "scale_state": None, "rng": None}))

    rep = SubprocessReplica(FACTORY, kwargs={"ledger_tag": "ad"},
                            replica_id="ad")
    try:
        def finish(tag):
            for _ in range(64):
                rep.step()
                _toks, fins, _re = rep.poll()
                for f in fins:
                    if f[0] == tag:
                        return f
            raise AssertionError("stream %r never finished" % (tag,))

        # stream admitted BEFORE the swap finishes on the old weights
        rep.submit(request_spec(prompt, 4), ("old", 0))
        rep.step()
        gen = rep.adopt(ck)
        assert gen == 1
        fin = finish(("old", 0))
        assert fin[1] == "ok"
        assert np.array_equal(np.asarray(fin[2]), want_old)
        rep.step()              # drained boundary: install worker-side
        assert rep.stats()["param_generation"] == 1
        # new admissions ride the new generation
        rep.submit(request_spec(prompt, 4), ("new", 0))
        fin = finish(("new", 0))
        assert fin[1] == "ok"
        assert np.array_equal(np.asarray(fin[2]), want_new)
        # a corrupt checkpoint raises TYPED across the boundary and
        # leaves the worker on its current generation
        bad = str(tmp_path / "bad.ckpt")
        with open(ck, "rb") as f:
            payload = f.read()
        write_verified(bad, payload)
        with open(bad, "r+b") as f:
            f.seek(10)
            f.write(b"\xff\xff\xff")
        with pytest.raises(CorruptCheckpointError):
            rep.adopt(bad)
        assert rep.stats()["param_generation"] == 1
        assert rep.stats()["adoption_failures"] == 1
        # rollback re-stages the previous generation worker-side
        assert rep.rollback() == 2
        rep.step()
        assert rep.stats()["param_generation"] == 2
        rep.submit(request_spec(prompt, 4), ("back", 0))
        fin = finish(("back", 0))
        assert fin[1] == "ok"
        assert np.array_equal(np.asarray(fin[2]), want_old)
    finally:
        rep.close()


# --------------------------------------------------------------------------
# one process per chip (docs/serving.md)
# --------------------------------------------------------------------------

def test_pool_workers_take_the_chip_their_env_names(monkeypatch):
    """Which chip a worker takes is said, never guessed: left alone it
    inherits this process's TPU settings untouched, and
    ``env=chip_pin_env`` gives replica i the libtpu settings for the
    i-th chip this process may use and nothing else (the CPU backend
    ignores them)."""
    from mxtpu.context import DeviceNotFoundError
    from mxtpu.serving import replica_pool
    from mxtpu.serving.transport import chip_pin_env

    monkeypatch.setattr(SubprocessReplica, "_spawn", lambda self: None)
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "2,3")
    for rep in replica_pool(FACTORY, n=2, transport="subprocess"):
        assert rep._child_env["TPU_VISIBLE_CHIPS"] == "2,3"
        assert "TPU_PROCESS_BOUNDS" not in rep._child_env
    # a host that hands this process chips 2 and 3 only
    pinned = replica_pool(FACTORY, n=2, transport="subprocess",
                          env=chip_pin_env)
    for rep, chip in zip(pinned, "23"):
        assert rep._child_env["TPU_VISIBLE_CHIPS"] == chip
        assert rep._child_env["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert rep._child_env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    with pytest.raises(DeviceNotFoundError, match="chip 2 .* 2 chip"):
        replica_pool(FACTORY, n=3, transport="subprocess",
                     env=chip_pin_env)
    monkeypatch.delenv("TPU_VISIBLE_CHIPS")
    assert chip_pin_env(3)["TPU_VISIBLE_CHIPS"] == "3"
    # one env for every worker still passes straight through
    (rep,) = replica_pool(FACTORY, n=1, transport="subprocess",
                          env={"MXTPU_X": "1"})
    assert rep._child_env["MXTPU_X"] == "1"


def test_pool_parent_that_holds_the_chip_is_refused(monkeypatch):
    """A chip belongs to one process: a parent that has initialised an
    accelerator backend gets a typed error at construction — no worker
    is spawned to hang in the handshake."""
    from jax._src import xla_bridge

    from mxtpu.serving import ChipHeldError
    from mxtpu.serving.transport import held_accelerator

    assert held_accelerator() is None       # a CPU backend holds nothing
    monkeypatch.setattr(xla_bridge, "_backends",
                        dict(xla_bridge._backends, tpu=object()))
    assert held_accelerator() == "tpu"
    # workers held to the CPU backend reach for no chip and are exempt:
    # this session's own JAX_PLATFORMS=cpu must not be inherited here
    monkeypatch.delenv("JAX_PLATFORMS")
    spawned = []
    monkeypatch.setattr("subprocess.Popen",
                        lambda *a, **k: spawned.append(a))
    with pytest.raises(ChipHeldError, match="r0.*tpu backend"):
        SubprocessReplica(FACTORY)
    assert not spawned


def test_importing_mxtpu_initialises_no_backend():
    """A launcher or pool parent imports the package and must still not
    hold a chip: ``import mxtpu`` and its serving/parallel/gluon
    subpackages create no JAX backend (checked in a fresh interpreter —
    this one has long since created its CPU backend)."""
    import subprocess
    import sys

    code = ("import mxtpu, mxtpu.parallel, mxtpu.serving, mxtpu.gluon\n"
            "from jax._src import xla_bridge\n"
            "from mxtpu.serving.transport import held_accelerator\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n"
            "assert held_accelerator() is None\n")
    out = subprocess.run([sys.executable, "-c", code],
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stderr[-2000:]
