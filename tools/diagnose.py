#!/usr/bin/env python
"""Diagnose the runtime environment (parity: tools/diagnose.py — the
reference dumps platform/python/library/hardware info for bug reports;
this dumps the TPU-stack equivalents: jax/backend/devices/mesh-ability,
mxtpu feature flags, and env configuration)."""

from __future__ import annotations

import os
import platform
import sys
import time

# `python tools/diagnose.py` puts tools/ (not the repo root) on sys.path;
# make the in-repo mxtpu importable so the MXTPU/analysis sections report
# real data instead of IMPORT FAILED
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def check_python():
    print("----------Python Info----------")
    print("Version      :", platform.python_version())
    print("Compiler     :", platform.python_compiler())
    print("Build        :", platform.python_build())


def check_os():
    print("----------System Info----------")
    print("Platform     :", platform.platform())
    print("system       :", platform.system())
    print("machine      :", platform.machine())
    print("processor    :", platform.processor() or "n/a")
    try:
        print("cpu count    :", os.cpu_count())
    except Exception:
        pass


def check_libraries():
    print("----------Library Info----------")
    for lib in ("numpy", "jax", "jaxlib", "flax", "optax"):
        try:
            mod = __import__(lib)
            print("%-12s : %s" % (lib, getattr(mod, "__version__", "?")))
        except Exception as e:
            print("%-12s : NOT AVAILABLE (%s)" % (lib, e))


def check_mxtpu():
    print("----------MXTPU Info----------")
    t0 = time.time()
    try:
        import mxtpu
        print("mxtpu        :", getattr(mxtpu, "__version__", "dev"))
        print("import time  : %.2fs" % (time.time() - t0))
        from mxtpu.runtime import Features
        feats = Features()
        enabled = [f for f in feats.keys() if feats.is_enabled(f)]
        print("features     :", ", ".join(sorted(enabled)) or "none")
        check_engine_bulk()
        check_compile_ledger()
    except Exception as e:
        print("mxtpu        : IMPORT FAILED (%s: %s)"
              % (type(e).__name__, e))


def check_engine_bulk():
    """Exercise the op-bulking path once and report the segment-cache
    counters (docs/engine.md): a healthy install shows one cache miss on
    the first flush and a hit on the second, zero eager replays."""
    print("----------Engine Bulking----------")
    try:
        import mxtpu as mx
        from mxtpu import engine
        print("sync mode    :", engine.is_sync())
        print("ambient size :", engine.bulk_size(),
              "(MXTPU_ENGINE_BULK_SIZE)")
        engine.reset_bulk_stats()
        x = mx.nd.array([1.0, 2.0, 3.0])
        for _ in range(2):
            with engine.bulk(8):
                ((x * 2.0) + 1.0).asnumpy()  # trace-ok: diagnostic probe
        st = engine.bulk_stats()
        print("bulk cache   : %d hit / %d miss / %d flushes, "
              "%d ops bulked, %d eager replays, %d cached programs"
              % (st["cache_hits"], st["cache_misses"], st["flushes"],
                 st["bulked_ops"], st["eager_replays"], st["cache_size"]))
    except Exception as e:
        print("bulking      : FAILED (%s: %s)" % (type(e).__name__, e))


def check_compile_ledger():
    """Print the process compile ledger (docs/analysis.md): programs
    compiled, hit/miss per jit site, top-cardinality signatures, and the
    discipline checker's verdict.  The engine-bulk probe above already
    populated the ledger, so a healthy install shows the engine.bulk
    site with one miss and one hit."""
    print("----------Compile Ledger----------")
    try:
        from mxtpu.analysis import check_compiles, get_ledger
        led = get_ledger()
        print("enabled      :", led.enabled, "(MXTPU_COMPILE_LEDGER)")
        print("dump path    :",
              os.environ.get("MXTPU_COMPILE_LEDGER_DUMP") or "none")
        stats = led.stats()
        if not stats:
            print("sites        : none recorded")
        for site, s in stats.items():
            print("%-13s: %d program(s), %d hit / %d miss, "
                  "top shape cardinality %d"
                  % (site[:13], s["misses"], s["hits"], s["misses"],
                     s["shape_cardinality"]))
        rep = check_compiles()
        print("discipline   :", rep.summary())
        for d in rep.errors:
            print("  ", d)
    except Exception as e:
        print("ledger       : FAILED (%s: %s)" % (type(e).__name__, e))


def check_serving():
    """Exercise the paged continuous-batching engine once on a micro
    model (single-device CPU mesh, two requests sharing a prompt
    prefix) and print the paged-cache counters (docs/inference.md): a
    healthy install shows a prefix hit, a copy-on-write clone, and an
    empty pool after the drain."""
    print("----------Serving (paged KV cache)----------")
    try:
        import numpy as np

        import mxtpu as mx
        from mxtpu import nd
        from mxtpu.models.transformer import (
            TransformerLM, transformer_lm_sharding_rules)
        from mxtpu.parallel import PagedContinuousBatchingEngine
        from mxtpu.parallel.mesh import DeviceMesh

        mx.random.seed(7)
        lm = TransformerLM(32, units=16, hidden_size=32, num_layers=1,
                           num_heads=2, num_kv_heads=2)
        lm.initialize()
        eng = PagedContinuousBatchingEngine(
            lm, DeviceMesh(dp=1), transformer_lm_sharding_rules(),
            num_slots=2, max_length=32, block_size=8, prefill_chunk=8)
        rng = np.random.RandomState(0)
        shared = rng.randint(0, 32, (1, 11))
        # first prompt: 17 tokens -> pages 0 and 1 both full and
        # registered once its 3-chunk prefill completes; the second
        # diverges at token 11, INSIDE page 1 -> one full-page prefix
        # hit plus a copy-on-write clone of page 1
        pa = np.concatenate([shared, rng.randint(0, 32, (1, 6))], axis=1)
        pb = np.concatenate([shared, rng.randint(0, 32, (1, 4))], axis=1)
        eng.submit(nd.array(pa, dtype="int32"), 3)
        for _ in range(3):
            eng.step()  # drive A's chunked prefill to registration
        eng.submit(nd.array(pb, dtype="int32"), 3)
        eng.run()
        st = eng.stats
        print("pool         : %d pages x %d tokens, %d in use / %d "
              "free after drain"
              % (st["num_blocks"], st["block_size"],
                 st["blocks_in_use"], st["blocks_free"]))
        print("sharing      : %d prefix hit(s), %d page(s) shared now, "
              "%d COW cop%s"
              % (st["prefix_hit_requests"], st["blocks_shared"],
                 st["cow_copied_blocks"],
                 "y" if st["cow_copied_blocks"] == 1 else "ies"))
        print("traffic      : %d step(s), %d token(s), %d quarantined, "
              "%d shed" % (st["steps"], st["generated_tokens"],
                           st["quarantined_requests"],
                           st["shed_requests"]))
        healthy = (st["prefix_hit_requests"] >= 1
                   and st["cow_copied_blocks"] >= 1
                   and st["blocks_in_use"] == 0)
        print("probe        :", "ok (prefix hit + COW + clean drain)"
              if healthy else "UNEXPECTED counters %r" % (st,))
    except Exception as e:
        print("serving      : FAILED (%s: %s)" % (type(e).__name__, e))
    check_speculative()


def check_speculative():
    """Exercise speculative decoding once (docs/inference.md): the
    pinned cycling micro model (tests/test_speculative.py) under a
    repetitive prompt forces real draft accepts, so a healthy install
    shows accepted tokens and >1.0 tokens per slot-iteration — while
    the stream stays bit-identical to non-speculative decode."""
    print("----------Serving (speculative decode)----------")
    try:
        import numpy as np

        import mxtpu as mx
        from mxtpu import nd
        from mxtpu.models.transformer import (
            TransformerLM, transformer_lm_sharding_rules)
        from mxtpu.parallel import ContinuousBatchingEngine
        from mxtpu.parallel.mesh import DeviceMesh

        mx.random.seed(1)   # cycling greedy continuations at vocab 20
        lm = TransformerLM(20, units=32, hidden_size=64, num_layers=1,
                           num_heads=4, num_kv_heads=2)
        lm.initialize()
        eng = ContinuousBatchingEngine(
            lm, DeviceMesh(dp=1), transformer_lm_sharding_rules(),
            num_slots=2, max_length=64, spec_k=3)
        rng = np.random.RandomState(0)
        pat = rng.randint(0, 20, (1, 4))
        prompt = nd.array(np.tile(pat, 4).astype(np.int32))
        eng.submit(prompt, 16)
        eng.submit(nd.array(rng.randint(0, 20, (1, 5)),
                            dtype="int32"), 12)
        eng.run()
        st = eng.stats
        rate = (st["generated_tokens"] / st["slot_iterations"]
                if st["slot_iterations"] else 0.0)
        print("drafting     : %d drafted, %d accepted (hit rate %.2f), "
              "%d verify call(s)"
              % (st["drafted_tokens"], st["accepted_tokens"],
                 st["draft_hit_rate"], st["verify_calls"]))
        print("throughput   : %.2f tokens/slot-iteration "
              "(non-speculative = 1.0)" % rate)
        healthy = (st["drafted_tokens"] > 0 and st["accepted_tokens"] > 0
                   and st["verify_calls"] > 0 and rate > 1.0)
        print("probe        :", "ok (accepts + >1.0 tokens/slot-iter)"
              if healthy else "UNEXPECTED counters %r" % (st,))

        # TREE arm: a branchy prompt (trailing n-gram recurs with two
        # continuations) through spec_tree drafting — ancestor-masked
        # verify + side-branch fix-up on the same micro model
        teng = ContinuousBatchingEngine(
            lm, DeviceMesh(dp=1), transformer_lm_sharding_rules(),
            num_slots=2, max_length=64, spec_tree=(6, 2))
        teng.submit(nd.array(np.array(
            [[1, 2, 3, 1, 2, 4, 1, 2, 3, 1, 2]], np.int32)), 16)
        teng.submit(nd.array(np.array(
            [[5, 6, 7, 5, 6, 8, 5, 6, 7, 5, 6]], np.int32)), 14)
        teng.run()
        ts = teng.stats
        trate = (ts["generated_tokens"] / ts["slot_iterations"]
                 if ts["slot_iterations"] else 0.0)
        print("tree         : %d nodes drafted over %d paths, "
              "%d accepted, %.2f tokens/slot-iteration"
              % (ts["tree_nodes_drafted"], ts["tree_paths"],
                 ts["accepted_tokens"], trate))
        thealthy = (ts["tree_nodes_drafted"] > 0 and ts["tree_paths"] > 0
                    and ts["accepted_tokens"] > 0
                    and "verify_tree_slots" in ts["compiled_programs"])
        print("tree probe   :", "ok (tree drafts + ancestor-masked "
              "verify accepts)"
              if thealthy else "UNEXPECTED counters %r" % (ts,))
    except Exception as e:
        print("speculative  : FAILED (%s: %s)" % (type(e).__name__, e))
    check_quantized()


def check_quantized():
    """Exercise the quantized serving path once (docs/inference.md
    "Quantized serving"): weight-only int8 matmuls + int8 KV cache on
    the paged engine, one request asserted bit-identical to the
    isolated quantized generate, plus the cache-byte ratio from the
    abstract-eval pricer.  A healthy install shows exact stream parity
    and a ratio of 0.5 + 2/head_dim."""
    print("----------Serving (quantized int8)----------")
    try:
        import numpy as np

        import mxtpu as mx
        from mxtpu import nd
        from mxtpu.analysis.memory_estimate import kv_cache_residency
        from mxtpu.contrib.quantization import quantize_weights
        from mxtpu.models.transformer import (
            TransformerLM, transformer_lm_sharding_rules)
        from mxtpu.parallel import (PagedContinuousBatchingEngine,
                                    ShardedDecoder)
        from mxtpu.parallel.mesh import DeviceMesh

        mx.random.seed(7)
        lm = TransformerLM(32, units=16, hidden_size=32, num_layers=1,
                           num_heads=2, num_kv_heads=2)
        lm.initialize()
        rng = np.random.RandomState(0)
        prompt = nd.array(rng.randint(0, 32, (1, 9)), dtype="int32")
        lm(prompt)  # resolve deferred shapes before the weight rewrite
        rules = quantize_weights(lm, bits=8,
                                 rules=transformer_lm_sharding_rules())
        bf, _ = kv_cache_residency(lm, 2, 32, "bfloat16")
        i8, _ = kv_cache_residency(lm, 2, 32, "int8")
        print("weights      : %d Dense layer(s) -> packed int8 + scales"
              % len(rules.quantized_params))
        print("cache bytes  : int8/bf16 = %.4f (0.5 payload + scales)"
              % (i8 / bf))
        mesh = DeviceMesh(dp=1)
        want = ShardedDecoder(lm, mesh, rules).generate(
            prompt, max_new_tokens=4, max_length=32,
            cache_dtype="int8").asnumpy()
        eng = PagedContinuousBatchingEngine(
            lm, mesh, rules, num_slots=2, max_length=32, block_size=8,
            prefill_chunk=8, cache_dtype="int8")
        rid = eng.submit(prompt, 4)
        got = eng.run()[rid].asnumpy()
        exact = bool(np.array_equal(got, want))
        print("parity       : engine stream %s isolated quantized "
              "generate" % ("==" if exact else "!="))
        healthy = exact and eng.stats["blocks_in_use"] == 0
        print("probe        :", "ok (bit-exact int8 stream + clean "
              "drain)" if healthy else "UNEXPECTED %r" % (eng.stats,))
    except Exception as e:
        print("quantized    : FAILED (%s: %s)" % (type(e).__name__, e))
    check_hierarchical()


def check_hierarchical():
    """Exercise the hierarchical prefix cache once (docs/inference.md
    "Hierarchical prefix cache"): pin a finished chain, drain to a
    LULL, re-hit it, then force a host-tier swap round trip — a healthy
    install shows prefill tokens avoided on the re-hit, matching
    swap_out/swap_in page counts, a bit-exact swapped-in stream, and a
    pool that drains to zero once the pins release."""
    print("----------Serving (hierarchical cache)----------")
    try:
        import numpy as np

        import mxtpu as mx
        from mxtpu import nd
        from mxtpu.models.transformer import (
            TransformerLM, transformer_lm_sharding_rules)
        from mxtpu.parallel import (PagedContinuousBatchingEngine,
                                    ShardedDecoder)
        from mxtpu.parallel.mesh import DeviceMesh

        mx.random.seed(7)
        lm = TransformerLM(32, units=16, hidden_size=32, num_layers=1,
                           num_heads=2, num_kv_heads=2)
        lm.initialize()
        mesh = DeviceMesh(dp=1)
        rules = transformer_lm_sharding_rules()
        eng = PagedContinuousBatchingEngine(
            lm, mesh, rules, num_slots=2, max_length=32, block_size=8,
            prefill_chunk=8, pin_bytes="64KiB",
            host_cache_bytes="64KiB")
        rng = np.random.RandomState(0)
        prompt = nd.array(rng.randint(0, 32, (1, 19)), dtype="int32")
        want = ShardedDecoder(lm, mesh, rules).generate(
            prompt, max_new_tokens=4, max_length=32).asnumpy()
        eng.submit(prompt, 4)
        eng.run()                 # drain completely — the traffic lull
        pinned = eng.stats["pinned_blocks"]
        rid = eng.submit(prompt, 4)
        res = eng.run()           # re-hit the PINNED chain
        hit_ok = bool(np.array_equal(res[rid].asnumpy(), want))
        avoided = eng.stats["prefill_tokens_avoided"]
        # force the host tier: spill every pinned chain, then re-admit
        for chain in list(eng._hc._chains.values()):
            eng._spill_chain(chain)
        spilled = eng.stats["spilled_blocks"]
        rid = eng.submit(prompt, 4)
        res = eng.run()           # swap_in restores the chain
        swap_ok = bool(np.array_equal(res[rid].asnumpy(), want))
        st = eng.stats
        print("pinning      : %d page(s) pinned across the lull, "
              "%d prefill token(s) avoided on the re-hit"
              % (pinned, avoided))
        print("host tier    : %d page(s) spilled, %d swapped out / "
              "%d swapped in" % (spilled, st["swapped_out_blocks"],
                                 st["swapped_in_blocks"]))
        eng._hc.pin_blocks = 0    # release the cache and check drain
        eng._enforce_pin_budget()
        clean = eng.stats["blocks_in_use"] == 0
        healthy = (pinned > 0 and avoided > 0
                   and st["swapped_in_blocks"] > 0
                   and hit_ok and swap_ok and clean)
        print("probe        :", "ok (pin -> lull -> re-hit -> swap "
              "round trip, streams bit-exact, clean drain)"
              if healthy else "UNEXPECTED counters %r" % (st,))
    except Exception as e:
        print("hierarchical : FAILED (%s: %s)" % (type(e).__name__, e))
    check_router()


def check_router():
    """Exercise the multi-replica service layer once (docs/serving.md):
    a 2-replica micro pool routes a repeat prompt to the warm replica
    (locality hit), hedges a deadline'd request, then a deterministic
    ``replica.health`` plan kills one replica mid-decode — a healthy
    install drains it clean (zero pages), requeues its request, and
    every stream stays bit-exact to the isolated decode."""
    print("----------Serving (router / replica pool)----------")
    try:
        import numpy as np

        import mxtpu as mx
        from mxtpu import nd
        from mxtpu.models.transformer import (
            TransformerLM, transformer_lm_sharding_rules)
        from mxtpu.parallel import (PagedContinuousBatchingEngine,
                                    ShardedDecoder)
        from mxtpu.parallel.mesh import DeviceMesh
        from mxtpu.resilience import fault_plan
        from mxtpu.serving import Gateway, replica_pool

        mx.random.seed(7)
        lm = TransformerLM(32, units=16, hidden_size=32, num_layers=1,
                           num_heads=2, num_kv_heads=2)
        lm.initialize()
        mesh = DeviceMesh(dp=1)
        rules = transformer_lm_sharding_rules()
        iso = ShardedDecoder(lm, mesh, rules)
        pool = replica_pool(
            lambda i: PagedContinuousBatchingEngine(
                lm, mesh, rules, num_slots=2, max_length=32,
                block_size=8, prefill_chunk=8, pin_bytes="64KiB",
                ledger_tag="probe-r%d" % i), n=2)
        gw = Gateway(pool, fail_threshold=2, hedge_fraction=0.25)
        rng = np.random.RandomState(0)
        p = nd.array(rng.randint(0, 32, (1, 17)), dtype="int32")
        want = iso.generate(p, max_new_tokens=6,
                            max_length=32).asnumpy()
        r1 = gw.submit(p, 6)
        gw.run()                  # warms one replica's pinned chain
        # locality re-hit + a deadline tight enough that the hedge
        # fires mid-decode (decode takes ~9 ticks; hedge at 12*0.25=3)
        r2 = gw.submit(p, 6, deadline_ticks=12)
        res = gw.run()
        loc = gw.router.stats
        ok_loc = (bool(np.array_equal(res[r2].asnumpy(), want))
                  and loc["locality_hits"] >= 1
                  and gw.stats["hedged_requests"] >= 1)
        r3 = gw.submit(p, 6)
        with fault_plan("replica.health#r0@2x2:raise="
                        "OSError(probe-kill)"):
            res = gw.run()
        sup = gw.stats["supervisor"]
        dead = gw.supervisor.replica("r0")
        drained = dead.stats()
        ok_death = (bool(np.array_equal(res[r3].asnumpy(), want))
                    and sup["deaths"] == 1
                    and drained["blocks_in_use"] == 0
                    and drained["pinned_blocks"] == 0)
        print("routing      : %d dispatch(es), %d locality hit(s), "
              "hit rate %.2f, %d hedge(s)"
              % (loc["dispatches"], loc["locality_hits"],
                 loc["prefix_hit_rate"], gw.stats["hedged_requests"]))
        print("supervision  : %d death(s), %d request(s) requeued, "
              "%d alive of %d" % (sup["deaths"],
                                  sup["requeued_requests"],
                                  sup["alive"], sup["replicas"]))
        healthy = ok_loc and ok_death
        print("probe        :", "ok (locality hit + forced replica "
              "death + clean drain, streams bit-exact)"
              if healthy else "UNEXPECTED (locality=%r death=%r %r)"
              % (ok_loc, ok_death, sup))
    except Exception as e:
        print("router       : FAILED (%s: %s)" % (type(e).__name__, e))
    check_lifecycle()


def check_lifecycle():
    """Exercise the serving-lifecycle page sanitizer once (docs/
    analysis.md "lifecycle_check"): an ARMED micro-engine driven
    through the full page lifecycle — prefix share, copy-on-write,
    host-tier spill, swap-in restore, clean drain — a healthy install
    raises ZERO V0xx violations while the shadow accounting tracks
    every page, and the ``lifecycle.*`` metrics source reports the
    same stats through the unified registry."""
    print("----------Serving (lifecycle sanitizer)----------")
    try:
        import numpy as np

        import mxtpu as mx
        from mxtpu import nd
        from mxtpu.analysis.lifecycle_check import (RING_DEPTH,
                                                    get_sanitizer,
                                                    page_sanitizing)
        from mxtpu.models.transformer import (
            TransformerLM, transformer_lm_sharding_rules)
        from mxtpu.parallel import PagedContinuousBatchingEngine
        from mxtpu.parallel.mesh import DeviceMesh

        print("ambient      : MXTPU_PAGE_SANITIZER=%s"
              % (os.environ.get("MXTPU_PAGE_SANITIZER") or "unset"))
        mx.random.seed(7)
        lm = TransformerLM(32, units=16, hidden_size=32, num_layers=1,
                           num_heads=2, num_kv_heads=2)
        lm.initialize()
        viol_before = get_sanitizer().stats()["violations_ever"]
        with page_sanitizing():
            eng = PagedContinuousBatchingEngine(
                lm, DeviceMesh(dp=1), transformer_lm_sharding_rules(),
                num_slots=2, max_length=32, block_size=8,
                prefill_chunk=8, pin_bytes="64KiB",
                host_cache_bytes="64KiB")
            rng = np.random.RandomState(0)
            shared = rng.randint(0, 32, (1, 11))
            pa = np.concatenate([shared, rng.randint(0, 32, (1, 6))],
                                axis=1)
            pb = np.concatenate([shared, rng.randint(0, 32, (1, 4))],
                                axis=1)
            eng.submit(nd.array(pa, dtype="int32"), 3)
            for _ in range(3):
                eng.step()      # drive A's chunked prefill to register
            eng.submit(nd.array(pb, dtype="int32"), 3)
            eng.run()           # prefix SHARE + COW under the sanitizer
            for chain in list(eng._hc._chains.values()):
                eng._spill_chain(chain)     # host-tier SPILL
            eng.submit(nd.array(pa, dtype="int32"), 3)
            eng.run()           # swap-in RESTORE
            eng._hc.pin_blocks = 0
            eng._enforce_pin_budget()       # release pins -> clean drain
            st = eng.stats
            san = get_sanitizer().stats()
            from mxtpu.observability import get_registry
            m = get_registry().snapshot(sources=("lifecycle",))
        new_viol = san["violations_ever"] - viol_before
        print("shadow state : %d page(s) tracked, %d event ring(s) "
              "(depth %d), %d transition(s) recorded"
              % (san["pages_tracked"], san["rings"], RING_DEPTH,
                 san["transitions"]))
        print("lifecycle    : %d COW cop%s, %d spilled / %d swapped "
              "in, %d in use after drain"
              % (st["cow_copied_blocks"],
                 "y" if st["cow_copied_blocks"] == 1 else "ies",
                 st["spilled_blocks"], st["swapped_in_blocks"],
                 st["blocks_in_use"]))
        print("metrics      : lifecycle.armed=%d "
              "lifecycle.violations_ever=%d (unified registry)"
              % (m["lifecycle.armed"], m["lifecycle.violations_ever"]))
        healthy = (st["cow_copied_blocks"] >= 1
                   and st["spilled_blocks"] >= 1
                   and st["swapped_in_blocks"] >= 1
                   and st["blocks_in_use"] == 0
                   and san["pages_tracked"] > 0
                   and new_viol == 0)
        print("probe        :", "ok (armed share -> COW -> spill -> "
              "restore -> drain, zero V0xx violations)" if healthy
              else "UNEXPECTED (viol=%d stats=%r)" % (new_viol, st))
    except Exception as e:
        print("lifecycle    : FAILED (%s: %s)" % (type(e).__name__, e))
    check_elastic()


def check_elastic():
    """Exercise elastic serving once (docs/serving.md "Elastic
    serving"): a 1-replica micro pool ramps up under backlog pressure,
    adopts a fresh checkpoint generation mid-stream (the in-flight
    stream finishes bit-exact on the OLD weights), and retires back
    down through the graceful drain — zero requeues, zero pages on
    the retired replica, every decision postmortemed."""
    print("----------Serving (elastic: autoscale / hot-swap)----------")
    try:
        import os
        import pickle
        import tempfile

        import numpy as np

        import mxtpu as mx
        from mxtpu import nd
        from mxtpu.models.transformer import (
            TransformerLM, transformer_lm_sharding_rules)
        from mxtpu.observability import flight_recording
        from mxtpu.parallel import (PagedContinuousBatchingEngine,
                                    ShardedDecoder)
        from mxtpu.parallel.mesh import DeviceMesh
        from mxtpu.resilience.checkpoint import write_verified
        from mxtpu.serving import Autoscaler, Gateway, replica_pool

        def build_lm(seed):
            mx.random.seed(seed)
            net = TransformerLM(32, units=16, hidden_size=32,
                                num_layers=1, num_heads=2,
                                num_kv_heads=2)
            net.initialize()
            net(nd.array(np.asarray([[1, 2]], dtype=np.int32)))
            return net

        lm, lm_b = build_lm(7), build_lm(23)
        mesh = DeviceMesh(dp=1)
        rules = transformer_lm_sharding_rules()
        fac = lambda i: PagedContinuousBatchingEngine(  # noqa: E731
            lm, mesh, rules, num_slots=1, max_length=32, block_size=8,
            prefill_chunk=8, ledger_tag="probe-el%d" % i)
        gw = Gateway(replica_pool(fac, n=1), hedge_fraction=None)
        asc = Autoscaler(gw, fac, min_replicas=1, max_replicas=2,
                         cooldown_ticks=2)
        rng = np.random.RandomState(1)
        iso_old = ShardedDecoder(lm, mesh, rules)
        prompts = [nd.array(rng.randint(0, 32, (1, 5)), dtype="int32")
                   for _ in range(3)]
        wants_old = [iso_old.generate(p, max_new_tokens=4,
                                      max_length=32).asnumpy()
                     for p in prompts]
        ck = os.path.join(tempfile.mkdtemp(prefix="probe_el_"),
                          "gen1.ckpt")
        dec_b = ShardedDecoder(lm_b, mesh, rules)
        write_verified(ck, pickle.dumps({
            "step": 1, "num_update": 1,
            "params": {p.name: np.asarray(p.data()._data)
                       for p in dec_b._params},
            "opt_states": {}, "scale_state": None, "rng": None}))
        with flight_recording(buffer=64) as fl:
            rids = [gw.submit(p, 4) for p in prompts[:2]]  # 2 > 1
            for _ in range(4):                             # slot:
                gw.pump()                                  # backlog
                asc.tick()
            grew = asc.stats["scale_ups"]
            staged = asc.adopt(ck)      # mid-stream: the in-flight
            for _ in range(200):        # streams pin the OLD weights
                gw.pump()
                asc.tick()
                if not gw.stats["outstanding"]:
                    break
            exact_old = all(
                np.array_equal(gw.result(r).asnumpy(), w)
                for r, w in zip(rids, wants_old))
            r_new = gw.submit(prompts[2], 4)   # post-adopt admission:
            for _ in range(200):               # the NEW generation
                gw.pump()
                asc.tick()
                if not gw.stats["outstanding"]:
                    break
            exact_new = np.array_equal(
                gw.result(r_new).asnumpy(),
                ShardedDecoder(lm_b, mesh, rules).generate(
                    prompts[2], max_new_tokens=4,
                    max_length=32).asnumpy())
            for _ in range(30):         # idle lull: retire back down
                gw.pump()
                asc.tick()
                if len(asc.supervisor.replicas) == 1:
                    break
            st = asc.stats
            gen = max(r.stats().get("param_generation", 0)
                      for r in gw.supervisor.alive)
            pms = [p.kind for p in fl.postmortems]
        print("scaling      : %d scale-up(s), %d retire(s), "
              "%d replica(s) final, cooldown %d tick(s)"
              % (st["scale_ups"], st["retired_replicas"],
                 st["replicas"], st["cooldown_remaining"]))
        print("hot-swap     : %d replica(s) staged gen %d, live "
              "generation %d, %d adoption(s) pushed to late spawns"
              % (len(staged), max(staged.values()) if staged else 0,
                 gen, st["adoptions_pushed"]))
        print("streams      : %d in-flight bit-exact on OLD weights, "
              "1 post-adopt bit-exact on NEW weights, %d requeued"
              % (len(rids), gw.stats["requeued_requests"]))
        healthy = (grew >= 1 and st["retired_replicas"] >= 1
                   and st["replicas"] == 1 and gen >= 1
                   and exact_old and exact_new
                   and gw.stats["requeued_requests"] == 0)
        print("probe        :", "ok (backlog grow -> mid-stream adopt "
              "-> graceful retire, zero requeues, streams bit-exact; "
              "postmortems: %s)" % (sorted(set(pms)) or "none")
              if healthy else
              "UNEXPECTED (grew=%r old=%r new=%r gen=%r stats=%r)"
              % (grew, exact_old, exact_new, gen, st))
    except Exception as e:
        print("elastic      : FAILED (%s: %s)" % (type(e).__name__, e))


def check_resilience():
    """Exercise the fault-injection + retry machinery once (injected
    clock/sleep — no real waiting) and print the process-wide resilience
    counters (docs/resilience.md): a healthy install shows one injected
    fault absorbed by exactly one retry."""
    print("----------Resilience----------")
    try:
        from mxtpu import resilience
        from mxtpu.resilience import RetryPolicy, fault_plan, faults

        print("fault sites  :", ", ".join(faults.SITES))
        print("env plan     :",
              os.environ.get("MXTPU_FAULT_PLAN") or "none")
        # session counters FIRST (through the unified registry — the
        # same keys Prometheus exposition serves) — the probe below
        # must not pollute (and must never reset) what this process
        # actually experienced
        from mxtpu.observability import get_registry
        c = get_registry().snapshot(sources=("resilience",))
        print("counters     : %d retries / %d exhaustions / "
              "%d quarantines / %d deadline evictions / %d sheds"
              % (c["resilience.retries"],
                 c["resilience.retry_exhaustions"],
                 c["resilience.quarantined_slots"],
                 c["resilience.deadline_evictions"],
                 c["resilience.shed_requests"]))
        sleeps = []
        pol = RetryPolicy(max_attempts=3, base_delay=0.01,
                          sleep=sleeps.append)
        with fault_plan("diagnose.probe@1:raise=OSError(probe)"):
            pol.call(faults.inject, "diagnose.probe")
        d = get_registry().delta(c, get_registry().snapshot(
            sources=("resilience",)))
        print("probe        : ok (%d injected fault, %d retry, no real "
              "sleep)" % (d.get("resilience.faults_injected", 0),
                          d.get("resilience.retries", 0)))
    except Exception as e:
        print("resilience   : FAILED (%s: %s)" % (type(e).__name__, e))


def check_guardian():
    """Exercise the verified-checkpoint machinery once (tempdir, tiny
    blobs, one deliberate corruption) and print the guardian counters
    (docs/guardian.md): a healthy install detects the damaged newest
    checkpoint and falls back to the previous good one."""
    print("----------Guardian----------")
    try:
        import tempfile

        from mxtpu import resilience
        from mxtpu.resilience import checkpoint as ckpt

        print("guard default:",
              "on" if resilience.guard_enabled_default() else "off",
              "(MXTPU_GUARDIAN=%s)"
              % (os.environ.get("MXTPU_GUARDIAN") or "unset"))
        print("ckpt keep    : %d (MXTPU_CKPT_KEEP=%s)"
              % (ckpt.default_keep(),
                 os.environ.get("MXTPU_CKPT_KEEP") or "unset"))
        # session counters FIRST (unified-registry keys) — the probe
        # must not pollute the report
        from mxtpu.observability import get_registry
        c = get_registry().snapshot(sources=("resilience",))
        print("counters     : %d skips / %d rollbacks / %d ckpt writes / "
              "%d corruptions / %d fallbacks"
              % (c["resilience.guardian_skips"],
                 c["resilience.guardian_rollbacks"],
                 c["resilience.ckpt_writes"],
                 c["resilience.ckpt_corruptions"],
                 c["resilience.ckpt_fallbacks"]))
        with tempfile.TemporaryDirectory() as d:
            cs = ckpt.CheckpointSet(d, keep=3)
            cs.save(0, b"probe-0")
            cs.save(1, b"probe-1")
            buf = bytearray(open(cs.path(1), "rb").read())
            buf[0] ^= 0xFF
            open(cs.path(1), "wb").write(bytes(buf))
            got = cs.latest_verified()
        if got == (0, b"probe-0"):
            print("probe        : ok (corrupt newest detected, fell back "
                  "to previous good)")
        else:
            print("probe        : UNEXPECTED result %r" % (got,))
    except Exception as e:
        print("guardian     : FAILED (%s: %s)" % (type(e).__name__, e))


def check_observability():
    """Exercise the unified observability layer once (docs/
    observability.md): a traced + flight-recorded micro-engine run
    under a deterministic fault plan — a healthy install records
    tick-clock spans along the full request path, an automatic
    ``fault.<site>`` event, a quarantine postmortem naming the request,
    a valid chrome-trace export, and Prometheus exposition of the
    unified registry (with ZERO extra compiled programs from tracing)."""
    print("----------Observability----------")
    try:
        import json

        import numpy as np

        import mxtpu as mx
        from mxtpu import nd
        from mxtpu.analysis import get_ledger
        from mxtpu.models.transformer import (
            TransformerLM, transformer_lm_sharding_rules)
        from mxtpu.observability import (export_chrome_trace,
                                         flight_recording, get_registry,
                                         tracing)
        from mxtpu.parallel import PagedContinuousBatchingEngine
        from mxtpu.parallel.mesh import DeviceMesh
        from mxtpu.resilience import fault_plan

        print("ambient      : MXTPU_TRACE=%s MXTPU_FLIGHT_BUFFER=%s"
              % (os.environ.get("MXTPU_TRACE") or "unset",
                 os.environ.get("MXTPU_FLIGHT_BUFFER") or "unset"))
        mx.random.seed(7)
        lm = TransformerLM(32, units=16, hidden_size=32, num_layers=1,
                           num_heads=2, num_kv_heads=2)
        lm.initialize()
        eng = PagedContinuousBatchingEngine(
            lm, DeviceMesh(dp=1), transformer_lm_sharding_rules(),
            num_slots=2, max_length=32, block_size=8, prefill_chunk=8)
        rng = np.random.RandomState(0)
        prompt = nd.array(rng.randint(0, 32, (1, 9)), dtype="int32")
        led = get_ledger()
        eng.submit(prompt, 3)
        eng.run()                       # compile everything UNTRACED
        seq = led.sequence()
        with tracing() as tr, flight_recording(64) as fl:
            with fault_plan("serving.step@2:raise=RuntimeError(probe)"):
                eng.submit(prompt, 3, seed=5, temperature=0.7)
                eng.run()
            types = sorted({e.etype for e in tr.events()})
            spans, events = tr.span_count(), len(tr.events())
            pm = fl.postmortems
            record = (fl.postmortem_record(pm[0]) if pm else {})
        extra = len(led.misses_after(seq, sites=("serving.*",)))
        chrome = json.loads(export_chrome_trace())
        reg = get_registry()
        reg.register_stats("diag_engine", eng)
        try:
            prom = reg.to_prometheus()
        finally:
            reg.unregister("diag_engine")
        print("trace        : %d event(s) / %d span(s), types: %s"
              % (events, spans, ", ".join(
                  t for t in types if not t.startswith("engine.") )
                 or "(engine-only)"))
        print("flight       : %d postmortem(s)%s"
              % (len(pm), " — %r over %d timeline event(s)"
                 % (pm[0].kind, sum(len(v) for v in
                                    record.get("requests", {}).values()))
                 if pm else ""))
        print("exports      : chrome traceEvents=%d, prometheus "
              "lines=%d" % (len(chrome.get("traceEvents", ())),
                            len(prom.splitlines())))
        healthy = (events > 0 and spans > 0
                   and "fault.serving.step" in types
                   and pm and pm[0].kind == "quarantine"
                   and extra == 0
                   and "mxtpu_resilience_faults_injected" in prom)
        print("probe        :", "ok (traced faulted run + postmortem + "
              "exports, 0 extra compiled programs)" if healthy
              else "UNEXPECTED (types=%r postmortems=%r extra=%d)"
              % (types, [p.kind for p in pm], extra))
    except Exception as e:
        print("observability: FAILED (%s: %s)" % (type(e).__name__, e))


def check_multistep_trainer():
    """Compile N∈{1,8} trainer windows on a micro model and report the
    compile-ledger program counts plus the donation verdict for the
    fused window (docs/training.md): a healthy install shows ONE
    program per N and the scanned program's params + optimizer state
    aliasing their outputs (D003)."""
    print("----------Trainer (multi-step capture)----------")
    try:
        import numpy as np

        import mxtpu as mx
        from mxtpu import gluon, nd
        from mxtpu.gluon import nn
        from mxtpu.parallel import make_mesh, SPMDTrainer
        from mxtpu.analysis import get_ledger
        from mxtpu.analysis.donation_check import check_trainer_donation

        def build():
            mx.random.seed(3)
            net = nn.Dense(4, in_units=8, prefix="diag_ms_")
            net.initialize()
            return net, SPMDTrainer(
                net, gluon.loss.L2Loss(), "sgd", make_mesh(dp=1),
                optimizer_params={"learning_rate": 1e-2}, guard=True)

        R = np.random.RandomState(0)
        win = np.stack([R.randn(8, 8).astype(np.float32)
                        for _ in range(8)])
        lwin = np.stack([R.randn(8, 4).astype(np.float32)
                         for _ in range(8)])
        led = get_ledger()
        before = led.miss_counts(("spmd_trainer.step",
                                  "spmd_trainer.step_multi"))
        net1, tr1 = build()
        for i in range(8):                      # N=1: the per-step path
            tr1.step(nd.array(win[i]), nd.array(lwin[i]))
        net2, tr2 = build()
        res = tr2.step_window(win, lwin)        # N=8: ONE fused program
        after = led.miss_counts(("spmd_trainer.step",
                                 "spmd_trainer.step_multi"))
        bit_exact = np.array_equal(net1.weight.data().asnumpy(),
                                   net2.weight.data().asnumpy())
        print("programs     : N=1 -> %d (spmd_trainer.step), N=8 -> %d "
              "(spmd_trainer.step_multi)"
              % (after.get("spmd_trainer.step", 0)
                 - before.get("spmd_trainer.step", 0),
                 after.get("spmd_trainer.step_multi", 0)
                 - before.get("spmd_trainer.step_multi", 0)))
        print("window probe : 8 steps, %d applied, host syncs 1, "
              "trajectory %s vs per-step"
              % (res.num_good,
                 "bit-exact" if bit_exact else "MISMATCH"))
        rep = check_trainer_donation(tr2, win[0], lwin[0], n_steps=8)
        d3 = rep.filter(code="D003").diagnostics
        d1 = rep.filter(code="D001").diagnostics
        if d1:
            print("donation     : DROPPED (%d D001)" % len(d1))
            for d in d1:
                print("  ", d)
        elif d3:
            print("donation     : verified — %s" % d3[0].message)
        else:
            print("donation     : no verdict (no donated args?)")
    except Exception as e:
        print("multi-step   : FAILED (%s: %s)" % (type(e).__name__, e))


def check_devices(timeout_s=60):
    print("----------Device Info----------")
    try:
        import jax
        t0 = time.time()
        devs = jax.devices()
        print("backend      :", jax.default_backend())
        print("devices      :", devs)
        print("device query : %.2fs" % (time.time() - t0))
        import jax.numpy as jnp
        import numpy as np
        t0 = time.time()
        x = jnp.ones((256, 256)) @ jnp.ones((256, 256))
        np.asarray(x)  # host transfer = the reliable barrier (PERF.md)
        print("compute      : ok (%.2fs incl. compile)"
              % (time.time() - t0))
    except Exception as e:
        print("devices      : FAILED (%s: %s)" % (type(e).__name__, e))


def check_analysis(full=False):
    """Run the repo's own static analyses (trace-safety lint; with
    --full also the op-registry audit, ~20s of abstract evals) and print
    the summary — the bug-report equivalent of the reference's
    operator-registry dump."""
    print("----------Static Analysis----------")
    try:
        from mxtpu.analysis import audit_registry, trace_lint
        lint = trace_lint()
        print("trace lint     :", lint.summary())
        for d in lint.errors:
            print("  ", d)
        if full:
            import mxtpu.ndarray  # noqa: F401 — populate the registry
            reg = audit_registry()
            print("registry audit :", reg.summary())
            for d in reg.errors:
                print("  ", d)
        else:
            print("registry audit : skipped (pass --full, or run "
                  "`python -m mxtpu.analysis registry`)")
    except Exception as e:
        print("analysis       : FAILED (%s: %s)" % (type(e).__name__, e))
    check_kernel_geometry()


def check_kernel_geometry():
    """Run the kernel_check pass over the shipped Pallas kernels at
    their real TPU serving/training geometries (docs/analysis.md K0xx):
    a healthy checkout verdicts every spec clean and prints each one's
    per-grid-step VMEM price — the pre-compile gate ROADMAP-item-2
    kernels land behind."""
    print("----------Pallas Kernel Geometry----------")
    try:
        from mxtpu.analysis import check_kernels, default_kernel_specs
        specs = default_kernel_specs()
        rep = check_kernels(specs)
        print("kernel specs :", len(specs), "pallas_call geometrie(s) "
              "(flash fwd/bwd, KDA, paged decode+prefill "
              "fp32/int8 incl. tp-sharded)")
        print("verdict      :", rep.summary())
        for d in rep.errors:
            print("  ", d)
        for d in rep.filter(code="M007"):
            print("  %-42s %s" % (d.subject[:42],
                                  d.message.split(", smem")[0]))
        from mxtpu.ops.pallas import counters
        counts = counters.counts()
        if counts:
            print("invocations  :",
                  ", ".join("%s=%d" % kv for kv in sorted(counts.items())))
        else:
            print("invocations  : none this process "
                  "(kernel_invocations.* in the metrics registry)")
    except Exception as e:
        print("kernel check : FAILED (%s: %s)" % (type(e).__name__, e))


def check_environment():
    print("----------Environment----------")
    for k, v in sorted(os.environ.items()):
        if k.startswith(("MXTPU_", "MXNET_", "JAX_", "XLA_", "TPU_",
                         "PALLAS_", "DMLC_")):
            print("%s=%s" % (k, v))


def main():
    full = "--full" in sys.argv[1:]
    check_python()
    check_os()
    check_libraries()
    check_environment()
    check_mxtpu()
    check_serving()
    check_resilience()
    check_guardian()
    check_observability()
    check_multistep_trainer()
    check_analysis(full=full)
    check_devices()


if __name__ == "__main__":
    main()
