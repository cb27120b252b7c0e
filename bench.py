"""Headline benchmarks (BASELINE metrics 1-2).

Line 1: ResNet-50 training throughput, images/sec/chip (config 2:
GluonCV ResNet-50, hybridized train step) — with step-time p50, achieved
TFLOP/s and MFU.
Line 2: BERT-base training samples/sec (config 3: MHA + LayerNorm path).

Each metric prints ONE JSON line:
  {"metric", "value", "unit", "vs_baseline", ...extras}

Runs on the chip or not at all: the battery runs in THIS process, which
then owns the chip (one process per chip — docs/serving.md).  With no
TPU it writes one line to stderr and exits non-zero; a bench that raises
ends the run with its traceback and a non-zero exit, whatever was
printed before.  A number from a CPU run is never printed under a device
metric's name.

vs_baseline for ResNet-50 divides by 375 img/s — the commonly cited
upstream MXNet 1.x fp32 ResNet-50 per-V100 figure (BASELINE.md: the
reference mount was empty both rounds; 375 is the documented midpoint of
the O(300-400) range, to be replaced when the reference number lands).
BERT-base has no number even in upstream's repo (it lives in GluonNLP
docs), so its vs_baseline is null with a note.

MFU accounting: ResNet-50 fwd+bwd ≈ 3 x 4.09 GFLOP/image; BERT fwd+bwd ≈
6 x (non-embedding params) x tokens per sample, over the chip's bf16
peak (PEAK_BF16_FLOPS).
"""

import json
import sys
import time

import os

RESNET_BASELINE_IPS = 375.0
RESNET_FLOPS_PER_IMG = 3 * 4.09e9
#: bf16 peak FLOP/s per chip, keyed by jax's ``device_kind`` (Google
#: Cloud documentation, "TPU v5e": 197 TFLOP/s).  A device that is not
#: here is an error, never a default.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def _peak_flops():
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in PEAK_BF16_FLOPS:
        raise KeyError("no bf16 peak recorded for device kind %r (known: "
                       "%s) — add it to PEAK_BF16_FLOPS with its source"
                       % (kind, sorted(PEAK_BF16_FLOPS)))
    return PEAK_BF16_FLOPS[kind]


def _measure(trainer, X, y, platform, items_per_batch, flops_per_item,
             iters_accel=50, iters_cpu=3):
    """Shared throughput + blocked-p50 + MFU machinery for every model
    bench (factored per round-2 review)."""
    for _ in range(3):  # compile + warm caches
        trainer.step(X, y).asnumpy()

    iters = iters_accel if platform != "cpu" else iters_cpu
    t0 = time.perf_counter()
    loss = None
    for _ in range(iters):
        loss = trainer.step(X, y)
    loss.asnumpy()  # drain the async queue (real host transfer)
    dt = time.perf_counter() - t0
    ips = items_per_batch * iters / dt

    lat = []  # blocked per-step latency (includes host dispatch)
    for _ in range(20 if platform != "cpu" else 3):
        t0 = time.perf_counter()
        trainer.step(X, y).asnumpy()
        lat.append(time.perf_counter() - t0)
    lat.sort()
    p50 = lat[len(lat) // 2]

    peak = _peak_flops()
    achieved = ips * flops_per_item
    return {
        "value": round(ips, 2),
        "iters": iters,
        "step_time_p50_ms": round(p50 * 1e3, 2),
        "achieved_tflops": round(achieved / 1e12, 2),
        "mfu": round(achieved / peak, 4),
        "platform": platform,
    }


def _bench_resnet():
    import numpy as np
    import mxtpu as mx
    from mxtpu import gluon
    from mxtpu.gluon.model_zoo import vision
    from mxtpu.parallel import make_mesh, SPMDTrainer
    import jax

    platform = jax.devices()[0].platform
    # batch 128 + NHWC-internal convs + one-pass bf16 BatchNorm: the
    # profile-driven round-3 config (tools/profile_resnet.py sweep on a
    # real v5e; batch 256/512 measured slower, NCHW-internal 13.2% MFU)
    batch = 128 if platform != "cpu" else 8
    net = vision.resnet50_v1()
    net.initialize()
    net.cast("bfloat16")  # MXU-native compute

    mesh = make_mesh(dp=1)
    trainer = SPMDTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                          "sgd", mesh,
                          optimizer_params={"learning_rate": 0.1,
                                            "momentum": 0.9})
    X = mx.nd.array(np.random.rand(batch, 3, 224, 224), dtype="bfloat16")
    y = mx.nd.array(np.random.randint(0, 1000, (batch,)), dtype="int32")

    m = _measure(trainer, X, y, platform, batch, RESNET_FLOPS_PER_IMG)
    rec = {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "unit": "images/sec",
        "vs_baseline": round(m["value"] / RESNET_BASELINE_IPS, 3),
        "batch": batch,
        **m,
        "baseline_note": "375 img/s = documented placeholder midpoint of "
                         "upstream V100 fp32 range; reference mount empty",
        "bottleneck_note": "HBM-bandwidth-bound on v5e by roofline: "
                           "ResNet-50 fwd+bwd ~140 flops/byte < 240 "
                           "flops/byte ridge; profiler trace shows conv "
                           "fusions at ~92% of 819 GB/s peak, conv "
                           "weight-grads = 43% of step time (PERF.md)",
    }
    print(json.dumps(rec), flush=True)


def _bench_bert():
    import numpy as np
    import mxtpu as mx
    from mxtpu import gluon
    from mxtpu.gluon import HybridBlock
    from mxtpu.models import transformer
    from mxtpu.parallel import make_mesh, SPMDTrainer
    import jax

    platform = jax.devices()[0].platform
    # CPU fallback: a 2-layer/batch-4 sub-config, explicitly labeled —
    # BERT-base at batch 32 cannot finish on the 1-core host within the
    # fallback budget, which left BENCH_r04.json with 1 of 3 metrics
    # (VERDICT r4 item 4: every metric line must print in degraded mode)
    cpu = platform == "cpu"
    batch, seq = (4, 32) if cpu else (32, 128)

    class BertForMLM(HybridBlock):
        """BERT-base with the MLM head as the training output (exercises
        the full encoder + vocab projection: MHA, LayerNorm, GELU path).
        On CPU fallback a labeled 2-layer sub-config substitutes."""

        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                if cpu:
                    self.bert = transformer.BERTModel(
                        units=128, hidden_size=512, num_layers=2,
                        num_heads=4, max_length=seq, dropout=0.0)
                else:
                    self.bert = transformer.bert_base(max_length=seq,
                                                      dropout=0.0)

        def hybrid_forward(self, F, tokens):
            _seq, _pooled, mlm = self.bert(tokens)
            return mlm

    net = BertForMLM()
    net.initialize()
    net.cast("bfloat16")

    class MLMLoss(gluon.loss.Loss):
        def __init__(self):
            super().__init__(1.0, 0)
            self._ce = gluon.loss.SoftmaxCrossEntropyLoss()

        def hybrid_forward(self, F, mlm, labels):
            return self._ce(mlm.reshape((-1, mlm.shape[-1])),
                            labels.reshape((-1,)))

    mesh = make_mesh(dp=1)
    trainer = SPMDTrainer(net, MLMLoss(), "adam", mesh,
                          optimizer_params={"learning_rate": 1e-4})
    X = mx.nd.array(np.random.randint(0, 30522, (batch, seq)), dtype="int32")
    y = mx.nd.array(np.random.randint(0, 30522, (batch, seq)), dtype="int32")

    # 6ND approximation on matmul-bearing (non-embedding-lookup) params;
    # the tied mlm vocab projection IS a matmul so it stays in the count.
    # NOTE: excludes the QK^T/AV attention matmuls (~8% more FLOPs at
    # seq=128), so the reported MFU UNDERSTATES true utilization.
    n_params = 0
    for p in net.collect_params().values():
        if "embed" in p.name and "weight" in p.name:
            continue
        n_params += int(np.prod(p.shape))
    flops_per_sample = 6 * n_params * seq

    m = _measure(trainer, X, y, platform, batch, flops_per_sample)
    rec = {
        "metric": "bert_base_train_samples_per_sec_per_chip",
        "unit": "samples/sec",
        "vs_baseline": None,
        "batch": batch,
        "seq_len": seq,
        **m,
        "baseline_note": "no in-repo reference number (BERT perf lives in "
                         "GluonNLP docs); reference mount empty",
        "flops_note": "6ND count omits QK^T/AV attention matmuls (~8% at "
                      "seq=128): reported MFU understates utilization",
    }
    if cpu:
        rec["config_note"] = ("CPU fallback runs a LABELED 2-layer/"
                              "units-128 sub-config at batch 4 — plumbing "
                              "evidence only, NOT a BERT-base number")
    print(json.dumps(rec), flush=True)


def _bench_attention():
    """Long-sequence attention fwd+bwd (round-3 verdict item 5: measure
    the flash-attention backward instead of assuming it).  seq 512 and
    2048, bf16, causal — the LM training configuration."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from mxtpu.ops.pallas.flash_attention import flash_attention

    platform = jax.devices()[0].platform
    if platform == "cpu":
        # structured skip record: one parseable line per metric
        print(json.dumps({
            "metric": "flash_attention_fwd_bwd_tflops_seq2048",
            "value": None,
            "unit": "TFLOP/s",
            "vs_baseline": None,
            "skipped": True,
            "platform": platform,
            "skip_reason": "interpret-mode Pallas on CPU is a correctness "
                           "tool, not a benchmark — metric only "
                           "meaningful on TPU",
        }), flush=True)
        return

    B, H, D = 8, 16, 64
    rng = np.random.RandomState(0)
    results = {}
    for T in (512, 2048):
        q, k, v = (jnp.asarray(rng.randn(B, H, T, D), jnp.bfloat16)
                   for _ in range(3))

        def loss(q, k, v):
            return flash_attention(q, k, v, causal=True).astype(
                jnp.float32).sum()

        step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        jax.block_until_ready(step(q, k, v))  # compile
        iters = 20
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = step(q, k, v)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / iters
        # causal fwd+bwd matmul flops: (4 + 8) * B*H*T^2*D / 2
        flops = 12 * B * H * T * T * D / 2
        results[T] = {"step_ms": round(dt * 1e3, 3),
                      "tflops": round(flops / dt / 1e12, 2)}
    rec = {
        "metric": "flash_attention_fwd_bwd_tflops_seq2048",
        "value": results[2048]["tflops"],
        "unit": "TFLOP/s",
        "vs_baseline": None,
        "platform": platform,
        "config": {"batch": B, "heads": H, "head_dim": D,
                   "dtype": "bfloat16", "causal": True,
                   "backward": "one pallas kernel (dq, dk, dv)"},
        "seq_512": results[512],
        "seq_2048": results[2048],
        "baseline_note": "no upstream analogue (reference has no "
                         "flash-attention); absolute TFLOP/s vs 197 peak",
    }
    print(json.dumps(rec), flush=True)


def _bench_continuous_decode():
    """Serving throughput (round-6 tentpole): continuous batching with
    slot-based KV cache reuse vs static run-to-completion batches, under
    mixed-length Poisson arrivals — the workload where a static batch
    pays max(prompt) padding and max(new) decode for every member while
    the slot pool backfills freed rows mid-flight.  Reports useful
    (requested) tokens/sec for both schedulers; the CPU fallback runs a
    LABELED tiny config (plumbing evidence, per bench conventions)."""
    import numpy as np
    import jax
    import mxtpu as mx
    from mxtpu import nd
    from mxtpu.models import transformer
    from mxtpu.parallel import (ContinuousBatchingEngine, ShardedDecoder,
                                make_mesh)
    from mxtpu.parallel.decode import _bucket

    platform = jax.devices()[0].platform
    cpu = platform == "cpu"
    mx.random.seed(7)
    if cpu:
        lm = transformer.llama_tiny(vocab_size=256)
        slots, n_req, max_len = 4, 10, 64
        plo, phi, glo, ghi, vocab = 4, 24, 4, 16, 256
    else:
        # real-architecture reduced config (llama geometry, head_dim
        # 128) sized to decode comfortably within the child budget —
        # this metric prints LAST, so it must fit the remaining slice
        lm = transformer.llama_3_8b(vocab_size=32000, width_factor=0.25,
                                    depth_factor=0.25)
        slots, n_req, max_len = 8, 16, 256
        plo, phi, glo, ghi, vocab = 16, 96, 16, 64, 32000
    lm.initialize()
    mesh = make_mesh(dp=1)
    rules = transformer.transformer_lm_sharding_rules()

    R = np.random.RandomState(0)
    plens = R.randint(plo, phi + 1, n_req)
    news = R.randint(glo, ghi + 1, n_req).tolist()
    prompts = [nd.array(R.randint(0, vocab, (1, int(t))), dtype="int32")
               for t in plens]
    # Poisson arrivals measured in scheduler iterations: requests trickle
    # in while earlier ones decode, so short requests meet long ones
    arrivals = np.cumsum(R.poisson(2, size=n_req))
    useful = float(sum(news))

    eng = ContinuousBatchingEngine(lm, mesh, rules, num_slots=slots,
                                   max_length=max_len)
    from mxtpu.analysis import get_ledger
    _led = get_ledger()
    _serving_compiles_before = sum(
        _led.miss_counts(("serving.*",)).values())

    def run_continuous(retries=0):
        it, nxt, rids = 0, 0, []
        t0 = time.perf_counter()
        while nxt < n_req or eng.pending or eng.active:
            while nxt < n_req and arrivals[nxt] <= it:
                rids.append(eng.submit(prompts[nxt], news[nxt],
                                       retries=retries))
                nxt += 1
            if eng.pending or eng.active:
                eng.step()
            it += 1
        eng.run()  # collect/clear results
        dt = time.perf_counter() - t0
        return dt, sum(1 for r in rids if eng.status(r) != "ok")

    dec = ShardedDecoder(lm, mesh, rules)

    def run_static():
        # run-to-completion batches in arrival order: every member pays
        # the batch max prompt (right-padded) and max decode length
        t0 = time.perf_counter()
        for s in range(0, n_req, slots):
            bp, bn = prompts[s:s + slots], news[s:s + slots]
            tmax = max(p.shape[1] for p in bp)
            arr = np.zeros((len(bp), tmax), np.int32)
            for i, p in enumerate(bp):
                arr[i, :p.shape[1]] = p.asnumpy()[0]
            dec.generate(nd.array(arr, dtype="int32"),
                         max_new_tokens=max(bn),
                         max_length=_bucket(tmax + max(bn)))
        return time.perf_counter() - t0

    run_continuous()           # compile warmup (programs live on eng)
    cont_dt, _ = run_continuous()
    run_static()               # compile warmup (programs live on dec)
    static_dt = run_static()
    cont_tps = useful / cont_dt
    static_tps = useful / static_dt

    rec = {
        "metric": "decode_tokens_per_sec_continuous",
        "value": round(cont_tps, 2),
        "unit": "tokens/sec",
        "vs_baseline": None,
        "platform": platform,
        "static_batch_tokens_per_sec": round(static_tps, 2),
        "speedup_vs_static": round(cont_tps / static_tps, 3),
        "config": {"num_slots": slots, "requests": n_req,
                   "prompt_len": [plo, phi], "new_tokens": [glo, ghi],
                   "max_length": max_len,
                   "arrivals": "poisson(2)/iteration"},
        "compiled_programs": len(eng._dec._jit_cache),
        # ledger-counted programs for the whole mixed-length workload
        # (warmup + timed + the static column's decoder): the number the
        # O(log T) discipline bounds, tracked numerically per round
        "compiled_program_count": sum(
            _led.miss_counts(("serving.*",)).values())
        - _serving_compiles_before,
        "baseline_note": "no upstream analogue (reference has no serving "
                         "path); static-batch column is this repo's own "
                         "run-to-completion ShardedDecoder and IGNORES "
                         "arrival delays (an upper bound for static — "
                         "the engine pays the Poisson trickle)",
    }
    if cpu:
        rec["config_note"] = ("CPU fallback runs a LABELED llama_tiny "
                              "config — plumbing evidence only, NOT a "
                              "TPU serving number")
    print(json.dumps(rec), flush=True)

    # -- degraded mode (round-9 tentpole: mxtpu.resilience) --------------
    # Same workload under a DETERMINISTIC 1%-step-failure plan (every
    # 100th per-slot step-site hit raises; counter-driven, replayable
    # bit-for-bit) with retries=2 per request: failed slots quarantine,
    # restart from scratch, and the engine keeps serving — the metric is
    # useful (requested) tokens/sec including all retry waste.
    from mxtpu.observability import get_registry
    from mxtpu.resilience import fault_plan

    # counter deltas through the unified metrics registry (the same
    # keys diagnose and the Prometheus exposition serve)
    reg = get_registry()
    reg.register_stats("bench_engine", eng, replace=True)
    plan_spec = "serving.step%100:raise=RuntimeError(injected)"
    s0 = reg.snapshot(sources=("bench_engine",))
    with fault_plan(plan_spec):
        deg_dt, deg_failed = run_continuous(retries=2)
    ds = reg.delta(s0, reg.snapshot(sources=("bench_engine",)))
    reg.unregister("bench_engine")
    deg_tps = useful / deg_dt
    rec = {
        "metric": "decode_tokens_per_sec_degraded",
        "value": round(deg_tps, 2),
        "unit": "tokens/sec",
        "vs_baseline": None,
        "platform": platform,
        "fault_free_tokens_per_sec": round(cont_tps, 2),
        "degradation_vs_fault_free": round(deg_tps / cont_tps, 3),
        "fault_plan": plan_spec,
        "quarantined": ds.get("bench_engine.quarantined_requests", 0),
        "retries": ds.get("bench_engine.retried_requests", 0),
        # honesty guard: the numerator is REQUESTED tokens — any request
        # that exhausted its retries did not deliver, so a non-zero
        # count here flags the headline number as an overstatement
        "undelivered_requests": deg_failed,
        "config": {"num_slots": slots, "requests": n_req,
                   "retries_per_request": 2,
                   "arrivals": "poisson(2)/iteration"},
        "baseline_note": "no upstream analogue (reference serving has no "
                         "failure path at all — the comparison column is "
                         "this repo's own fault-free continuous run); "
                         "value counts REQUESTED tokens — see "
                         "undelivered_requests",
    }
    if cpu:
        rec["config_note"] = ("CPU fallback runs a LABELED llama_tiny "
                              "config — plumbing evidence only, NOT a "
                              "TPU serving number")
    print(json.dumps(rec), flush=True)


def _bench_trace_overhead():
    """Observability overhead (round-19 tentpole): the SAME
    continuous-decode rig driven tracer-off vs tracer-on
    (docs/observability.md).  Tracing is host-side bookkeeping on a
    deterministic tick clock, so the DETERMINISTIC evidence is (a) the
    span/event counts the traced arm records and (b) ZERO extra
    compiled programs (compile-ledger delta, asserted in-record — the
    acceptance bar: observability never perturbs compile discipline);
    the CPU wall-clock overhead percentage is reported NOISE-labeled
    per bench conventions.  Runs the tiny rig on every platform — the
    overhead under measurement is host python, not device compute."""
    import numpy as np
    import jax
    import mxtpu as mx
    from mxtpu import nd
    from mxtpu.models import transformer
    from mxtpu.analysis import get_ledger
    from mxtpu.observability import get_flight, get_tracer, tracing
    from mxtpu.parallel import ContinuousBatchingEngine, make_mesh

    platform = jax.devices()[0].platform
    mx.random.seed(7)
    lm = transformer.llama_tiny(vocab_size=256)
    lm.initialize()
    mesh = make_mesh(dp=1)
    rules = transformer.transformer_lm_sharding_rules()
    slots, n_req = 4, 10
    eng = ContinuousBatchingEngine(lm, mesh, rules, num_slots=slots,
                                   max_length=64)
    R = np.random.RandomState(0)
    prompts = [nd.array(R.randint(0, 256, (1, int(t))), dtype="int32")
               for t in R.randint(4, 25, n_req)]
    news = R.randint(4, 17, n_req).tolist()
    arrivals = np.cumsum(R.poisson(2, size=n_req))

    def drive():
        it, nxt = 0, 0
        t0 = time.perf_counter()
        while nxt < n_req or eng.pending or eng.active:
            while nxt < n_req and arrivals[nxt] <= it:
                eng.submit(prompts[nxt], news[nxt], seed=nxt,
                           temperature=0.5)
                nxt += 1
            if eng.pending or eng.active:
                eng.step()
            it += 1
        eng.run()
        return time.perf_counter() - t0

    # the baseline arm must be GENUINELY untraced: ambient MXTPU_TRACE=1
    # or MXTPU_FLIGHT_BUFFER would otherwise arm the tracer (or a flight
    # sink) during the "off" measurement and leave tracing() restoring
    # enabled=True on exit
    tr0, fl0 = get_tracer(), get_flight()
    ambient_trace, ambient_flight = tr0.enabled, fl0.active
    fl0.disable()
    tr0.disable()
    try:
        led = get_ledger()
        drive()                          # compile warmup
        off_dt = drive()                 # tracer OFF (the baseline)
        seq = led.sequence()
        with tracing() as tr:
            on_dt = drive()              # tracer ON, same workload
            spans = tr.span_count()
            events = len(tr.events())
        assert not tr0.active, "tracing context leaked"
        extra_programs = len(led.misses_after(seq, sites=("serving.*",)))
    finally:
        if ambient_flight:
            fl0.enable(reset=False)
        if ambient_trace:
            tr0.enable(reset=False)
    overhead_pct = 100.0 * (on_dt - off_dt) / off_dt
    rec = {
        "metric": "trace_overhead_pct",
        "value": round(overhead_pct, 1),
        "unit": "% wall-clock (CPU host, NOISE)",
        "vs_baseline": None,
        "platform": platform,
        # the deterministic evidence: what the traced arm recorded and
        # what it compiled (nothing)
        "trace_spans": spans,
        "trace_events": events,
        "extra_compiled_programs": extra_programs,
        "zero_compile_perturbation": bool(extra_programs == 0),
        "tracer_off_s_NOISE": round(off_dt, 3),
        "tracer_on_s_NOISE": round(on_dt, 3),
        "config": {"num_slots": slots, "requests": n_req,
                   "model": "llama_tiny", "seeded_sampled": True,
                   "arrivals": "poisson(2)/iteration"},
        "baseline_note": "wall-clock pct is NOISE-DOMINATED on the "
                         "oversubscribed CPU host (tiny host-bound "
                         "rig); the span/event counts and the ZERO "
                         "extra compiled programs are the "
                         "deterministic evidence",
    }
    assert extra_programs == 0, \
        "tracing must add zero compiled programs, got %d" % extra_programs
    print(json.dumps(rec), flush=True)


def _bench_paged_decode():
    """Paged-KV-cache serving (round-12 tentpole): the block-paged
    engine with cross-request prefix sharing + chunked prefill vs the
    slot engine AT THE SAME CACHE HBM, under Poisson mixed-length
    arrivals where every prompt opens with one shared system prompt.
    Two metrics:

    - ``slots_resident_at_fixed_hbm``: peak concurrently-resident
      requests.  The slot engine's ceiling is its slot count (each slot
      reserves max_length positions); the paged engine spends the same
      pool bytes page-by-page — right-sized allocation + refcounted
      shared prefix pages — so more requests fit.
    - ``decode_tokens_per_sec_paged``: useful tokens/sec on the same
      workload, slot-engine column alongside.

    CPU fallback runs a LABELED tiny config (plumbing evidence only)."""
    import numpy as np
    import jax
    import mxtpu as mx
    from mxtpu import nd
    from mxtpu.models import transformer
    from mxtpu.parallel import (ContinuousBatchingEngine,
                                PagedContinuousBatchingEngine, make_mesh)

    platform = jax.devices()[0].platform
    cpu = platform == "cpu"
    mx.random.seed(7)
    if cpu:
        lm = transformer.llama_tiny(vocab_size=256)
        slots, n_req, max_len = 4, 12, 64
        sys_len, plo, phi, glo, ghi, vocab = 12, 4, 12, 8, 16, 256
        block_size, chunk, lane_mult = 8, 16, 3
    else:
        lm = transformer.llama_3_8b(vocab_size=32000, width_factor=0.25,
                                    depth_factor=0.25)
        slots, n_req, max_len = 8, 24, 256
        sys_len, plo, phi, glo, ghi, vocab = 48, 16, 48, 24, 64, 32000
        block_size, chunk, lane_mult = 16, 64, 3
    lm.initialize()
    mesh = make_mesh(dp=1)
    rules = transformer.transformer_lm_sharding_rules()

    R = np.random.RandomState(0)
    system = R.randint(0, vocab, (1, sys_len))
    plens = R.randint(plo, phi + 1, n_req)
    news = R.randint(glo, ghi + 1, n_req).tolist()
    prompts = [nd.array(np.concatenate(
        [system, R.randint(0, vocab, (1, int(t)))], axis=1),
        dtype="int32") for t in plens]
    # dense Poisson arrivals: demand outpaces completions, so peak
    # residency measures the ENGINE's ceiling, not the workload's
    arrivals = np.cumsum(R.poisson(1, size=n_req))
    useful = float(sum(news))

    # EQUAL cache HBM: the paged pool holds exactly the bytes the slot
    # engine's (slots x max_len) rows hold; only the paged engine gets
    # extra scheduler LANES (host bookkeeping, not cache bytes) so the
    # freed bytes can actually become concurrency
    paged = PagedContinuousBatchingEngine(
        lm, mesh, rules, num_slots=slots * lane_mult,
        max_length=max_len, block_size=block_size,
        num_blocks=slots * max_len // block_size, prefill_chunk=chunk)
    slot_eng = ContinuousBatchingEngine(lm, mesh, rules,
                                        num_slots=slots,
                                        max_length=max_len)
    from mxtpu.analysis import get_ledger
    _led = get_ledger()
    _paged_before = sum(_led.miss_counts(
        ("serving.page_prefill", "serving.step_pages")).values())

    def drive(eng):
        it, nxt, peak = 0, 0, 0
        t0 = time.perf_counter()
        while nxt < n_req or eng.pending or eng.active:
            while nxt < n_req and arrivals[nxt] <= it:
                eng.submit(prompts[nxt], news[nxt])
                nxt += 1
            if eng.pending or eng.active:
                eng.step()
            peak = max(peak, eng.active)
            it += 1
        eng.run()  # collect/clear results
        return time.perf_counter() - t0, peak

    drive(paged)                   # compile warmup
    s0 = paged.stats               # counters below are timed-drive deltas
    paged_dt, paged_peak = drive(paged)
    drive(slot_eng)                # compile warmup
    slot_dt, slot_peak = drive(slot_eng)
    st = paged.stats
    cfg = {"slot_engine_slots": slots, "paged_lanes": slots * lane_mult,
           "requests": n_req, "system_prompt_len": sys_len,
           "prompt_len": [sys_len + plo, sys_len + phi],
           "new_tokens": [glo, ghi], "max_length": max_len,
           "block_size": block_size, "prefill_chunk": chunk,
           "num_blocks": slots * max_len // block_size,
           "arrivals": "poisson(1)/iteration"}
    rec = {
        "metric": "slots_resident_at_fixed_hbm",
        "value": paged_peak,
        "unit": "concurrent requests",
        "vs_baseline": None,
        "platform": platform,
        "slot_engine_peak": slot_peak,
        "residency_gain_vs_slot_engine": round(
            paged_peak / max(slot_peak, 1), 3),
        "prefix_hits": (st["prefix_hit_requests"]
                        - s0["prefix_hit_requests"]),
        "cow_copies": st["cow_copied_blocks"] - s0["cow_copied_blocks"],
        "config": cfg,
        "baseline_note": "both engines hold IDENTICAL cache bytes "
                         "(paged pool == slot rows); the slot column is "
                         "hard-capped at its slot count by construction "
                         "— the gain is right-sized page allocation + "
                         "refcounted shared system-prompt pages",
    }
    if cpu:
        rec["config_note"] = ("CPU fallback runs a LABELED llama_tiny "
                              "config — plumbing evidence only, NOT a "
                              "TPU serving number")
    print(json.dumps(rec), flush=True)

    rec = {
        "metric": "decode_tokens_per_sec_paged",
        "value": round(useful / paged_dt, 2),
        "unit": "tokens/sec",
        "vs_baseline": None,
        "platform": platform,
        "slot_engine_tokens_per_sec": round(useful / slot_dt, 2),
        "speedup_vs_slot_engine": round(slot_dt / paged_dt, 3),
        "compiled_program_count": sum(_led.miss_counts(
            ("serving.page_prefill", "serving.step_pages")).values())
        - _paged_before,
        "config": cfg,
        "baseline_note": "no upstream analogue; comparison column is "
                         "this repo's own slot engine on the identical "
                         "shared-system-prompt workload at identical "
                         "cache HBM",
    }
    if cpu:
        rec["config_note"] = ("CPU fallback runs a LABELED llama_tiny "
                              "config — plumbing evidence only, NOT a "
                              "TPU serving number; on the oversubscribed "
                              "CPU host this wall-clock comparison is "
                              "NOISE-DOMINATED (0.6x-1.9x observed across "
                              "identical runs) — the deterministic "
                              "slots_resident_at_fixed_hbm record above "
                              "is the HBM-side evidence; TPU tokens/s: "
                              "not measured")
    print(json.dumps(rec), flush=True)


def _bench_kernel_traffic():
    """Serving-kernel memory accounting (round-16 tentpole): the
    deterministic evidence for the kernel-default fast path (counts,
    not a speed).  ``kernel_hbm_traffic`` sweeps the
    REAL scalar-prefetch index maps over the full grid (exact host
    math, no compile, no wall clock anywhere in this record):

    - decode: page-pool fetches are O(valid pages) — one DMA per
      table-live page per kv-head walk — vs one fetch per grid step
      on the gather path;
    - prefill: per-grid-step VMEM residency of the chunked kernel vs
      the ~2 MiB/row the XLA path materializes at T=2048 fp32."""
    import numpy as np
    import jax
    from mxtpu.analysis import kernel_hbm_traffic, kernel_vmem_estimate
    from mxtpu.ops.pallas import paged_attention as pa
    from mxtpu.ops.pallas import prefill_attention as pf

    platform = jax.devices()[0].platform
    B, KV, rep, D, bs, L = 16, 8, 4, 128, 16, 2048
    M = L // bs
    R = np.random.RandomState(0)
    pos = R.randint(1, L, B).astype(np.int32)
    nv = np.minimum(pos // bs + 1, M).astype(np.int32)
    tables = np.zeros((B, M), np.int32)
    perm = R.permutation(np.arange(1, B * M + 1)).astype(np.int32)
    off = 0
    for b in range(B):
        tables[b, :nv[b]] = perm[off:off + nv[b]]
        off += nv[b]
    spec = pa.kernel_spec(B=B, KV=KV, rep=rep, W=1, D=D, block_size=bs,
                          max_length=L, num_blocks=B * M + 1,
                          tables=tables, pos=pos)
    tr = kernel_hbm_traffic(spec)
    pool = {n: tr["per_operand"][n] for n in ("pool_k", "pool_v")}
    valid = int(nv.sum())
    grid = tr["grid_points"]
    fetches = sum(p["fetches"] for p in pool.values())
    rec = {
        "metric": "decode_pool_fetches_vs_grid_steps",
        "value": fetches,
        "unit": "page DMAs (K+V)",
        "vs_baseline": 2 * grid,   # gather path: every step refetches
        "platform": platform,
        "valid_pages": valid,
        "grid_points": grid,
        "traffic_ratio_vs_gather": round(fetches / (2 * grid), 4),
        "pool_bytes": sum(p["bytes"] for p in pool.values()),
        "config": {"B": B, "KV": KV, "rep": rep, "D": D,
                   "block_size": bs, "max_length": L,
                   "fill": "uniform(1, max_length) seeded"},
        "baseline_note": "DETERMINISTIC: exact index-map sweep "
                         "(analysis.kernel_hbm_traffic), bit-stable "
                         "across reruns; baseline is one pool fetch "
                         "per grid step x2 operands (the gather "
                         "path's traffic at the same geometry)",
    }
    assert fetches <= 2 * (KV * valid + B * KV), "O(valid pages) broken"
    print(json.dumps(rec), flush=True)

    pspec = pf.kernel_spec(T=128, KV=KV, rep=rep, D=D, block_size=bs,
                           max_length=L, start_pos=L - 128)
    est = kernel_vmem_estimate(pspec)
    xla_row = 2 * L * D * 4                    # K+V rows, fp32
    rec = {
        "metric": "prefill_chunk_tile_vmem_bytes",
        "value": est["total_bytes"],
        "unit": "bytes/grid-step",
        "vs_baseline": xla_row,
        "platform": platform,
        "residency_gain_vs_xla_rows": round(xla_row / est["total_bytes"],
                                            2),
        "config": {"T": 128, "KV": KV, "rep": rep, "D": D,
                   "block_size": bs, "max_length": L,
                   "start_pos": L - 128, "cache_dtype": "float32"},
        "baseline_note": "DETERMINISTIC: kernel_vmem_estimate cost "
                         "model (double-buffered tiles + scratch) vs "
                         "the full fp32 K+V rows the XLA gather arm "
                         "materializes per (slot, kv-head) at T=2048; "
                         "tier-1 pins the >=4x floor",
    }
    assert xla_row >= 4 * est["total_bytes"]
    print(json.dumps(rec), flush=True)


def _bench_hierarchical_cache():
    """Hierarchical prefix cache (round-15 tentpole): persistent HBM
    pinning + host-RAM tiering + multi-turn sessions vs the overlap-
    only sharing of PR 7, on a BURSTY, SESSION-STRUCTURED Poisson
    workload — bursts of conversation turns separated by full drains
    (traffic lulls), every prompt opening with one shared system
    prompt.  The overlap-only engine loses all sharing at every lull
    and re-prefills whole transcripts each turn; the hierarchical
    engine pins chains across lulls and reuses each session's pages.
    Two metrics, both DETERMINISTIC host counters:

    - ``prefill_tokens_avoided``: prompt tokens whose prefill was
      skipped (radix hit on pinned/restored/session pages).
      Acceptance: >= 2x the overlap-only engine's count.
    - ``prefix_hit_rate_bursty``: admissions that hit at least one
      shared/pinned page.

    CPU wall-clock is reported as an extra and NOISE-labeled; the
    counters are the evidence."""
    import numpy as np
    import jax
    import mxtpu as mx
    from mxtpu import nd
    from mxtpu.models import transformer
    from mxtpu.parallel import PagedContinuousBatchingEngine, make_mesh

    platform = jax.devices()[0].platform
    cpu = platform == "cpu"
    mx.random.seed(7)
    if cpu:
        lm = transformer.llama_tiny(vocab_size=256)
        slots, max_len, bs, chunk = 4, 96, 8, 16
        n_sessions, n_turns, sys_len, msg_lo, msg_hi, glo, ghi = \
            4, 4, 16, 4, 8, 4, 8
        # pool sized so later turn-bursts create POOL PRESSURE: session
        # chains spill to the host tier and swap back in at the next
        # turn — the full three-tier round trip under one workload
        vocab, num_blocks = 256, 20
    else:
        lm = transformer.llama_3_8b(vocab_size=32000, width_factor=0.25,
                                    depth_factor=0.25)
        slots, max_len, bs, chunk = 8, 512, 16, 64
        n_sessions, n_turns, sys_len, msg_lo, msg_hi, glo, ghi = \
            8, 4, 64, 16, 32, 16, 32
        vocab, num_blocks = 32000, 512
    lm.initialize()
    mesh = make_mesh(dp=1)
    rules = transformer.transformer_lm_sharding_rules()

    R = np.random.RandomState(0)
    system = R.randint(0, vocab, (1, sys_len))
    # session-structured turns: turn prompts are built from the LIVE
    # transcript as each engine emits it, so both engines see the
    # identical token streams (greedy decode, identical models)
    first_msgs = [R.randint(0, vocab, (1, int(R.randint(msg_lo,
                                                        msg_hi + 1))))
                  for _ in range(n_sessions)]
    next_msgs = [[R.randint(0, vocab, (1, int(R.randint(msg_lo,
                                                        msg_hi + 1))))
                  for _ in range(n_turns - 1)]
                 for _ in range(n_sessions)]
    news = R.randint(glo, ghi + 1, size=(n_sessions, n_turns))
    # bursty Poisson arrivals WITHIN each turn-burst (in scheduler
    # iterations); the drain between bursts is the lull
    offsets = np.cumsum(R.poisson(1, size=(n_turns, n_sessions)),
                        axis=1)

    from mxtpu.analysis import get_ledger
    _led = get_ledger()
    _swap_before = sum(_led.miss_counts(("serving.swap",)).values())

    def drive(use_sessions):
        eng = PagedContinuousBatchingEngine(
            lm, mesh, rules, num_slots=slots, max_length=max_len,
            block_size=bs, num_blocks=num_blocks, prefill_chunk=chunk,
            pin_bytes="256MiB" if use_sessions else 0,
            host_cache_bytes="1GiB" if use_sessions else 0)
        transcripts = [np.asarray(system) for _ in range(n_sessions)]
        for s in range(n_sessions):
            transcripts[s] = np.concatenate(
                [transcripts[s], first_msgs[s]], axis=1)
        t0 = time.perf_counter()
        for turn in range(n_turns):
            rids, nxt, it = {}, 0, 0
            while nxt < n_sessions or eng.pending or eng.active:
                while nxt < n_sessions and offsets[turn][nxt] <= it:
                    s = nxt
                    rids[s] = eng.submit(
                        nd.array(transcripts[s], dtype="int32"),
                        int(news[s][turn]),
                        session=("s%d" % s) if use_sessions else None)
                    nxt += 1
                if eng.pending or eng.active:
                    eng.step()
                it += 1
            res = eng.run()            # full drain = the lull
            for s in range(n_sessions):
                transcripts[s] = np.asarray(res[rids[s]].asnumpy())
                if turn < n_turns - 1:
                    transcripts[s] = np.concatenate(
                        [transcripts[s], next_msgs[s][turn]], axis=1)
        dt = time.perf_counter() - t0
        st = eng.stats
        for s in range(n_sessions):
            eng.close_session("s%d" % s)
        admissions = n_sessions * n_turns
        return st, dt, st["prefix_hit_requests"] / admissions, transcripts

    st_h, dt_h, rate_h, tr_h = drive(True)
    st_o, dt_o, rate_o, tr_o = drive(False)
    # identical greedy streams on both engines: the counters compare
    # the same work, and the hierarchy changed no output
    streams_equal = all(np.array_equal(a, b)
                        for a, b in zip(tr_h, tr_o))
    gain = (st_h["prefill_tokens_avoided"]
            / max(st_o["prefill_tokens_avoided"], 1))
    cfg = {"sessions": n_sessions, "turns": n_turns,
           "system_prompt_len": sys_len,
           "message_len": [msg_lo, msg_hi],
           "new_tokens": [glo, ghi], "slots": slots,
           "max_length": max_len, "block_size": bs,
           "num_blocks": num_blocks, "prefill_chunk": chunk,
           "arrivals": "poisson(1)/iteration within each burst, "
                       "full drain (lull) between bursts"}
    rec = {
        "metric": "prefill_tokens_avoided",
        "value": int(st_h["prefill_tokens_avoided"]),
        "unit": "prompt tokens skipped",
        "vs_baseline": None,
        "platform": platform,
        "overlap_only_avoided": int(st_o["prefill_tokens_avoided"]),
        "gain_vs_overlap_only": round(gain, 3),
        "session_hits": int(st_h["session_hit_requests"]),
        "pinned_blocks_peak_end": int(st_h["pinned_blocks"]),
        "spilled_blocks_end": int(st_h["spilled_blocks"]),
        "swap_ins": int(st_h["swapped_in_blocks"]),
        "swap_outs": int(st_h["swapped_out_blocks"]),
        "streams_bit_identical_to_overlap_only": streams_equal,
        "compiled_program_count_swap": sum(_led.miss_counts(
            ("serving.swap",)).values()) - _swap_before,
        "config": cfg,
        "baseline_note": "comparison column is this repo's own paged "
                         "engine with PR-7 overlap-only sharing on the "
                         "IDENTICAL bursty session workload; counters "
                         "are deterministic host-side page math "
                         "(acceptance: gain >= 2x; the lull drains kill "
                         "overlap-only sharing by construction)",
    }
    if cpu:
        rec["config_note"] = ("CPU fallback runs a LABELED llama_tiny "
                              "config — plumbing evidence only")
    print(json.dumps(rec), flush=True)

    rec = {
        "metric": "prefix_hit_rate_bursty",
        "value": round(rate_h, 3),
        "unit": "admissions hitting shared/pinned pages",
        "vs_baseline": None,
        "platform": platform,
        "overlap_only_hit_rate": round(rate_o, 3),
        "prefill_tokens_avoided": int(st_h["prefill_tokens_avoided"]),
        "wall_s_hierarchical": round(dt_h, 2),
        "wall_s_overlap_only": round(dt_o, 2),
        "config": cfg,
        "baseline_note": "deterministic admission counters; the wall_s "
                         "extras are CPU host wall-clock and NOISE-"
                         "DOMINATED on the oversubscribed builder — the "
                         "hit-rate/avoided-token counters are the "
                         "evidence; TPU tokens/s: not measured",
    }
    if cpu:
        rec["config_note"] = ("CPU fallback runs a LABELED llama_tiny "
                              "config — plumbing evidence only")
    print(json.dumps(rec), flush=True)


def _bench_router():
    """Multi-replica serving (round-17 tentpole): the supervised
    replica pool + prefix-locality router + QoS gateway of
    ``mxtpu.serving`` on a BURSTY Poisson workload whose prompts open
    with one shared system prompt.  Four deterministic arms:

    - 2-replica LOCALITY pool (headline): time-to-first-token p50/p99
      measured in gateway TICKS (pump iterations — a host counter, so
      the latency distribution is bit-reproducible) + the router's
      prefix-hit-rate counters;
    - 2-replica ROUND-ROBIN control: identical workload, placement
      blind to locality — the hit-rate gap is the router's win and the
      record asserts locality > round-robin;
    - SINGLE replica: the ttft distribution the pool is compared to;
    - FAULT arm: the same locality pool under a 1%% ``replica.health``
      plan (every 100th probe fails, fail_threshold=1, probation
      revival) — replica deaths, drained-and-requeued request counts
      (the ``steps_to_recover`` analogue), and every stream still
      bit-identical (spot-asserted against the fault-free arm).

    CPU wall-clock is reported as an extra and NOISE-labeled; the tick
    and counter records are the evidence."""
    import numpy as np
    import jax
    import mxtpu as mx
    from mxtpu import nd
    from mxtpu.models import transformer
    from mxtpu.parallel import PagedContinuousBatchingEngine, make_mesh
    from mxtpu.resilience import fault_plan
    from mxtpu.serving import Gateway, replica_pool

    platform = jax.devices()[0].platform
    cpu = platform == "cpu"
    mx.random.seed(7)
    if cpu:
        lm = transformer.llama_tiny(vocab_size=256)
        slots, max_len, bs, chunk = 2, 64, 8, 8
        # 8 prompt FAMILIES (tenants with distinct long system
        # prompts), 3 repeats each; per-replica pool sized so ONE
        # replica can hold its locality share of pinned chains but
        # blind placement duplicating every family across both
        # replicas hits pool pressure and thrashes the pinned tier
        fams, reps_per, fam_len, tlo, thi, glo, ghi = 8, 3, 24, 2, 4, \
            6, 10
        vocab, num_blocks = 256, 26
    else:
        lm = transformer.llama_3_8b(vocab_size=32000, width_factor=0.25,
                                    depth_factor=0.25)
        slots, max_len, bs, chunk = 4, 256, 16, 64
        fams, reps_per, fam_len, tlo, thi, glo, ghi = 8, 4, 96, 8, 16, \
            16, 32
        vocab, num_blocks = 32000, 80
    n_req = fams * reps_per
    lm.initialize()
    mesh = make_mesh(dp=1)
    rules = transformer.transformer_lm_sharding_rules()

    R = np.random.RandomState(0)
    families = [R.randint(0, vocab, (1, fam_len)) for _ in range(fams)]
    order = R.permutation(n_req)
    prompts = [nd.array(np.concatenate(
        [families[int(i) % fams],
         R.randint(0, vocab, (1, int(R.randint(tlo, thi + 1))))],
        axis=1), dtype="int32") for i in order]
    news = R.randint(glo, ghi + 1, n_req).tolist()
    # bursty Poisson arrivals in gateway ticks: two bursts separated by
    # a lull long enough to drain (the pinned tier carries the family
    # prompts across it; the overlap-only window would lose them)
    a1 = np.cumsum(R.poisson(1, size=n_req // 2))
    a2 = np.cumsum(R.poisson(1, size=n_req - n_req // 2)) + a1[-1] + 30
    arrivals = np.concatenate([a1, a2])

    def build_pool(tag, n):
        return replica_pool(
            lambda i: PagedContinuousBatchingEngine(
                lm, mesh, rules, num_slots=slots, max_length=max_len,
                block_size=bs, prefill_chunk=chunk, pin_bytes="64MiB",
                num_blocks=num_blocks,
                ledger_tag="%s%d" % (tag, i)), n=n)

    def drive(gw, plan=None):
        ctx = fault_plan(plan) if plan else None
        if ctx is not None:
            ctx.__enter__()
        try:
            t0 = time.perf_counter()
            it, nxt, rids = 0, 0, []
            while nxt < n_req or gw.stats["outstanding"]:
                while nxt < n_req and arrivals[nxt] <= it:
                    rids.append(gw.submit(prompts[nxt], news[nxt]))
                    nxt += 1
                gw.pump()
                it += 1
                if it > 500 * (1 + n_req):
                    raise RuntimeError("bench router drive wedged")
            dt = time.perf_counter() - t0
        finally:
            if ctx is not None:
                ctx.__exit__(None, None, None)
        ttft = [gw.stats["ttft_ticks"][r] for r in rids
                if r in gw.stats["ttft_ticks"]]
        return gw, rids, sorted(ttft), dt

    def pct(sorted_vals, q):
        if not sorted_vals:
            return None
        i = min(len(sorted_vals) - 1,
                int(round(q * (len(sorted_vals) - 1))))
        return sorted_vals[i]

    # arm 1: locality pool
    gw_loc, rids_loc, ttft_loc, dt_loc = drive(
        Gateway(build_pool("bl", 2), hedge_fraction=None))
    res_loc = {r: gw_loc.result(r).asnumpy() for r in rids_loc}
    # arm 2: round-robin control (identical engines-shape, fresh pool)
    gw_rr, rids_rr, ttft_rr, _ = drive(
        Gateway(build_pool("br", 2), hedge_fraction=None,
                router="round_robin"))
    # arm 3: single replica
    gw_one, rids_one, ttft_one, _ = drive(
        Gateway(build_pool("b1", 1), hedge_fraction=None))
    # arm 4: locality pool under the 1% replica.health plan
    gw_f, rids_f, ttft_f, _ = drive(
        Gateway(build_pool("bf", 2), fail_threshold=1,
                revive_after_ticks=8, hedge_fraction=None),
        plan="replica.health%100:raise=OSError(bench-kill)")
    # every faulted-arm stream bit-identical to the fault-free arm
    exact = all(np.array_equal(gw_f.result(rf).asnumpy(), res_loc[rl])
                for rf, rl in zip(rids_f, rids_loc))

    loc_hit = gw_loc.router.stats["prefix_hit_rate"]
    rr_hit = gw_rr.router.stats["prefix_hit_rate"]
    sup_f = gw_f.stats["supervisor"]
    rec = {
        "metric": "router_ttft_p99_ticks",
        "value": pct(ttft_loc, 0.99),
        "unit": "gateway ticks (deterministic)",
        "vs_baseline": None,
        "platform": platform,
        "ttft_p50_ticks": pct(ttft_loc, 0.5),
        "single_replica_ttft_p50_p99": [pct(ttft_one, 0.5),
                                        pct(ttft_one, 0.99)],
        "round_robin_ttft_p50_p99": [pct(ttft_rr, 0.5),
                                     pct(ttft_rr, 0.99)],
        "prefix_hit_rate_locality": round(loc_hit, 3),
        "prefix_hit_rate_round_robin": round(rr_hit, 3),
        "locality_beats_round_robin": bool(loc_hit > rr_hit),
        "prefill_tokens_avoided_locality": sum(
            r.stats()["prefill_tokens_avoided"]
            for r in gw_loc.supervisor.replicas),
        "prefill_tokens_avoided_round_robin": sum(
            r.stats()["prefill_tokens_avoided"]
            for r in gw_rr.supervisor.replicas),
        "fault_arm": {
            "plan": "replica.health%100:raise (1% of probes, "
                    "counter-driven)",
            "replica_deaths": sup_f["deaths"],
            "revivals": sup_f["revivals"],
            "requeued_requests": gw_f.stats["requeued_requests"],
            "ttft_p99_ticks": pct(ttft_f, 0.99),
            "streams_bit_identical_to_fault_free": bool(exact),
        },
        "config": {"replicas": 2, "slots_per_replica": slots,
                   "requests": n_req, "prompt_families": fams,
                   "family_prompt_len": fam_len,
                   "repeats_per_family": reps_per,
                   "new_tokens": [glo, ghi], "max_length": max_len,
                   "block_size": bs, "prefill_chunk": chunk,
                   "num_blocks_per_replica": num_blocks,
                   "arrivals": "two poisson(1) bursts + 30-tick lull"},
        "wall_clock_s_NOISE": round(dt_loc, 2),
        "baseline_note": "no upstream analogue (single-process serving "
                         "only); comparison columns are this repo's own "
                         "single replica and round-robin placement on "
                         "the identical workload.  All tick/counter "
                         "values are deterministic host counters; the "
                         "wall-clock extra is CPU NOISE per bench "
                         "conventions",
    }
    if cpu:
        rec["config_note"] = ("CPU fallback runs a LABELED llama_tiny "
                              "config — plumbing evidence only, NOT a "
                              "TPU serving number")
    print(json.dumps(rec), flush=True)


def _bench_cross_process():
    """Cross-process replica serving (round-19 tentpole): the SAME
    bursty prefix-family workload over 2 replicas hosted in spawned OS
    worker processes (:class:`mxtpu.serving.SubprocessReplica`, pipe
    RPC) vs 2 in-process replicas with identical engine configs.  Three
    deterministic arms:

    - SUBPROCESS pool (headline): ttft p50/p99 in gateway ticks +
      prefix-hit-rate, every protocol call crossing a process boundary
      as host data;
    - IN-PROCESS control: identical engines and workload; the record
      asserts every stream is BIT-IDENTICAL across the two transports
      (the boundary adds latency, never entropy);
    - KILL-DRAIN arm: the subprocess pool under a counter-planned
      ``transport.worker_death`` SIGKILL of worker r1 mid-decode —
      replica deaths, drained-and-requeued counts, zero pages resident
      on the dead worker, and every stream still bit-identical.

    Tick and counter records are the evidence; CPU wall-clock is an
    extra, NOISE-labeled per bench conventions."""
    import numpy as np
    import jax
    import mxtpu as mx
    from mxtpu import nd
    from mxtpu.models import transformer
    from mxtpu.parallel import PagedContinuousBatchingEngine, make_mesh
    from mxtpu.resilience import fault_plan
    from mxtpu.serving import Gateway, replica_pool

    platform = jax.devices()[0].platform
    # worker engine config (demo_paged_engine defaults, shared by both
    # transports): llama_tiny(vocab=50), 2 slots, max_length=32
    vocab, max_len = 50, 32
    fams, reps_per, fam_len = 4, 3, 10
    n_req = fams * reps_per

    R = np.random.RandomState(0)
    families = [R.randint(0, vocab, (1, fam_len)) for _ in range(fams)]
    order = R.permutation(n_req)
    prompts = [nd.array(np.concatenate(
        [families[int(i) % fams],
         R.randint(0, vocab, (1, int(R.randint(2, 5))))],
        axis=1), dtype="int32") for i in order]
    news = R.randint(4, 7, n_req).tolist()
    arrivals = np.cumsum(R.poisson(1, size=n_req))

    def sub_pool():
        return replica_pool(
            "mxtpu.serving.worker:demo_paged_engine", n=2,
            transport="subprocess",
            kwargs=lambda i: {"ledger_tag": "r%d" % i})

    def drive(gw, plan=None):
        ctx = fault_plan(plan) if plan else None
        if ctx is not None:
            ctx.__enter__()
        try:
            t0 = time.perf_counter()
            it, nxt, rids = 0, 0, []
            while nxt < n_req or gw.stats["outstanding"]:
                while nxt < n_req and arrivals[nxt] <= it:
                    rids.append(gw.submit(prompts[nxt], news[nxt]))
                    nxt += 1
                gw.pump()
                it += 1
                if it > 500 * (1 + n_req):
                    raise RuntimeError("bench cross-process wedged")
            dt = time.perf_counter() - t0
        finally:
            if ctx is not None:
                ctx.__exit__(None, None, None)
        ttft = sorted(gw.stats["ttft_ticks"][r] for r in rids
                      if r in gw.stats["ttft_ticks"])
        return gw, rids, ttft, dt

    def pct(sorted_vals, q):
        if not sorted_vals:
            return None
        i = min(len(sorted_vals) - 1,
                int(round(q * (len(sorted_vals) - 1))))
        return sorted_vals[i]

    # arm 1: subprocess pool (headline)
    pool_s = sub_pool()
    try:
        gw_s, rids_s, ttft_s, dt_s = drive(
            Gateway(pool_s, hedge_fraction=None))
        res_s = {r: gw_s.result(r).asnumpy() for r in rids_s}
    finally:
        for rep in pool_s:
            rep.close()
    # arm 2: in-process control — ONE seeded net shared by both replica
    # engines (each worker process reseeds and owns its copy; in ONE
    # process two independently-built nets would interleave their
    # deferred weight draws on the global generator and diverge)
    mx.random.seed(77)
    lm = transformer.llama_tiny(vocab_size=vocab)
    lm.initialize()
    mesh = make_mesh(dp=1)
    rules = transformer.transformer_lm_sharding_rules()
    gw_i, rids_i, ttft_i, _ = drive(Gateway(
        replica_pool(lambda i: PagedContinuousBatchingEngine(
            lm, mesh, rules, num_slots=2, max_length=max_len,
            block_size=8, prefill_chunk=8, pin_bytes="1MiB",
            ledger_tag="ci%d" % i), n=2), hedge_fraction=None))
    exact_transport = all(
        np.array_equal(gw_i.result(ri).asnumpy(), res_s[rs])
        for ri, rs in zip(rids_i, rids_s))
    # arm 3: kill-drain — SIGKILL worker r1 mid-decode via the planned
    # transport.worker_death site; streams must survive bit-identical
    pool_f = sub_pool()
    try:
        gw_f, rids_f, ttft_f, _ = drive(
            Gateway(pool_f, fail_threshold=1, hedge_fraction=None),
            plan="transport.worker_death#r1@25:raise="
                 "OSError(bench-kill)")
        exact_kill = all(
            np.array_equal(gw_f.result(rf).asnumpy(), res_s[rs])
            for rf, rs in zip(rids_f, rids_s))
        sup_f = gw_f.stats["supervisor"]
        dead_stats = pool_f[1].stats()
        dead_exit = pool_f[1].exit_code
    finally:
        for rep in pool_f:
            rep.close()

    rec = {
        "metric": "cross_process_ttft_p99_ticks",
        "value": pct(ttft_s, 0.99),
        "unit": "gateway ticks (deterministic)",
        "vs_baseline": None,
        "platform": platform,
        "ttft_p50_ticks": pct(ttft_s, 0.5),
        "inprocess_ttft_p50_p99": [pct(ttft_i, 0.5),
                                   pct(ttft_i, 0.99)],
        "prefix_hit_rate_subprocess": round(
            gw_s.router.stats["prefix_hit_rate"], 3),
        "prefix_hit_rate_inprocess": round(
            gw_i.router.stats["prefix_hit_rate"], 3),
        "streams_bit_identical_across_transports": bool(
            exact_transport),
        "kill_drain_arm": {
            "plan": "transport.worker_death#r1@25:raise (25th RPC to "
                    "r1 SIGKILLs its worker, counter-driven)",
            "replica_deaths": sup_f["deaths"],
            "requeued_requests": gw_f.stats["requeued_requests"],
            "dead_worker_exit_code": dead_exit,
            "dead_worker_blocks_in_use": dead_stats["blocks_in_use"],
            "ttft_p99_ticks": pct(ttft_f, 0.99),
            "streams_bit_identical_to_fault_free": bool(exact_kill),
        },
        "config": {"replicas": 2, "transport": "subprocess (pipe RPC, "
                   "json frames)", "requests": n_req,
                   "prompt_families": fams, "family_prompt_len": fam_len,
                   "repeats_per_family": reps_per, "new_tokens": [4, 6],
                   "max_length": max_len,
                   "worker_factory":
                       "mxtpu.serving.worker:demo_paged_engine"},
        "wall_clock_s_NOISE": round(dt_s, 2),
        "baseline_note": "no upstream analogue (single-process serving "
                         "only); the comparison column is this repo's "
                         "own in-process pool on the identical "
                         "workload.  Tick/counter values are "
                         "deterministic host counters; the wall-clock "
                         "extra is CPU NOISE per bench conventions.  "
                         "The worker engine is a LABELED llama_tiny "
                         "demo config on every platform — transport "
                         "plumbing evidence, not a model-scale number",
    }
    print(json.dumps(rec), flush=True)


def _bench_autoscale():
    """Elastic serving (round-20 tentpole): the metrics-driven
    ``Autoscaler`` on a DIURNAL-RAMP workload — arrivals climb to a
    peak the single-replica deployment cannot absorb, then fall back
    to a lull — with a live weight hot-swap adopted mid-traffic.

    Two deterministic arms on the identical workload:

    - FIXED: 1 replica, no autoscaler — the peak sheds requests
      (``QosShedError``: users turned away);
    - AUTOSCALE: the same gateway with ``Autoscaler(min=1, max=3)``
      ticking once per pump — the pool grows through the ramp
      (backlog pressure, BEFORE the queue overflows), absorbs the
      peak, and retires back down through the lull with ZERO
      requeued requests (graceful drain, never the death path).

    The headline is the shed delta (fixed arm sheds − autoscale arm
    sheds, a deterministic counter); the hot-swap coda measures
    adoption latency in AUTOSCALER TICKS under load — two canary
    streams submitted before ``adopt()`` must finish bit-identical to
    the OLD-weight isolated reference while the new generation
    installs behind them.  Completed streams are spot-asserted
    bit-identical across the arms; wall clock is NOISE-labeled."""
    import pickle
    import tempfile

    import numpy as np
    import jax
    import mxtpu as mx
    from mxtpu import nd
    from mxtpu.models import transformer
    from mxtpu.models.transformer import TransformerLM
    from mxtpu.parallel import (PagedContinuousBatchingEngine,
                                ShardedDecoder, make_mesh)
    from mxtpu.resilience import LoadShedError
    from mxtpu.resilience.checkpoint import write_verified
    from mxtpu.serving import Autoscaler, Gateway, replica_pool

    platform = jax.devices()[0].platform
    cpu = platform == "cpu"
    vocab = 24

    def build_lm(seed):
        mx.random.seed(seed)
        net = TransformerLM(vocab, units=32, hidden_size=64,
                            num_layers=1, num_heads=4, num_kv_heads=2)
        net.initialize()
        net(nd.array(np.asarray([[1, 2]], dtype=np.int32)))
        return net

    lm = build_lm(11)
    lm_b = build_lm(29)              # the hot-swap target generation
    mesh = make_mesh(dp=1)
    rules = transformer.transformer_lm_sharding_rules()
    if cpu:
        slots, max_len, bs, chunk = 2, 48, 8, 8
        n_req, glo, ghi, max_pending, eng_pending = 24, 4, 8, 3, 3
    else:
        slots, max_len, bs, chunk = 2, 96, 8, 16
        n_req, glo, ghi, max_pending, eng_pending = 36, 8, 16, 3, 3

    R = np.random.RandomState(3)
    prompts = [nd.array(R.randint(0, vocab, (1, int(R.randint(3, 7)))),
                        dtype="int32") for _ in range(n_req)]
    news = R.randint(glo, ghi + 1, n_req).tolist()
    # diurnal ramp in gateway ticks: sparse dawn arrivals, a dense
    # midday burst (several requests per tick — the overload: both the
    # engine queue (max_pending) and the gateway queue are bounded, so
    # the fixed deployment turns users away), then a long idle dusk
    # for the scale-down to drain into
    third = n_req // 4
    a1 = np.cumsum(R.poisson(4, size=third))                 # dawn
    mid = n_req - 2 * third
    a2 = np.cumsum(R.poisson(0.4, size=mid)) + a1[-1]        # midday
    a3 = np.cumsum(R.poisson(4, size=third)) + a2[-1] + 4    # dusk
    arrivals = np.concatenate([a1, a2, a3])

    def factory_for(tag):
        return lambda i: PagedContinuousBatchingEngine(
            lm, mesh, rules, num_slots=slots, max_length=max_len,
            block_size=bs, prefill_chunk=chunk,
            max_pending=eng_pending, ledger_tag="%s%d" % (tag, i))

    def drive(tag, autoscale):
        fac = factory_for(tag)
        gw = Gateway(replica_pool(fac, n=1), hedge_fraction=None,
                     max_pending=max_pending)
        asc = (Autoscaler(gw, fac, min_replicas=1, max_replicas=3,
                          cooldown_ticks=3) if autoscale else None)
        t0 = time.perf_counter()
        it, nxt, rids = 0, 0, {}
        while nxt < n_req or gw.stats["outstanding"]:
            while nxt < n_req and arrivals[nxt] <= it:
                try:
                    rids[nxt] = gw.submit(prompts[nxt], news[nxt])
                except LoadShedError:   # the user turned away
                    pass                # (counted by the gateway)
                nxt += 1
            gw.pump()
            if asc is not None:
                asc.tick()
            it += 1
            if it > 500 * (1 + n_req):
                raise RuntimeError("bench autoscale drive wedged")
        # idle tail: the lull after the last stream finishes is where
        # the scale-down policy drains the pool back to min_replicas
        extra = 0
        while (asc is not None and extra < 60
               and len(asc.supervisor.replicas) > 1):
            gw.pump()
            asc.tick()
            extra += 1
        shed = (gw.stats["qos_shed_requests"]
                + gw.stats["engine_shed_requests"])
        done = {i: gw.result(r).asnumpy() for i, r in rids.items()
                if gw.status(r) == "ok"}
        return gw, asc, shed, done, it, time.perf_counter() - t0

    gw_fix, _, shed_fix, done_fix, _, _ = drive("af", False)
    gw_el, asc, shed_el, done_el, ticks_el, dt = drive("ae", True)
    # streams completed in BOTH arms are bit-identical (same seeds)
    both = sorted(set(done_fix) & set(done_el))
    exact = all(np.array_equal(done_fix[i], done_el[i]) for i in both)

    # -- hot-swap coda: adopt lm_b's weights under two live canaries --
    ckpt_dir = tempfile.mkdtemp(prefix="bench_hotswap_")
    named = {p.name: np.asarray(p.data()._data)
             for p in ShardedDecoder(lm_b, mesh, rules)._params}
    ck = os.path.join(ckpt_dir, "gen1.ckpt")
    write_verified(ck, pickle.dumps(
        {"step": 1, "num_update": 1, "params": named,
         "opt_states": {}, "scale_state": None, "rng": None}))
    dec_old = ShardedDecoder(lm, mesh, rules)
    canaries = [(nd.array(R.randint(0, vocab, (1, 4)), dtype="int32"), 6)
                for _ in range(2)]
    want_old = [dec_old.generate(p, max_new_tokens=n,
                                 max_length=max_len).asnumpy()
                for p, n in canaries]
    crids = [gw_el.submit(p, n) for p, n in canaries]
    gw_el.pump(); asc.tick()
    staged = asc.adopt(ck)           # canaries pinned on OLD weights
    t_adopt, lat = asc.stats["ticks"], None
    for _ in range(400):
        gw_el.pump(); asc.tick()
        reps = gw_el.supervisor.alive
        if lat is None and reps and all(
                r.stats().get("param_generation", 0) >= 1
                for r in reps):
            lat = asc.stats["ticks"] - t_adopt
        if lat is not None and not gw_el.stats["outstanding"]:
            break
    exact_canary = all(
        np.array_equal(gw_el.result(r).asnumpy(), w)
        for r, w in zip(crids, want_old))

    st = asc.stats
    rec = {
        "metric": "autoscale_shed_delta",
        "value": shed_fix - shed_el,
        "unit": "requests (deterministic counters: fixed-arm sheds "
                "minus autoscale-arm sheds, identical workload)",
        "vs_baseline": None,
        "platform": platform,
        "sheds_fixed_1_replica": shed_fix,
        "sheds_autoscaled": shed_el,
        "scale_ups": st["scale_ups"],
        "scale_downs": st["scale_downs"],
        "retired_replicas": st["retired_replicas"],
        "requeued_requests_autoscaled":
            gw_el.stats["requeued_requests"],
        "zero_dropped_streams": bool(
            gw_el.stats["requeued_requests"] == 0
            and len(done_el) == n_req - shed_el),
        "streams_bit_identical_across_arms": bool(exact),
        "hot_swap": {
            "replicas_staged": staged,
            "adoption_latency_ticks": lat,
            "canaries_bit_identical_on_old_weights":
                bool(exact_canary),
            "param_generation": max(
                r.stats().get("param_generation", 0)
                for r in gw_el.supervisor.alive),
        },
        "config": {"min_replicas": 1, "max_replicas": 3,
                   "cooldown_ticks": 3, "requests": n_req,
                   "max_pending": max_pending,
                   "slots_per_replica": slots,
                   "new_tokens": [glo, ghi],
                   "arrivals": "diurnal ramp: poisson(4) dawn, "
                               "poisson(0.4) midday burst, poisson(4) "
                               "dusk"},
        "wall_clock_s_NOISE": round(dt, 2),
        "baseline_note": "no upstream analogue (no elastic serving in "
                         "the reference); the comparison column is "
                         "this repo's own fixed 1-replica deployment "
                         "on the identical workload.  All scale "
                         "decisions and shed counts are deterministic "
                         "host counters; wall clock is CPU NOISE per "
                         "bench conventions.  The model is a LABELED "
                         "micro TransformerLM — policy-loop evidence, "
                         "not a model-scale number",
    }
    if cpu:
        rec["config_note"] = ("CPU fallback runs a LABELED micro "
                              "config — plumbing evidence only, NOT a "
                              "TPU serving number")
    print(json.dumps(rec), flush=True)


def _bench_quantized_decode():
    """Quantized serving (round-14 tentpole): int8 KV cache with
    per-head scales vs the bf16 paged engine.  Two metrics, BOTH
    deterministic (no wall clock — the CPU wall-clock comparison is
    noise-dominated on this host; TPU tokens/s: not measured):

    - ``kv_cache_bytes_per_token``: per-token cache bytes incl. the
      scale tensors (abstract eval, no allocation) — int8 value with a
      bf16 column.  At head_dim 64 the ratio is 0.53125 = 0.5 payload
      + 2/64 scales.
    - ``slots_resident_at_fixed_hbm_int8``: peak concurrently-resident
      requests of an int8 paged pool holding IDENTICAL cache bytes to
      the bf16 pool (the freed bytes become pages, pages become
      admitted requests).  Acceptance >= 1.8x.
    """
    import numpy as np
    import jax
    import mxtpu as mx
    from mxtpu import nd
    from mxtpu.analysis.memory_estimate import paged_kv_cache_residency
    from mxtpu.models import transformer
    from mxtpu.parallel import PagedContinuousBatchingEngine, make_mesh

    platform = jax.devices()[0].platform
    cpu = platform == "cpu"
    mx.random.seed(7)
    # head_dim 64 (the scale-overhead regime that matters; tiny widths
    # would overstate the scale tax) — 1 layer keeps the CPU drive fast
    lm = transformer.TransformerLM(256, units=128, hidden_size=256,
                                   num_layers=1, num_heads=2,
                                   num_kv_heads=2)
    lm.initialize()
    mesh = make_mesh(dp=1)
    rules = transformer.transformer_lm_sharding_rules()
    bs, max_len, chunk, lanes = 16, 32, 16, 16
    bf_pages = 16

    bpb_bf = paged_kv_cache_residency(lm, bf_pages, bs,
                                      "bfloat16")["bytes_per_block"]
    bpb_i8 = paged_kv_cache_residency(lm, bf_pages, bs,
                                      "int8")["bytes_per_block"]
    # identical cache bytes: the int8 pool gets however many pages the
    # bf16 pool's bytes buy at the int8 per-page cost (incl. scales)
    i8_pages = bf_pages * bpb_bf // bpb_i8

    R = np.random.RandomState(0)
    n_req = 24
    # every request spans exactly 2 pages (16 < prompt+new <= 32), so
    # peak residency is pool_pages/2 on both sides — pure page math
    plens = R.randint(17, 21, n_req)
    news = R.randint(8, 12, n_req).tolist()
    prompts = [nd.array(R.randint(0, 256, (1, int(t))), dtype="int32")
               for t in plens]

    def drive(cache_dtype, pages):
        eng = PagedContinuousBatchingEngine(
            lm, mesh, rules, num_slots=lanes, max_length=max_len,
            block_size=bs, num_blocks=int(pages), prefill_chunk=chunk,
            cache_dtype=cache_dtype)
        for p, n in zip(prompts, news):
            eng.submit(p, n)
        peak = 0
        while eng.pending or eng.active:
            eng.step()
            peak = max(peak, eng.active)
        eng.run()
        return peak

    bf_peak = drive("bfloat16", bf_pages)
    i8_peak = drive("int8", i8_pages)

    cfg = {"units": 128, "head_dim": 64, "num_kv_heads": 2, "layers": 1,
           "block_size": bs, "max_length": max_len,
           "prefill_chunk": chunk, "scheduler_lanes": lanes,
           "bf16_pages": bf_pages, "int8_pages": int(i8_pages),
           "requests": n_req, "prompt_len": [17, 20],
           "new_tokens": [8, 11]}
    rec = {
        "metric": "kv_cache_bytes_per_token",
        "value": bpb_i8 // bs,
        "unit": "bytes/token (all layers, k+v, incl. scales)",
        "vs_baseline": None,
        "platform": platform,
        "bf16_bytes_per_token": bpb_bf // bs,
        "int8_over_bf16": round(bpb_i8 / bpb_bf, 5),
        "config": cfg,
        "baseline_note": "abstract eval (jax.eval_shape) — exact and "
                         "platform-independent; the int8 column prices "
                         "the per-head-per-position f32 scales, not "
                         "payload alone (0.5 + 4/(2*head_dim))",
    }
    print(json.dumps(rec), flush=True)

    rec = {
        "metric": "slots_resident_at_fixed_hbm_int8",
        "value": i8_peak,
        "unit": "concurrent requests",
        "vs_baseline": None,
        "platform": platform,
        "bf16_peak": bf_peak,
        "residency_gain_vs_bf16": round(i8_peak / max(bf_peak, 1), 3),
        "acceptance": ">= 1.8x bf16 at identical cache bytes",
        "config": cfg,
        "baseline_note": "both pools hold IDENTICAL cache bytes "
                         "(int8 pages sized by the bf16 pool's byte "
                         "budget at the int8 per-page cost incl. "
                         "scales); admission is page-limited with "
                         "demand outpacing completions, so peak "
                         "residency is the pool's capacity — a "
                         "deterministic record, no wall clock",
    }
    if cpu:
        rec["config_note"] = ("CPU host: the residency record is "
                              "deterministic page math and carries to "
                              "TPU unchanged; CPU wall-clock tokens/s "
                              "is NOISE-DOMINATED on this host and "
                              "deliberately not recorded — TPU "
                              "tokens/s via the bench battery")
    print(json.dumps(rec), flush=True)


def _bench_speculative_decode():
    """Speculative decoding in the pooled decode step (round-13
    tentpole): n-gram self-drafting + batched verification vs the plain
    pooled step on a REPETITIVE/templated workload — the regime
    prompt-lookup drafting targets (decode is HBM-bandwidth-bound, so
    k accepted drafts per cache read is a direct tokens/s multiplier).
    Two metrics:

    - ``accepted_tokens_per_step``: emitted tokens per pooled decode
      iteration (1.0 exactly without speculation; every accepted draft
      raises it).  Host-side counters over a DETERMINISTIC workload —
      honest acceptance evidence on any platform.
    - ``decode_tokens_per_sec_speculative``: useful tokens/sec with the
      non-speculative engine column alongside (CPU wall clock labeled
      NOISE-DOMINATED, per bench conventions — the counter record above
      is the platform-independent evidence; TPU tokens/s deferred to
      the bench battery)."""
    import numpy as np
    import jax
    import mxtpu as mx
    from mxtpu import nd
    from mxtpu.models import transformer
    from mxtpu.models.transformer import TransformerLM
    from mxtpu.parallel import ContinuousBatchingEngine, make_mesh

    platform = jax.devices()[0].platform
    cpu = platform == "cpu"
    if cpu:
        # the pinned cycling micro model (tests/test_speculative.py):
        # greedy continuations fall into short cycles, so prompt-lookup
        # accepts are a deterministic property of the workload, not luck
        mx.random.seed(1)
        lm = TransformerLM(20, units=32, hidden_size=64, num_layers=1,
                           num_heads=4, num_kv_heads=2)
        slots, n_req, max_len, vocab, spec_k = 4, 12, 64, 20, 3
        glo, ghi = 12, 24
    else:
        mx.random.seed(1)
        lm = transformer.llama_3_8b(vocab_size=32000, width_factor=0.25,
                                    depth_factor=0.25)
        slots, n_req, max_len, vocab, spec_k = 8, 16, 256, 32000, 3
        glo, ghi = 24, 64
    lm.initialize()
    mesh = make_mesh(dp=1)
    rules = transformer.transformer_lm_sharding_rules()

    R = np.random.RandomState(0)
    # templated prompts: short patterns tiled — the repetition structure
    # the n-gram lookup exploits
    prompts = []
    for _ in range(n_req):
        pat = R.randint(0, vocab, (1, int(R.randint(3, 6))))
        prompts.append(nd.array(
            np.tile(pat, int(R.randint(3, 5)))[:, :max_len // 2]
            .astype(np.int32)))
    news = R.randint(glo, ghi + 1, n_req).tolist()
    useful = float(sum(news))

    from mxtpu.analysis import get_ledger
    _led = get_ledger()
    _verify_before = sum(_led.miss_counts(
        ("serving.verify_slots",)).values())

    spec = ContinuousBatchingEngine(lm, mesh, rules, num_slots=slots,
                                    max_length=max_len, spec_k=spec_k)
    plain = ContinuousBatchingEngine(lm, mesh, rules, num_slots=slots,
                                     max_length=max_len)

    def drive(eng):
        t0 = time.perf_counter()
        for p, n in zip(prompts, news):
            eng.submit(p, n)
        eng.run()
        return time.perf_counter() - t0

    drive(spec)                    # compile warmup
    s0 = spec.stats
    spec_dt = drive(spec)
    s1 = spec.stats
    drive(plain)                   # compile warmup
    plain_dt = drive(plain)

    slot_iters = s1["slot_iterations"] - s0["slot_iterations"]
    toks = s1["generated_tokens"] - s0["generated_tokens"]
    drafted = s1["drafted_tokens"] - s0["drafted_tokens"]
    accepted = s1["accepted_tokens"] - s0["accepted_tokens"]
    cfg = {"num_slots": slots, "requests": n_req, "spec_k": spec_k,
           "new_tokens": [glo, ghi], "max_length": max_len,
           "workload": "tiled 3-5 token patterns (templated)"}
    rec = {
        "metric": "accepted_tokens_per_step",
        # per SLOT-iteration (one slot's share of one pooled call) —
        # the per-cache-read multiplier: non-speculative decode is 1.0
        # exactly, every accepted draft raises it
        "value": round(toks / max(slot_iters, 1), 3),
        "unit": "tokens/slot-iteration",
        "vs_baseline": 1.0,
        "platform": platform,
        "drafted_tokens": drafted,
        "accepted_tokens": accepted,
        "draft_hit_rate": round(accepted / drafted, 3) if drafted
        else 0.0,
        "verify_calls": s1["verify_calls"] - s0["verify_calls"],
        "pooled_tokens_per_iteration": round(
            toks / max(s1["steps"] - s0["steps"], 1), 3),
        "config": cfg,
        "baseline_note": "non-speculative decode emits exactly 1.0 "
                         "token per slot-iteration by construction; "
                         "value is a deterministic host-side counter "
                         "(timer-free), honest on any platform — every "
                         "stream stays bit-identical to "
                         "non-speculative decode",
    }
    if cpu:
        rec["config_note"] = ("CPU fallback runs the LABELED pinned "
                              "cycling micro model — acceptance "
                              "evidence, NOT a TPU serving number")
    print(json.dumps(rec), flush=True)

    rec = {
        "metric": "decode_tokens_per_sec_speculative",
        "value": round(useful / spec_dt, 2),
        "unit": "tokens/sec",
        "vs_baseline": None,
        "platform": platform,
        "non_speculative_tokens_per_sec": round(useful / plain_dt, 2),
        "speedup_vs_non_speculative": round(plain_dt / spec_dt, 3),
        # verify-program family compiled over warmup+timed: the number
        # the pow2 window ladder bounds (<= |ladder|)
        "compiled_program_count": sum(_led.miss_counts(
            ("serving.verify_slots",)).values()) - _verify_before,
        "config": cfg,
        "baseline_note": "no upstream analogue; comparison column is "
                         "this repo's own non-speculative slot engine "
                         "on the identical templated workload",
    }
    if cpu:
        rec["config_note"] = ("CPU wall-clock comparison is "
                              "NOISE-DOMINATED on the oversubscribed "
                              "host (speculation trades compute for "
                              "HBM reads — a win the CPU backend "
                              "cannot show); accepted_tokens_per_step "
                              "above is the deterministic evidence; "
                              "TPU tokens/s: not measured")
    print(json.dumps(rec), flush=True)


def _bench_tree_speculative():
    """Tree speculative decoding (round-18 tentpole): multi-branch
    draft trees verified in ONE pooled ancestor-masked cache read vs
    LINEAR windows of the same node budget, on a BRANCHY workload —
    histories whose trailing n-grams recur with different continuations,
    the regime where a linear window bets everything on one continuation
    and loses the whole draft at the first fork taken the other way.

    ``accepted_tokens_per_step_tree``: emitted tokens per slot-iteration
    under tree drafting, with the linear engine's number on the
    identical workload alongside — both DETERMINISTIC host-side
    counters (timer-free, honest on any platform); wall clock is
    recorded NOISE-labeled only."""
    import numpy as np
    import jax
    import mxtpu as mx
    from mxtpu import nd
    from mxtpu.models import transformer
    from mxtpu.models.transformer import TransformerLM
    from mxtpu.parallel import ContinuousBatchingEngine, make_mesh

    platform = jax.devices()[0].platform
    cpu = platform == "cpu"
    if cpu:
        mx.random.seed(1)   # the pinned cycling micro model
        lm = TransformerLM(20, units=32, hidden_size=64, num_layers=1,
                           num_heads=4, num_kv_heads=2)
        slots, n_req, max_len, vocab = 4, 8, 96, 20
        glo, ghi = 24, 40
    else:
        mx.random.seed(1)
        lm = transformer.llama_3_8b(vocab_size=32000, width_factor=0.25,
                                    depth_factor=0.25)
        slots, n_req, max_len, vocab = 8, 16, 256, 32000
        glo, ghi = 24, 64
    nodes, branch = 7, 2
    lm.initialize()
    mesh = make_mesh(dp=1)
    rules = transformer.transformer_lm_sharding_rules()

    R = np.random.RandomState(0)
    # branchy prompts: a short pattern tiled, but with the token after
    # one pattern occurrence PERTURBED — the trailing n-gram now recurs
    # with two different continuations, so the most-recent-occurrence
    # bet a linear window makes is wrong whenever the model continues
    # the other way; the tree drafts BOTH
    prompts = []
    for _ in range(n_req):
        w = int(R.randint(3, 6))
        pat = R.randint(0, vocab, (1, w))
        tiled = np.tile(pat, 6)[:, :max_len // 2 - 1]
        k = int(R.randint(1, w + 1))     # perturb inside tile 2
        tiled[0, w + k - 1] = int(R.randint(0, vocab))
        prompts.append(nd.array(tiled.astype(np.int32)))
    news = R.randint(glo, ghi + 1, n_req).tolist()
    useful = float(sum(news))

    from mxtpu.analysis import get_ledger
    _led = get_ledger()
    _sites = ("serving.verify_tree_slots", "serving.fixup_slots")
    _tree_before = sum(_led.miss_counts(_sites).values())

    tree = ContinuousBatchingEngine(lm, mesh, rules, num_slots=slots,
                                    max_length=max_len,
                                    spec_tree=(nodes, branch))
    # the linear comparator gets the SAME node budget: spec_k drafts
    # one chain as long as the tree's deepest path
    linear = ContinuousBatchingEngine(lm, mesh, rules, num_slots=slots,
                                      max_length=max_len, spec_k=nodes)

    def drive(eng):
        t0 = time.perf_counter()
        for p, n in zip(prompts, news):
            eng.submit(p, n)
        eng.run()
        return time.perf_counter() - t0

    drive(tree)                    # compile warmup
    t0s = tree.stats
    tree_dt = drive(tree)
    t1s = tree.stats
    drive(linear)                  # compile warmup
    linear_dt = drive(linear)
    l1s = linear.stats

    def rate(a, b=None):
        it = a["slot_iterations"] - (b["slot_iterations"] if b else 0)
        tk = a["generated_tokens"] - (b["generated_tokens"] if b else 0)
        return tk / max(it, 1)

    drafted = t1s["tree_nodes_drafted"] - t0s["tree_nodes_drafted"]
    paths = t1s["tree_paths"] - t0s["tree_paths"]
    accepted = t1s["accepted_tokens"] - t0s["accepted_tokens"]
    cfg = {"num_slots": slots, "requests": n_req,
           "spec_tree": [nodes, branch], "linear_spec_k": nodes,
           "new_tokens": [glo, ghi], "max_length": max_len,
           "workload": "tiled 3-5 token patterns with one perturbed "
                       "continuation (branchy)"}
    rec = {
        "metric": "accepted_tokens_per_step_tree",
        "value": round(rate(t1s, t0s), 3),
        "unit": "tokens/slot-iteration",
        # linear speculation at the SAME node budget on the SAME
        # branchy workload — the number the ancestor-masked tree beats
        "vs_baseline": round(rate(l1s), 3),
        "platform": platform,
        "tree_nodes_drafted": drafted,
        "tree_paths": paths,
        "accepted_tokens": accepted,
        "node_hit_rate": round(accepted / drafted, 3) if drafted
        else 0.0,
        # verify-tree + fixup program family compiled over warmup+timed:
        # bounded by the pow2 window ladder, never per tree shape
        "compiled_program_count": sum(
            _led.miss_counts(_sites).values()) - _tree_before,
        "wall_clock_note": "NOISE-DOMINATED CPU wall clock, recorded "
                           "for completeness only: tree %.2fs vs "
                           "linear %.2fs for %d useful tokens"
                           % (tree_dt, linear_dt, int(useful)),
        "config": cfg,
        "baseline_note": "comparison column is this repo's own LINEAR "
                         "speculative engine (spec_k = tree max_nodes) "
                         "on the identical branchy workload; both "
                         "values are deterministic host-side counters "
                         "(timer-free) and every stream on both "
                         "engines stays bit-identical to "
                         "non-speculative decode",
    }
    if cpu:
        rec["config_note"] = ("CPU fallback runs the LABELED pinned "
                              "cycling micro model — acceptance "
                              "evidence, NOT a TPU serving number")
    print(json.dumps(rec), flush=True)


def _bench_analysis():
    """Static-analysis wall time (round-11 tentpole: compile-discipline
    and device-memory static analysis).  Times every pass the repo
    self-applies in CI — trace lint, full registry audit, and the
    compile/memory/donation self-checks — so BENCH_*.json tracks the
    analysis budget per round.  Host-side work: honest on any platform."""
    import jax

    platform = jax.devices()[0].platform
    import mxtpu.ndarray  # noqa: F401 — populate the registry
    from mxtpu.analysis import audit_registry, trace_lint
    from mxtpu.analysis.__main__ import (_self_apply_compile,
                                         _self_apply_donation,
                                         _self_apply_lifecycle,
                                         _self_apply_memory)

    parts = {}
    errors = 0
    for name, fn in (("trace_lint", trace_lint),
                     ("registry_audit", audit_registry),
                     ("compile_check", _self_apply_compile),
                     ("memory_estimate", _self_apply_memory),
                     ("donation_check", _self_apply_donation),
                     ("lifecycle_check", _self_apply_lifecycle)):
        t0 = time.perf_counter()
        rep = fn()
        parts["%s_s" % name] = round(time.perf_counter() - t0, 3)
        errors += len(rep.errors)
    total = round(sum(parts.values()), 3)
    print(json.dumps({
        "metric": "analysis_wall_time",
        "value": total,
        "unit": "seconds",
        "vs_baseline": None,
        "platform": platform,
        "self_lint_errors": errors,
        **parts,
        "baseline_note": "no upstream analogue (reference graph passes "
                         "ran inside C++ executors); budget metric for "
                         "the repo's own CI self-analysis",
    }), flush=True)


def _bench_sanitizer_overhead():
    """Page-sanitizer arming cost (round-17 tentpole: serving-lifecycle
    sanitizer).  The SAME bursty paged workload — four prefix-sharing
    requests decoding concurrently — runs unarmed then armed in one
    process.  Arming must change NOTHING the device sees: the streams
    are asserted bit-identical and the compile-ledger delta across the
    armed arm is asserted EMPTY (zero extra compiled programs — the
    sanitizer is pure host bookkeeping on the alloc/release/pin/COW
    seams).  The wall-clock delta is reported but is a host-side number;
    the deterministic evidence is the transition count + ledger delta."""
    import numpy as np
    import jax
    import mxtpu as mx
    from mxtpu import nd
    from mxtpu.analysis import get_ledger
    from mxtpu.analysis.lifecycle_check import (get_sanitizer,
                                                page_sanitizing)
    from mxtpu.models.transformer import (
        TransformerLM, transformer_lm_sharding_rules)
    from mxtpu.parallel import PagedContinuousBatchingEngine
    from mxtpu.parallel.mesh import DeviceMesh

    platform = jax.devices()[0].platform
    mx.random.seed(7)
    lm = TransformerLM(32, units=16, hidden_size=32, num_layers=1,
                       num_heads=2, num_kv_heads=2)
    lm.initialize()
    eng = PagedContinuousBatchingEngine(
        lm, DeviceMesh(dp=1), transformer_lm_sharding_rules(),
        num_slots=4, max_length=64, block_size=8, prefill_chunk=8)
    rng = np.random.RandomState(0)
    shared = rng.randint(0, 32, (1, 11))
    prompts = [nd.array(np.concatenate(
        [shared, rng.randint(0, 32, (1, 3 + i))], axis=1),
        dtype="int32") for i in range(4)]

    def burst():
        rids = [eng.submit(p, 6) for p in prompts]
        res = eng.run()
        return np.concatenate([res[r].asnumpy().ravel() for r in rids])

    ref = burst()                 # compiles every shape, unarmed
    reps = 5
    t0 = time.perf_counter()
    for _ in range(reps):
        unarmed_out = burst()
    unarmed_s = (time.perf_counter() - t0) / reps
    led = get_ledger()
    seq = led.sequence()
    viol_before = get_sanitizer().stats()["violations_ever"]
    t0 = time.perf_counter()
    with page_sanitizing():
        for _ in range(reps):
            armed_out = burst()
        san = get_sanitizer().stats()
    armed_s = (time.perf_counter() - t0) / reps
    extra = led.misses_after(seq)
    if not (np.array_equal(unarmed_out, ref)
            and np.array_equal(armed_out, ref)):
        raise AssertionError("armed stream diverged from unarmed")
    if extra:
        raise AssertionError(
            "armed arm compiled %d new program(s): %r"
            % (len(extra), extra))
    rec = {
        "metric": "sanitizer_overhead",
        "value": round((armed_s - unarmed_s) / unarmed_s, 4),
        "unit": "fractional wall-clock delta (armed vs unarmed)",
        "vs_baseline": None,
        "platform": platform,
        "unarmed_burst_s": round(unarmed_s, 4),
        "armed_burst_s": round(armed_s, 4),
        "streams_bit_identical": True,
        "extra_compiled_programs": 0,   # asserted above (ledger delta)
        "pages_tracked": san["pages_tracked"],
        "shadow_transitions": san["transitions"],
        "violations": san["violations_ever"] - viol_before,
        "config": {"slots": 4, "requests": 4, "max_new_tokens": 6,
                   "block_size": 8, "shared_prefix_tokens": 11,
                   "reps": reps},
        "baseline_note": "no upstream analogue; comparison column is "
                         "this repo's own unarmed burst",
    }
    if platform == "cpu":
        rec["platform_note"] = ("CPU wall-clock delta is NOISE-DOMINATED "
                                "(host bookkeeping vs CPU-bound device "
                                "compute share the same cores); the "
                                "ledger delta + bit-identical streams "
                                "are the deterministic evidence")
    print(json.dumps(rec), flush=True)


def _bench_eager_dispatch():
    """Host-side dispatch throughput (round-7 tentpole: real op bulking).
    Two small-op-heavy workloads — a 200-op elementwise chain and a
    100-parameter SGD update loop — run unbulked (one registry dispatch
    per op) and bulked (engine.bulk: lazy record + one cached fused
    program per segment).  The overhead being measured is HOST-side
    (python dispatch + per-op jax enqueue), so unlike the model benches
    this metric is honest on the CPU builder host; it is labeled with the
    platform regardless."""
    import numpy as np
    import jax
    import mxtpu as mx
    from mxtpu import engine

    platform = jax.devices()[0].platform
    rs = np.random.RandomState(0)
    x0 = mx.nd.array(rs.rand(64, 64).astype(np.float32))
    N_OPS = 200

    def chain(x):
        for _ in range(N_OPS // 4):
            x = x * 1.0009
            x = x + 0.003
            x = x.relu()
            x = x - 0.001
        return x

    def run_chain(bulk_size):
        # bulk(0) for the baseline, NOT "no context": with the ambient
        # MXTPU_ENGINE_BULK_SIZE opt-in set, a bare run would bulk too
        # and the reported speedup would collapse to ~1x
        with engine.bulk(bulk_size):
            return chain(x0).asnumpy()

    # 100-param SGD update loop over the registered fused-update op
    n_params = 100
    ws = [mx.nd.array(rs.rand(256).astype(np.float32))
          for _ in range(n_params)]
    gs = [mx.nd.array(rs.rand(256).astype(np.float32))
          for _ in range(n_params)]

    def run_sgd(bulk_size):
        with engine.bulk(bulk_size):
            outs = [mx.nd.sgd_update(w, g, 0.01, wd=1e-4)
                    for w, g in zip(ws, gs)]
            for o in outs:
                o.asnumpy()  # trace-ok: draining is the measurement

    def time_it(fn, reps):
        fn()  # warm caches (segment compile / per-op dispatch paths)
        t0 = time.perf_counter()
        for _ in range(reps):
            fn()
        return (time.perf_counter() - t0) / reps

    reps = 20 if platform == "cpu" else 30
    ref = run_chain(0)
    bulked = run_chain(N_OPS + 8)
    # tolerance note: XLA contracts mul->add into FMA inside the fused
    # program (strictly MORE accurate; docs/engine.md "Numerics"), so
    # the chain agrees to ~ulp, not bitwise
    if not np.allclose(ref, bulked, rtol=1e-5, atol=1e-7):
        raise AssertionError("bulked chain diverged from eager chain: "
                             "max |d|=%g" % np.abs(ref - bulked).max())

    engine.reset_bulk_stats()
    chain_unbulked_s = time_it(lambda: run_chain(0), reps)
    chain_bulked_s = time_it(lambda: run_chain(N_OPS + 8), reps)
    sgd_unbulked_s = time_it(lambda: run_sgd(0), reps)
    sgd_bulked_s = time_it(lambda: run_sgd(n_params + 8), reps)
    stats = engine.bulk_stats()

    chain_ops = N_OPS / chain_bulked_s
    rec = {
        "metric": "eager_dispatch_ops_per_sec",
        "value": round(chain_ops, 1),
        "unit": "ops/sec",
        "vs_baseline": None,
        "platform": platform,
        "chain_ops_per_sec_unbulked": round(N_OPS / chain_unbulked_s, 1),
        "chain_speedup_bulked": round(chain_unbulked_s / chain_bulked_s, 3),
        "sgd100_updates_per_sec_bulked": round(n_params / sgd_bulked_s, 1),
        "sgd100_updates_per_sec_unbulked": round(
            n_params / sgd_unbulked_s, 1),
        "sgd_speedup_bulked": round(sgd_unbulked_s / sgd_bulked_s, 3),
        "bulk_cache": {k: stats[k] for k in
                       ("cache_hits", "cache_misses", "flushes",
                        "bulked_ops", "eager_replays")},
        "config": {"chain_ops": N_OPS, "chain_shape": [64, 64],
                   "sgd_params": n_params, "sgd_param_shape": [256],
                   "reps": reps},
        "baseline_note": "no upstream number mounted; the comparison "
                         "column is this repo's own per-op dispatch",
        "platform_note": "host-side dispatch overhead metric — valid on "
                         "the CPU builder host (the overhead being "
                         "bulked away is python/dispatch, not device "
                         "compute)",
    }
    print(json.dumps(rec), flush=True)


def _bench_guardian():
    """Guardian cost + recovery (round-10 tentpole: training guardian).

    Metric 1, train_step_guarded_overhead: blocked per-step p50 of the
    SAME model/optimizer with and without in-step containment (fused
    finiteness reduction + where-gated update + one ok-scalar host sync
    per step) — the acceptance bar is < 5% overhead.  Honest on any
    platform since both columns run identically; labeled regardless.

    Metric 2, train_steps_to_recover: the same guarded trainer driven by
    Guardian.run over a DETERMINISTIC 1%-NaN plan (every 100th batch is
    index-poisoned with a NaN — data-driven, replayable bit-for-bit)
    plus one forced rollback via the counter-driven guardian.check site.
    The value is the extra step executions (skips consume their batch;
    the rollback replays from the last verified checkpoint)."""
    import tempfile

    import numpy as np
    import jax
    import mxtpu as mx
    from mxtpu import gluon, nd
    from mxtpu.gluon import nn
    from mxtpu.parallel import make_mesh, SPMDTrainer
    from mxtpu.resilience import Guardian, fault_plan

    platform = jax.devices()[0].platform
    cpu = platform == "cpu"
    hidden, in_units, batch = (512, 256, 512) if cpu else (2048, 1024, 256)
    timed = 30 if cpu else 40

    def build(guard):
        mx.random.seed(17)
        net = nn.HybridSequential(prefix="g_")
        net.add(nn.Dense(hidden, activation="relu", in_units=in_units,
                         prefix="a_"),
                nn.Dense(hidden, activation="relu", in_units=hidden,
                         prefix="b_"),
                nn.Dense(10, in_units=hidden, prefix="c_"))
        net.initialize()
        return net, SPMDTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                "sgd", make_mesh(dp=1),
                                optimizer_params={"learning_rate": 0.05,
                                                  "momentum": 0.9},
                                guard=guard)

    R = np.random.RandomState(0)
    X = nd.array(R.rand(batch, in_units).astype(np.float32))
    y = nd.array(R.randint(0, 10, (batch,)).astype(np.float32))

    # INTERLEAVED A/B: alternate unguarded/guarded steps so thermal/
    # scheduler drift hits both columns equally (back-to-back blocks
    # showed ±6% swings on the CPU host — larger than the effect)
    _, tr_plain = build(False)
    _, tr_guard = build(True)
    for _ in range(3):
        tr_plain.step(X, y).asnumpy()  # compile + warm
        tr_guard.step(X, y).asnumpy()
    lat_p, lat_g = [], []
    for _ in range(timed):
        t0 = time.perf_counter()
        tr_plain.step(X, y).asnumpy()  # blocked: both columns sync fully
        lat_p.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        tr_guard.step(X, y).asnumpy()
        lat_g.append(time.perf_counter() - t0)
    lat_p.sort()
    lat_g.sort()
    plain = lat_p[len(lat_p) // 2]
    guarded = lat_g[len(lat_g) // 2]
    overhead = guarded / plain - 1.0
    rec = {
        "metric": "train_step_guarded_overhead",
        "value": round(overhead * 100, 2),
        "unit": "percent",
        "vs_baseline": None,
        "platform": platform,
        "guarded_step_ms": round(guarded * 1e3, 3),
        "unguarded_step_ms": round(plain * 1e3, 3),
        "config": {"hidden": hidden, "in_units": in_units, "batch": batch,
                   "timed_steps": timed, "optimizer": "sgd+momentum",
                   "method": "interleaved A/B, blocked p50"},
        "baseline_note": "no upstream analogue (reference has no in-step "
                         "containment); the comparison column is this "
                         "repo's own unguarded compiled step",
    }
    if cpu:
        rec["platform_note"] = ("CPU builder host — both columns equally "
                                "CPU-bound, ratio indicative but NOT a "
                                "TPU number")
    print(json.dumps(rec), flush=True)

    # -- recovery under the deterministic 1%-NaN plan --------------------
    num_steps = 200 if cpu else 300

    def data_fn(step):
        # pure function of the step index (the guardian's re-seeding
        # contract): batch synthesized from a per-step seed
        Rs = np.random.RandomState(1000 + step)
        Xb = Rs.rand(batch, in_units).astype(np.float32)
        yb = Rs.randint(0, 10, (batch,)).astype(np.float32)
        if (step + 1) % 100 == 0:  # deterministic 1% NaN poisoning
            Xb[0, 0] = np.nan
        return nd.array(Xb), nd.array(yb)

    net, tr = build(True)
    g = Guardian(tempfile.mkdtemp(prefix="mxtpu-guardian-bench-"),
                 max_skips=2, checkpoint_every=25)
    plan = "guardian.check@%d:raise" % (num_steps // 2)
    from mxtpu.analysis import get_ledger
    _led = get_ledger()
    _step_compiles_before = sum(
        _led.miss_counts(("spmd_trainer.step",)).values())
    t0 = time.perf_counter()
    with fault_plan(plan):
        stats = g.run(tr, data_fn, num_steps)
    dt = time.perf_counter() - t0
    extra = stats["steps"] - num_steps
    rec = {
        "metric": "train_steps_to_recover",
        "value": extra,
        "unit": "extra step executions",
        "vs_baseline": None,
        "platform": platform,
        "effective_steps": num_steps,
        "skips": stats["skips"],
        "rollbacks": stats["rollbacks"],
        "checkpoints": stats["checkpoints"],
        # ledger-counted train-step programs over the whole guarded
        # loop: the discipline number (1 = no retraces across skips,
        # rollbacks, and replays)
        "compiled_program_count": sum(
            _led.miss_counts(("spmd_trainer.step",)).values())
        - _step_compiles_before,
        "wall_s": round(dt, 2),
        "fault_plan": "NaN batch every 100th step (index-driven) + %s"
                      % plan,
        "baseline_note": "no upstream analogue; deterministic counter/"
                         "index-driven faults, replayable bit-for-bit",
    }
    if cpu:
        rec["platform_note"] = ("CPU builder host — recovery STEP counts "
                                "are platform-independent; wall time is "
                                "not a TPU number")
    print(json.dumps(rec), flush=True)

    # -- multi-step fused windows: steps/s at N∈{1,8,64} -----------------
    # Same rig, same model: N steps compiled as ONE donated lax.scan
    # program (docs/training.md) — the host dispatches once and reads
    # one ok-vector per window instead of per step.  On the CPU builder
    # host the win being measured is python/dispatch/sync overhead, so
    # wall-clock is NOISE-labeled; the deterministic evidence is the
    # ledger program count (one program per N) and the once-per-N sync
    # counter.
    total = 64
    per_window = {}
    from mxtpu.observability import get_registry as _get_registry
    _reg = _get_registry()
    _multi_before = sum(
        _led.miss_counts(("spmd_trainer.step_multi",)).values())
    _res_before = _reg.snapshot(sources=("resilience",))
    for N in (1, 8, 64):
        _, tr = build(True)
        if N == 1:
            tr.step(X, y).asnumpy()  # compile + warm
            t0 = time.perf_counter()
            for _ in range(total):
                loss = tr.step(X, y)
            loss.asnumpy()
            dt = time.perf_counter() - t0
        else:
            Xw = np.broadcast_to(
                X.asnumpy(), (N,) + tuple(X.shape)).copy()
            yw = np.broadcast_to(
                y.asnumpy(), (N,) + tuple(y.shape)).copy()
            tr.step_window(Xw, yw).losses.asnumpy()  # compile + warm
            t0 = time.perf_counter()
            for _ in range(total // N):
                res = tr.step_window(Xw, yw)
            res.losses.asnumpy()
            dt = time.perf_counter() - t0
        per_window[str(N)] = round(total / dt, 1)
    rec = {
        "metric": "train_steps_per_sec_multistep",
        "value": per_window["64"],
        "unit": "steps/sec at N=64",
        "vs_baseline": None,
        "platform": platform,
        "per_window": per_window,
        "speedup_n64_vs_n1": round(
            per_window["64"] / per_window["1"], 2),
        # deterministic evidence: one compiled program per window size
        # (N=8 and N=64), and one host sync per dispatched window
        "step_multi_programs": sum(
            _led.miss_counts(("spmd_trainer.step_multi",)).values())
        - _multi_before,
        "window_syncs": _reg.delta(_res_before, _reg.snapshot(
            sources=("resilience",))).get(
            "resilience.train_window_syncs", 0),
        "config": {"hidden": hidden, "in_units": in_units,
                   "batch": batch, "steps_per_column": total,
                   "optimizer": "sgd+momentum", "guard": True},
        "baseline_note": "no upstream analogue; comparison column is "
                         "this repo's own per-step guarded drive (N=1)",
    }
    if cpu:
        rec["platform_note"] = ("CPU builder host — wall-clock ratio is "
                                "NOISE-DOMINATED (dispatch overhead vs "
                                "CPU-bound compute); the program/sync "
                                "counts are the platform-independent "
                                "evidence; TPU steps/s: not measured")
    print(json.dumps(rec), flush=True)


def _battery():
    _bench_analysis()
    _bench_sanitizer_overhead()
    _bench_eager_dispatch()
    _bench_guardian()
    _bench_resnet()
    _bench_bert()
    _bench_attention()
    _bench_continuous_decode()
    _bench_trace_overhead()
    _bench_paged_decode()
    _bench_kernel_traffic()
    _bench_speculative_decode()
    _bench_tree_speculative()
    _bench_quantized_decode()
    _bench_hierarchical_cache()
    _bench_router()
    # _bench_cross_process is out of the on-chip battery: this process
    # holds the chip, and SubprocessReplica refuses to start workers
    # under a parent that does (docs/serving.md "One process per chip").
    # ROADMAP B5 gives it a four-chip home.
    _bench_autoscale()


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.stderr.write(
            "bench: no TPU (jax platform %r) — device metrics are "
            "measured on the chip or not at all\n" % dev.platform)
        return 1
    from mxtpu.runtime import enable_compile_cache

    enable_compile_cache()
    _battery()
    return 0


if __name__ == "__main__":
    sys.exit(main())
