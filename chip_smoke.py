#!/usr/bin/env python
"""The quickest proof that mxtpu still starts on the chip.

    python chip_smoke.py             # one TPU chip  (what the driver runs)
    python chip_smoke.py --chips 4   # one four-chip host: the multi-chip
                                     # path and what it is compared with,
                                     # nothing else

One process (a chip belongs to one process), the entry points a user
calls, published widths, random weights from SEED:

- trainer: BERT-base (12 x 768, batch 32 x seq 128, MLM head, bf16,
  adam) and ResNet-50 (bf16, batch 128, sgd) through ``SPMDTrainer``,
  — finite, falling loss on a fixed batch, the
  Pallas flash-attention kernel in the compiled BERT step;
- server: Llama-3-8B at every published width (units 4096, hidden 14336,
  32 Q / 8 KV heads of 128, vocab 128256), depth cut from 32 to 8 layers
  so weights + cache fit one 16 GB chip, bf16, through ``ShardedDecoder``
  -> ``PagedContinuousBatchingEngine`` -> ``Gateway``, with a bf16
  cache and again with the int8 cache: every stream equals the same
  request served alone, stays within a coarse bound of greedy under the
  plain full forward, and is compared with an isolated
  ``ShardedDecoder.generate``; the paged-decode / chunked-prefill Pallas
  kernels must be the path taken.

Any failure is a traceback and a non-zero exit; no TPU is a non-zero
exit before anything runs.  The last line of a passing run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

import argparse
import importlib.metadata
import json
import os
import sys
import time

SEED = 0
BERT_VOCAB, LLAMA_VOCAB = 30522, 128256
#: what a compiled Pallas (Mosaic) kernel is called in a TPU program
KERNEL_CALL = "tpu_custom_call"
#: How far from greedy a served token may be under the plain full
#: forward's own logits, as a fraction of the largest logit magnitude.
#: Three paths compute the same function — the plain forward (flash
#: kernel, no cache), isolated generate (XLA, contiguous cache) and the
#: paged server (Pallas kernels) — and in bf16, at random weights whose
#: logits tie within a few units in the last place, they round apart:
#: on the chip generate's OWN streams sit up to 0.017 (bf16 cache) and
#: 0.034 (int8 cache) from greedy by this measure (PERF.md, PR 22).  A
#: wrong token sits near 1.0 — so this bound catches a broken server,
#: not a subtly wrong kernel: it admits the runner-up logit (0.043 away
#: at the printed position).  Telling rounding from a fault needs the
#: comparison made where rounding cannot reach — float32 at full matmul
#: precision; designed and compiled for the chip, not yet run there
#: (PERF.md section 7).
NEAR_GREEDY = {"bfloat16": 2.0 ** -4, "int8": 2.0 ** -3}


def _version(package):
    try:
        return importlib.metadata.version(package)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def _gib(n):
    return "%.2f GiB" % (n / 2.0 ** 30)


def _hbm(dev):
    stats = dev.memory_stats() or {}
    return ("HBM in use %s, peak %s" % (
        _gib(stats["bytes_in_use"]), _gib(stats["peak_bytes_in_use"]))
        if stats else "HBM: not reported by this backend")


# ------------------------------------------------------------------ device

def device_phase(chips):
    import jax
    import jax.numpy as jnp
    import jaxlib

    devices = jax.devices()
    dev = devices[0]
    print("device: platform=%s kind=%s count=%d | jax %s jaxlib %s libtpu %s"
          % (dev.platform, dev.device_kind, len(devices), jax.__version__,
             jaxlib.__version__, _version("libtpu")), flush=True)
    if dev.platform != "tpu":
        sys.exit("chip_smoke: JAX found no TPU (platform %r) — nothing was "
                 "run and nothing is reported" % dev.platform)
    if len(devices) < chips:
        sys.exit("chip_smoke: --chips %d needs %d chips, this host has %d"
                 % (chips, chips, len(devices)))

    # does block_until_ready block?  Every time below depends on it: a
    # read of a finished result must cost next to nothing
    x = jnp.ones((8192, 8192), jnp.bfloat16)

    @jax.jit
    def chain(x):
        for _ in range(8):
            x = (x @ x) * (1.0 / 8192)
        return x[:1, :1].astype(jnp.float32)

    float(chain(x)[0, 0])           # compile, and warm the host read
    t0 = time.perf_counter()
    y = chain(x)
    t1 = time.perf_counter()
    y.block_until_ready()
    t2 = time.perf_counter()
    float(y[0, 0])
    t3 = time.perf_counter()
    flops = 8 * 2 * 8192 ** 3
    print("device: 8 x 8192^3 bf16 matmuls: dispatch %.2f ms, "
          "block_until_ready %.2f ms (%.0f TFLOP/s), host read after it "
          "%.2f ms" % ((t1 - t0) * 1e3, (t2 - t1) * 1e3,
                       flops / (t2 - t0) / 1e12, (t3 - t2) * 1e3),
          flush=True)
    assert t3 - t2 < 0.2 * (t2 - t0), \
        "block_until_ready returned before the device finished"
    return devices


# ----------------------------------------------------------------- trainer

def bert_for_mlm(seq):
    """BERT-base with the MLM head as the training output, and its
    loss."""
    from mxtpu import gluon
    from mxtpu.gluon import HybridBlock
    from mxtpu.models import transformer

    class BertForMLM(HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.bert = transformer.bert_base(max_length=seq,
                                                  dropout=0.0)

        def hybrid_forward(self, F, tokens):
            _seq, _pooled, mlm = self.bert(tokens)
            return mlm

    class MLMLoss(gluon.loss.Loss):
        def __init__(self):
            super().__init__(1.0, 0)
            self._ce = gluon.loss.SoftmaxCrossEntropyLoss()

        def hybrid_forward(self, F, mlm, labels):
            return self._ce(mlm.reshape((-1, mlm.shape[-1])),
                            labels.reshape((-1,)))

    return BertForMLM(), MLMLoss()


def bert_trainer(mesh, rules=None, batch=32, seq=128):
    import numpy as np

    import mxtpu as mx
    from mxtpu.parallel import SPMDTrainer

    mx.random.seed(SEED)
    net, loss = bert_for_mlm(seq)
    net.initialize()
    net.cast("bfloat16")
    trainer = SPMDTrainer(net, loss, "adam", mesh, rules=rules,
                          optimizer_params={"learning_rate": 1e-4})
    rng = np.random.RandomState(SEED)
    X = mx.nd.array(rng.randint(0, BERT_VOCAB, (batch, seq)), dtype="int32")
    y = mx.nd.array(rng.randint(0, BERT_VOCAB, (batch, seq)), dtype="int32")
    return trainer, X, y


def resnet_trainer(mesh, batch=128):
    import numpy as np

    import mxtpu as mx
    from mxtpu import gluon
    from mxtpu.gluon.model_zoo import vision
    from mxtpu.parallel import SPMDTrainer

    mx.random.seed(SEED)
    net = vision.resnet50_v1()
    net.initialize()
    net.cast("bfloat16")
    # an lr of 0.1 suits a throughput reading, not a falling loss on one
    # fixed random batch
    trainer = SPMDTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                          "sgd", mesh,
                          optimizer_params={"learning_rate": 0.01,
                                            "momentum": 0.9})
    rng = np.random.RandomState(SEED)
    X = mx.nd.array(rng.rand(batch, 3, 224, 224), dtype="bfloat16")
    y = mx.nd.array(rng.randint(0, 1000, (batch,)), dtype="int32")
    return trainer, X, y


def take_steps(name, trainer, X, y, steps):
    """``steps`` optimizer steps on one fixed batch: losses finite and
    falling.  Returns them."""
    import numpy as np

    t0 = time.perf_counter()
    losses = [float(trainer.step(X, y).asnumpy())]
    t1 = time.perf_counter()
    losses += [float(trainer.step(X, y).asnumpy())
               for _ in range(steps - 1)]
    t2 = time.perf_counter()
    print("trainer: %s first step (compile + run) %.1f s, then %.1f ms "
          "a step; losses %s" % (name, t1 - t0,
                                 (t2 - t1) / (steps - 1) * 1e3,
                                 " ".join("%.4f" % v for v in losses)),
          flush=True)
    assert np.all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], "loss did not fall: %r" % losses
    return losses


def trainer_phase(dev):
    from mxtpu.ops.pallas import counters
    from mxtpu.parallel import make_mesh

    mesh = make_mesh(dp=1, devices=[dev])
    trainer, X, y = bert_trainer(mesh)
    before = counters.count("flash_attention")
    take_steps("BERT-base 12x768 batch 32x128 bf16 adam", trainer, X, y, 6)
    traced = counters.count("flash_attention") - before
    in_step = KERNEL_CALL in trainer.lower_step(X, y).as_text()
    print("trainer: flash_attention traced %d time(s); %s in the lowered "
          "BERT step: %s; %s" % (traced, KERNEL_CALL, in_step, _hbm(dev)),
          flush=True)
    assert traced > 0 and in_step
    del trainer, X, y

    trainer, X, y = resnet_trainer(mesh)
    take_steps("ResNet-50 batch 128 bf16 sgd", trainer, X, y, 5)
    print("trainer: " + _hbm(dev), flush=True)


# ------------------------------------------------------------------ server

def llama(depth_factor=0.25):
    """Llama-3-8B at every published width; depth 32 * depth_factor."""
    import mxtpu as mx
    from mxtpu.models import transformer

    mx.random.seed(SEED)
    lm = transformer.llama_3_8b(width_factor=1.0, depth_factor=depth_factor)
    lm.collect_params().setattr("grad_req", "null")   # serving: no grads
    lm.cast("bfloat16")         # before initialize: never an f32 copy
    lm.initialize(mx.init.Normal(0.02))     # the published init std
    lm(mx.nd.zeros((1, 16), dtype="int32"))   # resolve deferred shapes
    return lm


def prompts(lengths):
    import numpy as np

    rng = np.random.RandomState(SEED)
    return [rng.randint(0, LLAMA_VOCAB, (1, n)).astype(np.int32)
            for n in lengths]


def greedy_gap(lm, prompt, stream):
    """How far from greedy is ``stream`` under the plain full forward
    (no cache, no paging)?  Teacher-forced over prompt + stream, padded
    to a power of two so every request reuses one set of compiled ops:
    the largest shortfall of an emitted token's logit below that
    position's maximum, as a fraction of the largest logit magnitude
    (NEAR_GREEDY is the bound on it)."""
    import numpy as np

    import mxtpu as mx

    tokens = np.concatenate([prompt[0], stream])
    padded = np.zeros((1, 1 << int(len(tokens) - 1).bit_length()), np.int32)
    padded[0, :len(tokens)] = tokens
    at = np.arange(prompt.shape[1] - 1, len(tokens) - 1)
    logits = lm(mx.nd.array(padded, dtype="int32"))[0].astype("float32")
    logits = logits.asnumpy()[at]                       # (new, vocab)
    short = logits.max(axis=1) - logits[np.arange(len(at)), stream]
    return float((short / np.abs(logits).max(axis=1)).max())


def serve(gw, requests, new_tokens):
    """Submit ``requests`` together, run the gateway dry, return each
    request's generated tokens."""
    import numpy as np

    import mxtpu as mx

    rids = [gw.submit(mx.nd.array(p, dtype="int32"), new_tokens)
            for p in requests]
    results = gw.run()
    streams = []
    for p, rid in zip(requests, rids):
        assert gw.status(rid) == "ok", (rid, gw.status(rid), gw.error(rid))
        out = results[rid].asnumpy()
        assert out.shape == (1, p.shape[1] + new_tokens), out.shape
        assert np.array_equal(out[0, :p.shape[1]], p[0])
        streams.append(out[0, p.shape[1]:])
    return streams


def serve_and_compare(lm, engine, dec, requests, new_tokens, cache_dtype):
    """Serve ``requests`` together through Gateway -> engine, and hold
    every stream to three references:

    - the same request served alone through the same server: equal,
      token for token (an answer does not depend on its neighbours);
    - the plain full forward, an independent implementation: every
      token greedy to within NEAR_GREEDY;
    - isolated ``ShardedDecoder.generate``: printed, with how far its
      own stream sits from greedy, as the measure of what rounding
      alone does between two correct paths."""
    import numpy as np

    import mxtpu as mx
    from mxtpu.serving import Gateway

    gw = Gateway([engine])
    t0 = time.perf_counter()
    together = serve(gw, requests, new_tokens)
    t1 = time.perf_counter()
    alone = [serve(gw, [p], new_tokens)[0] for p in requests]
    t2 = time.perf_counter()
    bound = NEAR_GREEDY[cache_dtype]
    for p, stream, solo in zip(requests, together, alone):
        assert np.array_equal(stream, solo), \
            "prompt %d: served with neighbours %r, alone %r" % (
                p.shape[1], stream, solo)
        ref = dec.generate(mx.nd.array(p, dtype="int32"), new_tokens,
                           max_length=1024, cache_dtype=cache_dtype
                           ).asnumpy()[0, p.shape[1]:]
        parts = np.flatnonzero(stream != ref)
        gap, ref_gap = greedy_gap(lm, p, stream), greedy_gap(lm, p, ref)
        print("server: %s prompt %4d: alone == together; at most %.4f of "
              "the largest logit from greedy (bound %.4f); isolated "
              "generate %s, itself %.4f from greedy"
              % (cache_dtype, p.shape[1], gap, bound,
                 "gives the same %d tokens" % new_tokens if not len(parts)
                 else "parts at token %d" % parts[0], ref_gap), flush=True)
        assert gap <= bound, "the served stream is not greedy decoding"
    print("server: %s cache: %d requests of %s prompt tokens + %d new: "
          "together %.1f s (compiles included), one at a time %.1f s, "
          "references %.1f s"
          % (cache_dtype, len(requests),
             "/".join(str(p.shape[1]) for p in requests), new_tokens,
             t1 - t0, t2 - t1, time.perf_counter() - t2), flush=True)


def engine_programs(tag):
    """Compiled programs of the engine with this ledger tag, by site."""
    from mxtpu.analysis.compile_ledger import get_ledger

    return {site: n for site, n in get_ledger().miss_counts(
        ("serving.*",)).items() if site.endswith("@" + tag) and n}


def server_phase(dev):
    from mxtpu.models.transformer import transformer_lm_sharding_rules
    from mxtpu.ops.pallas import counters
    from mxtpu.parallel import (PagedContinuousBatchingEngine,
                                ShardedDecoder, make_mesh)

    print("server: Llama-3-8B widths as published (units 4096, hidden "
          "14336, 32 Q / 8 KV heads of 128, vocab 128256); depth cut from "
          "32 to 8 layers so weights + cache fit one 16 GB chip",
          flush=True)
    t0 = time.perf_counter()
    lm = llama()
    mesh = make_mesh(dp=1, devices=[dev])
    rules = transformer_lm_sharding_rules()
    dec = ShardedDecoder(lm, mesh, rules)
    n_params = sum(p.data().size for p in lm.collect_params().values())
    print("server: %.2f B parameters built in %.1f s; %s"
          % (n_params / 1e9, time.perf_counter() - t0, _hbm(dev)),
          flush=True)

    before = dict(counters.counts())
    # prompts of 40..700 tokens: one chunk in the 64 bucket, one in the
    # 512 bucket, and 512 + a 256-bucket remainder; five requests over
    # four slots, so one waits for a slot to free
    engine = PagedContinuousBatchingEngine(
        lm, mesh, rules, num_slots=4, max_length=1024, block_size=16,
        prefill_chunk=512, cache_dtype="bfloat16", ledger_tag="bf16")
    serve_and_compare(lm, engine, dec, prompts([40, 300, 700, 60, 450]),
                      32, "bfloat16")
    check_engine(engine, "bf16", counters, before, dev)

    before = dict(counters.counts())
    # the int8 cache needs a page of 32 positions (K002: int8 sublane
    # tile) for the quantized kernels to be legal on the chip
    engine = PagedContinuousBatchingEngine(
        lm, mesh, rules, num_slots=4, max_length=1024, block_size=32,
        prefill_chunk=512, cache_dtype="int8", ledger_tag="int8")
    serve_and_compare(lm, engine, dec, prompts([40, 300]), 32, "int8")
    check_engine(engine, "int8", counters, before, dev)


def check_engine(engine, tag, counters, before, dev):
    """The kernels were the path taken, and the engine stayed inside its
    documented compile bound (#chunk buckets + 1 step program)."""
    stats = engine.stats
    traced = {k: counters.count(k) - before.get(k, 0)
              for k in ("paged_attention", "paged_prefill")}
    programs = engine_programs(tag)
    print("server: %s kernels traced %s; attention paths %s; compiled "
          "programs %s; %d decode steps, %d tokens; %s"
          % (tag, traced, json.dumps(stats["attention_paths"]), programs,
             stats["steps"], stats["generated_tokens"], _hbm(dev)),
          flush=True)
    assert all(n > 0 for n in traced.values()), traced
    paths = stats["attention_paths"]
    assert paths and all(v.startswith("pallas: ") for v in paths.values())
    assert {k.partition("[")[0] for k in paths} == {
        "paged_attention", "paged_prefill"}
    prefill = sum(n for s, n in programs.items() if "page_prefill" in s)
    step = sum(n for s, n in programs.items() if "step_pages" in s)
    buckets = sum(k.startswith("paged_prefill[") for k in paths)
    assert step == 1 and 1 <= prefill <= buckets, (programs, buckets)
    assert sum(programs.values()) <= buckets + 1, programs
    assert stats["blocks_in_use"] == 0, "pages leaked: %r" % stats


# -------------------------------------------------------------- four chips

def four_chip_phase(devices, dump_dir):
    """What exists only across chips, and what it is compared with:
    (a) the same 8-layer full-width Llama under tp=4 through the paged
    engine against tp=1 on chip 0; (b) BERT-base SPMDTrainer steps on
    dp=2 x tp=2 against one chip."""
    import glob

    import jax

    from mxtpu.models.transformer import (bert_sharding_rules,
                                          transformer_lm_sharding_rules)
    from mxtpu.ops.pallas import counters
    from mxtpu.parallel import (PagedContinuousBatchingEngine,
                                ShardedDecoder, make_mesh)

    from jax.experimental.compilation_cache import compilation_cache

    four = list(devices[:4])
    rules = transformer_lm_sharding_rules()
    requests = prompts([40, 300])
    # (a)'s programs are inspected after compilation (dump_dir), so they
    # are compiled here and not fetched from the persistent cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    # (a) reference: tp=1 on chip 0, isolated generate
    one = make_mesh(dp=1, devices=four[:1])
    lm1 = llama()
    dec1 = ShardedDecoder(lm1, one, rules)
    # tp=4: the same weights (same seed) split over four chips
    tp4 = make_mesh(tp=4, devices=four)
    lm4 = llama()
    before = dict(counters.counts())
    engine = PagedContinuousBatchingEngine(
        lm4, tp4, rules, num_slots=4, max_length=1024, block_size=16,
        prefill_chunk=512, cache_dtype="bfloat16", ledger_tag="tp4")
    serve_and_compare(lm1, engine, dec1, requests, 32, "bfloat16")
    check_engine(engine, "tp4", counters, before, four[0])

    def split_four_ways(what, arrays):
        total = held = 0
        for a in arrays:
            shards = a.addressable_shards
            assert {s.device for s in shards} == set(four), what
            assert all(s.data.nbytes * 4 == a.nbytes for s in shards), \
                "%s %r is not split four ways" % (what, a.shape)
            total += a.nbytes
            held += shards[0].data.nbytes
        print("four chips: %d %s, %s in all, %s a chip"
              % (len(arrays), what, _gib(total), _gib(held)), flush=True)

    params = [p.data()._data for p in lm4.collect_params().values()]
    matrices = [a for a in params if a.ndim >= 2]
    split_four_ways("weight matrices", matrices)
    vectors = sum(a.nbytes for a in params if a.ndim < 2)
    assert vectors < 1e-3 * sum(a.nbytes for a in matrices)  # norm gains
    stats = engine.stats
    pool = [a for a in jax.live_arrays() if a.ndim == 4
            and a.shape[0] == stats["num_blocks"] + 1
            and a.shape[2] == stats["block_size"]]
    assert len(pool) == 2 * len(lm4.layers), len(pool)  # K, V per layer
    split_four_ways("cache pool leaves", pool)
    programs = [open(f).read() for f in glob.glob(os.path.join(
        dump_dir, "*jit_program*after_optimizations.txt"))]
    both = sum("all-reduce" in t and KERNEL_CALL in t for t in programs)
    print("four chips: %d of %d compiled serving programs carry both "
          "all-reduce and %s (the kernel under head_shard_map)"
          % (both, len(programs), KERNEL_CALL), flush=True)
    assert both >= 3        # two prefill buckets and the decode step
    del lm1, lm4, dec1, engine
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()

    # (b) BERT-base, dp=2 x tp=2 against one chip: same seed, same batch
    trainer, X, y = bert_trainer(one)
    ref = take_steps("BERT-base on one chip", trainer, X, y, 3)
    del trainer
    trainer, X, y = bert_trainer(make_mesh(dp=2, tp=2, devices=four),
                                 rules=bert_sharding_rules())
    got = take_steps("BERT-base on dp=2 x tp=2", trainer, X, y, 3)
    worst = max(abs(a - b) / abs(b) for a, b in zip(got, ref))
    print("four chips: BERT-base losses agree within %.4f (bound 0.02)"
          % worst, flush=True)
    assert worst < 0.02, (got, ref)


# -------------------------------------------------------------------- main

def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args()
    here = os.path.dirname(os.path.abspath(__file__))
    dump_dir = os.path.join(here, ".scratch", "hlo_chips4")
    if args.chips == 4:
        # the compiled tp=4 serving programs, to look for collectives in
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_dump_to=%s "
            "--xla_dump_hlo_as_text --xla_dump_hlo_module_re=jit_program"
            % dump_dir).strip()

    import jax

    from mxtpu.io import native_decode          # initialises no backend
    from mxtpu.runtime import enable_compile_cache

    t_start = time.perf_counter()
    devices = device_phase(args.chips)

    cache = {"hits": 0, "misses": 0}

    def count(event, **_):
        if event.endswith("/cache_hits"):
            cache["hits"] += 1
        elif event.endswith("/cache_misses"):
            cache["misses"] += 1

    jax.monitoring.register_event_listener(count)
    cache_dir = enable_compile_cache()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print("compile cache: %s holds %d entries at start; native JPEG "
          "decoder loaded: %s" % (cache_dir, entries,
                                  native_decode.available()), flush=True)

    if args.chips == 4:
        four_chip_phase(devices, dump_dir)
    else:
        t0 = time.perf_counter()
        trainer_phase(devices[0])
        t1 = time.perf_counter()
        jax.clear_caches()
        server_phase(devices[0])
        t2 = time.perf_counter()
        print("phases: trainer %.0f s, server %.0f s" % (t1 - t0, t2 - t1),
              flush=True)
    print("compile cache: %d hits, %d programs compiled and written; "
          "whole run %.0f s" % (cache["hits"], cache["misses"],
                                time.perf_counter() - t_start), flush=True)
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
