"""Runner of serving cells: ``Gateway.submit`` / ``Gateway.pump`` over
one ``PagedContinuousBatchingEngine`` holding the weights made from
``--seed``.

Set-up builds the server, warms every compiled shape the mix uses (each
prefill bucket and the pooled decode step), then serves the mix's first
cycle - every (prompt, output) size the mix has, once - so that the window
opens in steady state with every shape met.  The window
drives the same loop: the generator keeps work offered, every pump's new
tokens are stamped with the host clock as they reach the client.  After
the window closes and the device's peak memory is read, the server is
freed and the plain reference runs once over a sample (drawn from the
seed, the longest request in it) of the requests the window finished:
prompt and served tokens, float32, layer by layer.  ``correct`` compares
the widest gap by which a served token's logit lies below the
reference's best at its position — valid because every request is greedy
— and that every finished request has the length it asked for.
"""

import gc
import json
import sys
import time

import jax.numpy as jnp
import numpy as np

from chipbench import compare, flops, models

PAD = 512       # reference sequences are padded to a multiple of this
SETUP_LIMIT_S = 240     # warm-up or lead-in longer than this is a fault


class Request:
    def __init__(self, n, prompt, output):
        self.n, self.prompt, self.output = n, prompt, output
        self.rid = None
        self.arrivals = []          # host time of each streamed token
        self.tokens = []
        self.status = None
        self.finished = None


class Driver:
    """The client side: offers the mix's requests, pumps the gateway,
    stamps tokens."""

    def __init__(self, cell, gateway, engine, stream):
        self.cell, self.gw, self.engine = cell, gateway, engine
        self.stream = stream
        self.live = {}              # rid -> Request
        self.done = []
        self.occupancy = []         # (time, decoding, prefilling, queued)
        slots = cell.config["serve"]["engine"]["num_slots"]
        self.keep = (1 + cell.traffic["pending_per_slot"]) * slots

    def offer(self):
        """Backlog: keep ``num_slots`` running and ``pending_per_slot``
        times as many waiting."""
        import mxtpu as mx

        while len(self.live) < self.keep:
            n, prompt, output = next(self.stream)
            req = Request(n, prompt, output)
            req.rid = self.gw.submit(mx.nd.array(prompt, dtype="int32"),
                                     output)
            self.live[req.rid] = req

    def pump(self):
        with self.cell.span("gateway.pump"):
            finished = self.gw.pump()
        now = time.perf_counter()
        slots = [s for s in self.engine._slots if s is not None]
        prefilling = sum(bool(s.prefilling) for s in slots)
        self.occupancy.append((now, len(slots) - prefilling, prefilling,
                               self.engine.pending))
        for rid, req in self.live.items():
            tokens = self.gw.streamed(rid)
            if len(tokens) > len(req.tokens):
                req.arrivals += [now] * (len(tokens) - len(req.tokens))
                req.tokens = tokens
        for rid in finished:
            req = self.live.pop(rid, None)
            if req is None:
                continue
            req.status, req.finished = self.gw.status(rid), now
            req.tokens = self.gw.streamed(rid)
            self.gw.take_result(rid)
            self.done.append(req)

    def run(self, seconds):
        start = time.perf_counter()
        while time.perf_counter() - start < seconds:
            with self.cell.span("generator"):
                self.offer()
            self.pump()
        return start, time.perf_counter()

    def lead_in(self):
        """Serve the mix until every request of its first cycle has
        finished: the window then opens in steady state, and every
        shape the mix's sizes bring has been met once."""
        first = self.cell.traffic["cycle"]
        start = time.perf_counter()
        while sum(r.n < first for r in self.done) < first:
            with self.cell.span("generator"):
                self.offer()
            self.pump()
            if time.perf_counter() - start > SETUP_LIMIT_S:
                raise RuntimeError(
                    "the mix's first cycle did not finish in %d s: %d of %d "
                    "requests done, gateway %r" % (
                        SETUP_LIMIT_S, len(self.done), first,
                        {k: v for k, v in self.gw.stats.items()
                         if isinstance(v, int)}))


def warm_up(cell, gateway):
    """One request for every prefill bucket the mix can reach (a last
    chunk of 8 .. prefill_chunk tokens after one whole chunk), a few
    tokens each: every compiled shape of the window, and no other."""
    import mxtpu as mx

    chunk = cell.config["serve"]["engine"]["prefill_chunk"]
    vocab = cell.config["vocab_size"]
    rng = np.random.default_rng([cell.seed, 3])
    lengths, b = [], 8
    while b <= chunk:
        lengths.append(min(chunk + b, cell.traffic["prompt"]["max"]))
        b *= 2
    rids = [gateway.submit(mx.nd.array(rng.integers(
        0, vocab, (1, n), dtype=np.int32), dtype="int32"), 4)
        for n in lengths]
    start = time.perf_counter()
    while any(gateway.status(rid) in ("queued", "dispatched")
              for rid in rids):
        gateway.pump()
        if time.perf_counter() - start > SETUP_LIMIT_S:
            break
    for rid in rids:
        if gateway.status(rid) != "ok":
            raise RuntimeError("warm-up request %r: %s %r" % (
                rid, gateway.status(rid), gateway.error(rid)))
        gateway.take_result(rid)


def spanned(cell, engine):
    """Traced runs: a host span around every ``engine.step()``, wrapped
    on the instance."""
    step = engine.step

    def traced_step():
        with cell.span("engine.step"):
            return step()

    engine.step = traced_step


def window_tokens(cfg, requests, start, end):
    """(tokens, model operations) credited to [start, end]: each
    generated token at its arrival, a request's prompt with its first
    token's."""
    tokens, ops = 0, 0.0
    layers = cfg["num_hidden_layers"]
    head = flops.decoder_head_flops_per_token(cfg)
    for req in requests:
        P = req.prompt.shape[1]
        for k, at in enumerate(req.arrivals):
            if not start <= at <= end:
                continue
            if k == 0:
                tokens += P + 1
                ops += flops.decoder_prefill_flops(cfg, P) + head
            else:
                tokens += 1
                ops += layers * flops.decoder_layer_flops_per_token(
                    cfg, P + k) + head
    return tokens, ops


def sample(requests, seed, count):
    """``count`` of the finished requests, drawn from the seed, and the
    longest of all with them."""
    ok = sorted((r for r in requests if r.status == "ok" and r.tokens),
                key=lambda r: r.n)
    if not ok:
        return []
    longest = max(ok, key=lambda r: r.prompt.shape[1] + len(r.tokens))
    rng = np.random.default_rng([int(seed), 4])
    drawn = [ok[i] for i in rng.permutation(len(ok))[:count]]
    return [longest] + [r for r in drawn if r is not longest][:count - 1]


def served_logit_gaps(cell, reference, requests, matmul="highest",
                      against=None):
    """For each request, the gaps (reference's best logit minus the
    logit of the token in question) at every served position, from one
    teacher-forced pass of the reference over prompt + served tokens.
    The token in question is the served one; with ``against`` (the
    float32 logits of an earlier call) it is the token that THIS
    ``matmul`` puts first, judged by those logits: the control's
    reading.  Returns (gaps per request, logits per request)."""
    cfg = cell.config
    sequences, rows = [], []
    for req in requests:
        P, n = req.prompt.shape[1], len(req.tokens)
        seq = np.concatenate([req.prompt[0], np.asarray(req.tokens[:-1],
                                                        np.int32)])
        padded = np.zeros(-(-len(seq) // PAD) * PAD, np.int32)
        padded[:len(seq)] = seq
        sequences.append(padded)
        rows.append(np.arange(P - 1, P - 1 + n))
    logits = reference.logits_at(cfg, cell.seed, cfg["serve"]["dtype"],
                                 sequences, rows, matmul)
    gaps = []
    for i, req in enumerate(requests):
        if against is None:
            judge, chosen = logits[i], jnp.asarray(req.tokens)
        else:
            judge, chosen = against[i], jnp.argmax(logits[i], axis=-1)
        picked = jnp.take_along_axis(judge, chosen[:, None], axis=-1)[:, 0]
        gaps.append(np.asarray(judge.max(axis=-1) - picked))
    return gaps, logits


def serve_window(cell, reference, generator, seconds):
    """Set-up, lead-in and window.  Returns what the window saw; the
    server is freed."""
    cfg, serve = cell.config, cell.config["serve"]
    gateway, engine = models.decoder_server(
        cfg, serve, lambda names: reference.init_leaves(
            cfg, cell.seed, names, serve["dtype"]), cell.devices)
    if cell.trace:
        spanned(cell, engine)
    warm_up(cell, gateway)
    driver = Driver(cell, gateway, engine,
                    generator.requests(cell.traffic, cfg, cell.seed))
    driver.lead_in()
    with cell.window():
        start, end = driver.run(seconds)
    stats = dict(engine.stats)
    inside = np.array([o[1:] for o in driver.occupancy
                       if start <= o[0] <= end], float)
    stats.update(pumps_in_window=len(inside),
                 mean_decoding_slots=float(inside[:, 0].mean()),
                 mean_prefilling_slots=float(inside[:, 1].mean()),
                 mean_engine_queue=float(inside[:, 2].mean()))
    everyone = driver.done + list(driver.live.values())
    finished = [r for r in driver.done if start <= r.finished <= end]
    del driver, gateway, engine
    gc.collect()
    return {"start": start, "end": end, "requests": everyone,
            "finished": finished, "engine_stats": stats}


def _quantiles(values):
    if not values:
        return None
    v = np.sort(np.asarray(values))
    return {"n": len(v), "mean": float(v.mean()),
            "p50": float(v[len(v) // 2]), "p95": float(v[int(0.95 * len(v))]),
            "max": float(v[-1])}


def run(cell):
    cfg = cell.config
    reference = cell.module("references", cfg["reference"])
    generator = cell.module("generators", cell.traffic["generator"])
    seen = serve_window(cell, reference, generator, cell.seconds)
    start, end = seen["start"], seen["end"]
    elapsed = end - start
    tokens, ops = window_tokens(cfg, seen["requests"], start, end)
    finished = seen["finished"]
    wrong = [r for r in finished
             if r.status != "ok" or len(r.tokens) != r.output]

    t0 = time.perf_counter()
    compared = sample(finished, cell.seed, cell.traffic["compared_requests"])
    gaps, _ = served_logit_gaps(cell, reference, compared)
    reference_s = time.perf_counter() - t0
    numbers = {
        "served_logit_gap": (float(max(g.max() for g in gaps))
                             if gaps else None),
        "wrong_length_or_failed": len(wrong),
    }
    spans = [(e - s) for name, s, e in cell.spans
             if name == "engine.step" and start <= s <= end]
    pumps = [(e - s) for name, s, e in cell.spans
             if name == "gateway.pump" and start <= s <= end]
    engine_stats = {k: v for k, v in seen["engine_stats"].items()
                    if isinstance(v, (int, float))}
    print("chipbench: serving diagnostics %s" % json.dumps({
        "requests_finished": len(finished), "tokens_credited": tokens,
        "pump_s": _quantiles(pumps), "engine_step_s": _quantiles(spans),
        "compared_tokens": int(sum(len(g) for g in gaps)),
        "reference_s": reference_s, "engine": engine_stats}),
        file=sys.stderr, flush=True)
    return {
        "attempted": len(finished), "failed": len(wrong),
        "end_to_end": {"serve_tokens_per_s": tokens / elapsed},
        "observed": {"elapsed_s": elapsed, "tokens": tokens,
                     "model_ops": ops,
                     "compared_tokens": int(sum(len(g) for g in gaps)),
                     "engine_stats": engine_stats},
        "checks": compare.checks(numbers, cfg["correct"]["limits"]),
        "numbers": numbers, "reference_s": reference_s,
    }


def readings(cell, seed, sides):
    """The compared numbers of one seed: one short window of the cell's
    own load, then, over the same sample of its finished requests, each
    of ``sides``: "program" (the served tokens), "control" (the token the
    reference at the configuration's lower precision puts first, at
    every position of the same prompts and tokens), "altered_token" (the
    served streams with one token of the longest request replaced by
    another id: the fault of a token altered where it is produced).  For
    setting limits (PERF.md) and for the tests; a benchmark run never
    calls it."""
    cell.seed = seed
    cfg = cell.config
    reference = cell.module("references", cfg["reference"])
    generator = cell.module("generators", cell.traffic["generator"])
    seen = serve_window(cell, reference, generator, cell.seconds)
    finished = seen["finished"]
    compared = sample(finished, seed, cell.traffic["compared_requests"])
    gaps, logits = served_logit_gaps(cell, reference, compared)
    everything = np.concatenate(gaps)
    wrong = [r for r in finished
             if r.status != "ok" or len(r.tokens) != r.output]
    out = {}
    for side in sides:
        if side == "program":
            numbers = {"served_logit_gap": float(everything.max()),
                       "wrong_length_or_failed": len(wrong)}
            where = {"finished": len(finished),
                     "compared_tokens": int(everything.size),
                     "gap_p50": float(np.median(everything)),
                     "gap_p99": float(np.quantile(everything, 0.99)),
                     "tokens_off_the_best": int((everything > 0).sum())}
        elif side == "control":
            control, _ = served_logit_gaps(
                cell, reference, compared,
                matmul=cfg["correct"]["control"], against=logits)
            control = np.concatenate(control)
            numbers = {"served_logit_gap": float(control.max())}
            where = {"gap_p50": float(np.median(control)),
                     "gap_p99": float(np.quantile(control, 0.99)),
                     "tokens_off_the_best": int((control > 0).sum())}
        elif side == "altered_token":
            rng = np.random.default_rng([int(seed), 5])
            at = int(rng.integers(len(gaps[0])))
            other = (compared[0].tokens[at] + 1
                     + int(rng.integers(cfg["vocab_size"] - 1))) \
                % cfg["vocab_size"]
            judge = logits[0][at]
            numbers = {"served_logit_gap": float(judge.max() - judge[other])}
            where = {"position": at}
        else:
            raise ValueError("unknown side %r" % side)
        out[side] = {"numbers": numbers, "where": where}
    return out
