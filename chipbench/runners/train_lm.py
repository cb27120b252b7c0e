"""Runner of language-model training cells: ``SPMDTrainer.step`` on
seeded batches of token ids and next-token labels.

The same set-up, window and comparison as ``runners/train.py`` — its
``issue``, ``first_steps`` and ``fetch`` are used as they are — with a
``build`` of its own: the model comes from ``models_lm.py`` holding the
reference's seeded weights and its frozen selection bias.  Besides the
training runner's ``observed`` keys it reports the experts' load over
the window's steps (the program's ``moe`` counters, read before the
window opens and after it has closed).

``reference_first_steps`` is this file's own, with the readings of
``train.reference_first_steps`` (the same steps, the same numbers): that
one keeps the starting weights, the weights, a tree of zeros, the
gradients and their scaled copy on the device at once and loads its
program after them, which at 603 M parameters (2.4 GB a tree) a chip
does not hold — on the v5e the program's load then found no room for its
temporaries (PERF.md, PR 29).  Here the program is compiled and loaded
first, while the device is empty, the starting weights stay on the host,
and every tree is given up to the one that replaces it.

``readings`` offers, in the program's place: "program", "control" (the
reference at the configuration's lower precision) and each fault the
configuration lists under ``correct.faults`` (the reference with that
part of the mathematics broken).  ``half_batch`` has no meaning at one
row.
"""

import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, models, models_lm


def build(cell, reference, seed):
    # a program without the model ends the run here, before any weight
    # is made
    import mxtpu.models.kimi_linear  # noqa: F401

    cfg = cell.config
    train = dict(cfg["train"], seq=cell.traffic["seq"])
    return models_lm.kimi_linear_trainer(
        cfg, train, reference.init_weights(cfg, seed),
        reference.selection_bias(cfg), cell.devices)


def held_pairs():
    """{layer: [pairs each held expert has received so far]} from the
    program's counters."""
    from mxtpu.models.kimi_linear import expert_loads

    return {layer: load["held_sum"]
            for layer, load in expert_loads().items()}


def release(cell, what):
    """Collect what is no longer referenced and unload every compiled
    program (the step program's code, and whatever memory the device
    keeps for a loaded program, would stand beside the reference's 12 GB
    of weights, gradients and Adam state), and say on standard error
    what the device still holds."""
    import sys

    _LOADED.clear()
    gc.collect()
    jax.clear_caches()
    gc.collect()
    stats = cell.devices[0].memory_stats() or {}
    print("chipbench: %s: %s" % (what, ", ".join(
        "%s %.3f GB" % (k, stats[k] / 1e9) for k in (
            "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
            "largest_free_block_bytes", "bytes_limit") if k in stats)),
        file=sys.stderr, flush=True)


_LOADED = {}        # the reference's one loaded program: {key: executable}


def reference_first_steps(cell, reference, generator, seed,
                          matmul="highest"):
    """The plain reference through the same first steps: float32, whole
    batch, accumulated over blocks of rows; {"loss", "grad", "change"} as
    ``train.reference_first_steps`` gives them."""
    base = cell.module("runners", "train")
    cfg, train = cell.config, base.train_settings(cell)
    block = cell.config["correct"]["rows_per_block"]
    batch, seq = train["batch"], train["seq"]
    count = float(batch * seq)
    device = cell.devices[0]
    on_chip = device.platform != "cpu"
    give_up = (lambda *n: n) if on_chip else (lambda *n: ())

    def block_grad(w, tokens, labels):
        return jax.value_and_grad(
            lambda w_: reference.loss_sum(cfg, w_, tokens, labels, matmul))(w)

    w0 = reference.init_weights(cfg, seed)                  # on the host
    shapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in
              w0.items()}
    rows = jax.ShapeDtypeStruct((min(block, batch), seq), np.int32)
    # compiled and loaded before anything else is on the device; kept for
    # the next seed (``readings`` asks many seeds of one process)
    key = (cell.name, matmul, cfg.get("fault"), batch, seq)
    if key not in _LOADED:
        _LOADED.clear()
        _LOADED[key] = jax.jit(block_grad).lower(shapes, rows,
                                                 rows).compile()
    block_grad = _LOADED[key]
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=give_up(0, 1))
    scale = jax.jit(lambda a, s: jax.tree_util.tree_map(
        lambda x: x * s, a), donate_argnums=give_up(0))
    adam = jax.jit(reference.adam_rule, donate_argnums=give_up(0, 1, 2, 3))
    w = jax.device_put(w0, device)
    state = None
    losses, grad = [], None
    for n in range(base.CHECK_STEPS):
        tokens, labels = generator.train_batch(cell.traffic, cfg, batch,
                                               seed, n)
        total, grads = 0.0, None
        for at in range(0, batch, block):
            loss, g = block_grad(w, tokens[at:at + block],
                                 labels[at:at + block])
            total = total + loss
            grads = g if grads is None else add(grads, g)
        grads = scale(grads, jnp.float32(1.0 / count))
        losses.append(float(total) / count)
        if n == 0:
            grad = {k: float(v) for k, v in
                    models.leaf_norms(grads).items()}
        if state is None:
            state = tuple(jax.tree_util.tree_map(jnp.zeros_like, grads)
                          for _ in range(2))
        t = n + 1           # MXNet's Adam: the correction folded in
        rate = train["learning_rate"] * math.sqrt(
            1.0 - reference.BETA2 ** t) / (1.0 - reference.BETA1 ** t)
        w, *state = adam(w, grads, *state, jnp.float32(rate))
    del state, grads
    change = {k: float(v) for k, v in models.change_norms(w, w0).items()}
    return {"loss": losses, "grad": grad, "change": change}


def run(cell):
    base = cell.module("runners", "train")
    cfg, train = cell.config, base.train_settings(cell)
    reference = cell.module("references", cell.config["reference"])
    generator = cell.module("generators", cell.traffic["generator"])
    tokens_per_step = train["batch"] * train["seq"]

    trainer, named = build(cell, reference, cell.seed)
    readings = base.first_steps(cell, reference, generator, trainer, named,
                                cell.seed)
    jax.block_until_ready(readings)
    pairs_before = held_pairs()

    inflight = cell.traffic["inflight"]
    pending, losses = [], []
    n = base.CHECK_STEPS
    with cell.window():
        start = time.perf_counter()
        while time.perf_counter() - start < cell.seconds:
            with cell.span("generator"):
                tokens, labels = generator.train_batch(
                    cell.traffic, cfg, train["batch"], cell.seed, n)
            with cell.span("trainer.step"):
                loss = base.issue(trainer, tokens, labels)
            losses.append(loss)
            pending.append(loss)
            n += 1
            if len(pending) > inflight:
                with cell.span("wait"):
                    pending.pop(0).block_until_ready()
        with cell.span("wait"):
            losses[-1].block_until_ready()
        elapsed = time.perf_counter() - start
    steps = len(losses)
    finite = int(np.isfinite(np.array([float(v) for v in losses])).sum())
    loads = {layer: [after - before for after, before
                     in zip(held, pairs_before[layer])]
             for layer, held in held_pairs().items()}
    layers = max(1, len(loads))
    pairs_per_token = sum(map(sum, loads.values())) \
        / (layers * max(1, steps) * tokens_per_step)

    prog = base.fetch(readings, reference.BETA1)
    del trainer, named, readings, pending, losses, loss
    release(cell, "the program's state freed")

    t0 = time.perf_counter()
    ref = reference_first_steps(cell, reference, generator, cell.seed)
    reference_s = time.perf_counter() - t0
    numbers, where = compare.training_numbers(prog, ref)
    return {
        "attempted": steps, "failed": steps - finite,
        "end_to_end": {
            "train_tokens_per_s": finite * tokens_per_step / elapsed},
        "observed": {"steps": steps, "tokens_per_step": tokens_per_step,
                     "elapsed_s": elapsed, "seq": train["seq"],
                     "batch": train["batch"], "where": where,
                     "expert_loads": loads,
                     "held_pairs_per_token": pairs_per_token},
        "checks": compare.checks(numbers, cell.config["correct"]["limits"]),
        "numbers": numbers, "where": where, "reference_s": reference_s,
    }


def readings(cell, seed, sides):
    """The compared numbers of one seed with, in the program's place,
    each of ``sides``.  For setting limits (PERF.md) and for the tests; a
    benchmark run never calls it."""
    base = cell.module("runners", "train")
    reference = cell.module("references", cell.config["reference"])
    generator = cell.module("generators", cell.traffic["generator"])
    ref = reference_first_steps(cell, reference, generator, seed)
    release(cell, "reference freed")
    out = {}
    for side in sides:
        if side == "program":
            trainer, named = build(cell, reference, seed)
            got = base.fetch(base.first_steps(cell, reference, generator,
                                              trainer, named, seed),
                             reference.BETA1)
            del trainer, named
            release(cell, "side program freed")
        elif side == "control":
            got = reference_first_steps(
                cell, reference, generator, seed,
                matmul=cell.config["correct"]["control"])
            release(cell, "side control freed")
        elif side in cell.config["correct"].get("faults", ()):
            sound = cell.config
            cell.config = dict(sound, fault=side)
            try:
                got = reference_first_steps(cell, reference, generator,
                                            seed)
            finally:
                cell.config = sound
            release(cell, "side %s freed" % side)
        else:
            raise ValueError("unknown side %r" % side)
        numbers, where = compare.training_numbers(got, ref)
        out[side] = {"numbers": numbers, "where": where}
    return out
