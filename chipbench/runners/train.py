"""Runner of training cells: ``SPMDTrainer.step`` on seeded batches.

Set-up builds ONE trainer holding the weights made from ``--seed``,
drives it through its first ``CHECK_STEPS`` optimizer steps by the
window's own call (``issue``) on the window's own feed, and hands that
same object to the window.  The window issues steps on fresh batches,
never reading a loss, at most ``inflight`` ahead of the device, and ends
in ``block_until_ready`` of the last step's loss.  After it closes and
the device's peak memory is read, the trainer is freed and the plain
reference follows the same first steps, in blocks of rows; ``correct``
compares each step's loss, the first gradient's norm as the optimizer
got it (from Adam's mean after one step) and the norm of the
parameters' change after the steps, by the worst leaf.
"""

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, models

CHECK_STEPS = 3


def issue(trainer, tokens, labels):
    """One optimizer step, as the window calls it.  Returns the loss, on
    the device."""
    import mxtpu as mx

    return trainer.step(mx.nd.array(tokens, dtype="int32"),
                        mx.nd.array(labels, dtype="int32"))._data


def build(cell, reference, seed):
    cfg, train = cell.config, train_settings(cell)
    return models.bert_trainer(cfg, train, reference.init_weights(cfg, seed),
                               cell.devices)


def train_settings(cell):
    return dict(cell.config["train"], seq=cell.traffic["seq"])


def first_steps(cell, reference, generator, trainer, named, seed):
    """Drive ``trainer`` through the compared steps; the readings stay
    on the device until the window has closed."""
    cfg, train = cell.config, train_settings(cell)
    losses, grad = [], None
    for n in range(CHECK_STEPS):
        with cell.span("trainer.step"):
            losses.append(issue(trainer, *generator.train_batch(
                cell.traffic, cfg, train["batch"], seed, n)))
        if n == 0:
            _, mean = models.trainer_state(trainer, named)
            grad = models.leaf_norms(mean)      # (1 - beta1) * |g|
    params, _ = models.trainer_state(trainer, named)
    change = models.change_norms(params, reference.init_weights(cfg, seed))
    return {"loss": losses, "grad": grad, "change": change}


def fetch(readings, beta1):
    """Device readings to host floats."""
    return {"loss": [float(v) for v in readings["loss"]],
            "grad": {k: float(v) / (1.0 - beta1)
                     for k, v in readings["grad"].items()},
            "change": {k: float(v) for k, v in readings["change"].items()}}


def reference_first_steps(cell, reference, generator, seed,
                          matmul="highest", rows=None):
    """The plain reference through the same first steps: float32, whole
    batch, accumulated over blocks of rows.  ``matmul`` and ``rows`` (use
    only these rows of every batch) serve the controls and faults."""
    cfg, train = cell.config, train_settings(cell)
    block = cell.config["correct"]["rows_per_block"]
    w0 = reference.init_weights(cfg, seed)

    @jax.jit
    def block_grad(w, tokens, labels):
        return jax.value_and_grad(
            lambda w_: reference.loss_sum(cfg, w_, tokens, labels, matmul))(w)

    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    scale = jax.jit(lambda a, s: jax.tree_util.tree_map(
        lambda x: x * s, a))
    zeros = jax.tree_util.tree_map(jnp.zeros_like, w0)
    w, state = w0, (zeros, zeros)
    losses, grad = [], None
    for n in range(CHECK_STEPS):
        tokens, labels = generator.train_batch(
            cell.traffic, cfg, train["batch"], seed, n)
        if rows is not None:
            tokens, labels = tokens[rows], labels[rows]
        total, grads = 0.0, None
        for at in range(0, len(tokens), block):
            loss, g = block_grad(w, tokens[at:at + block],
                                 labels[at:at + block])
            total = total + loss
            grads = g if grads is None else add(grads, g)
        count = float(tokens.size)
        grads = scale(grads, 1.0 / count)
        losses.append(float(total) / count)
        if n == 0:
            grad = {k: float(v) for k, v in models.leaf_norms(grads).items()}
        w, state = reference.adam_step(w, grads, state,
                                       train["learning_rate"], n + 1)
    change = {k: float(v) for k, v in models.change_norms(w, w0).items()}
    return {"loss": losses, "grad": grad, "change": change}


def run(cell):
    cfg, train = cell.config, train_settings(cell)
    reference = cell.module("references", cell.config["reference"])
    generator = cell.module("generators", cell.traffic["generator"])
    tokens_per_step = train["batch"] * train["seq"]

    trainer, named = build(cell, reference, cell.seed)
    readings = first_steps(cell, reference, generator, trainer, named,
                           cell.seed)
    jax.block_until_ready(readings)

    inflight = cell.traffic["inflight"]
    pending, losses = [], []
    n = CHECK_STEPS
    with cell.window():
        start = time.perf_counter()
        while time.perf_counter() - start < cell.seconds:
            with cell.span("generator"):
                tokens, labels = generator.train_batch(
                    cell.traffic, cfg, train["batch"], cell.seed, n)
            with cell.span("trainer.step"):
                loss = issue(trainer, tokens, labels)
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

    prog = fetch(readings, reference.BETA1)
    del trainer, named, readings, pending, losses
    gc.collect()

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
                     "batch": train["batch"], "where": where},
        "checks": compare.checks(numbers, cell.config["correct"]["limits"]),
        "numbers": numbers, "where": where, "reference_s": reference_s,
    }


def readings(cell, seed, sides):
    """The compared numbers of one seed with, in the program's place,
    each of ``sides``: "program" (as the configuration states),
    "program:<dtype>" (the program in another parameter type),
    "control" (the reference at the configuration's lower precision),
    "half_batch" (the reference on the first half of every batch's rows,
    the mean taken over them).  For setting limits (PERF.md) and for the
    tests; a benchmark run never calls it."""
    reference = cell.module("references", cell.config["reference"])
    generator = cell.module("generators", cell.traffic["generator"])
    ref = reference_first_steps(cell, reference, generator, seed)
    out = {}
    for side in sides:
        if side.startswith("program"):
            saved = cell.config["train"]
            if ":" in side:
                cell.config["train"] = dict(saved, dtype=side.split(":")[1])
            try:
                trainer, named = build(cell, reference, seed)
                got = fetch(first_steps(cell, reference, generator, trainer,
                                        named, seed), reference.BETA1)
            finally:
                cell.config["train"] = saved
            del trainer, named
            gc.collect()
        elif side == "control":
            got = reference_first_steps(
                cell, reference, generator, seed,
                matmul=cell.config["correct"]["control"])
        elif side == "half_batch":
            half = slice(0, cell.config["train"]["batch"] // 2)
            got = reference_first_steps(cell, reference, generator, seed,
                                        rows=half)
        else:
            raise ValueError("unknown side %r" % side)
        numbers, where = compare.training_numbers(got, ref)
        out[side] = {"numbers": numbers, "where": where}
    return out
