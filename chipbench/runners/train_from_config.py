"""Runner of a language model's training cells whose configuration
names the program: ``SPMDTrainer.step`` on seeded batches of token ids
and next-token labels, the model's own loss.

It names no model; the configuration's ``program`` does:

    "program": {"module":   the program's module with the model; imported
                            first, so that a program without it ends the
                            run at once, before any weight is made
                "models":   the chipbench module that builds it
                "trainer":  that module's function (cfg, train, weights,
                            selection_bias, devices) -> (trainer, named)
                "counts":   the program's function that reads the model's
                            counters as {name: number}}

``runners/train_lm.py``, ``train_glm.py`` and ``train_keye.py`` name
theirs in their ``build`` and may not be edited, so this file stands
beside them; the window's call, the first steps and their fetching are
``train.py``'s (``issue``, ``first_steps``, ``fetch``), the reference's
first steps, the experts' counters and the freeing of the device
``train_lm.py``'s (``reference_first_steps``, ``held_pairs``,
``release``).  The window and ``readings`` are written here a fourth
time: the fold ROADMAP C11 asks of a benchmark PR has this file as its
target (a configuration that brings its ``program`` needs no runner of
its own), and deletes the other three.

Besides the language-model runner's ``observed`` keys it reports
``counted``: what each of the program's counters rose by over the
window's steps, read before the window opens and after it has closed.
"""

import importlib
import json
import sys
import time

import jax
import numpy as np

from chipbench import compare


def _program(cell):
    # a program without the model ends the run here
    spec = cell.config["program"]
    return spec, importlib.import_module(spec["module"])


def build(cell, reference, seed):
    spec, _ = _program(cell)
    cfg = cell.config
    train = dict(cfg["train"], seq=cell.traffic["seq"])
    build_trainer = getattr(
        importlib.import_module("chipbench." + spec["models"]),
        spec["trainer"])
    return build_trainer(cfg, train, reference.init_weights(cfg, seed),
                         reference.selection_bias(cfg), cell.devices)


def run(cell):
    spec, program = _program(cell)
    counts = getattr(program, spec["counts"])
    base = cell.module("runners", "train")
    lm = cell.module("runners", "train_lm")
    held_pairs = lm.held_pairs      # every live expert layer, this model's
    cfg, train = cell.config, base.train_settings(cell)
    reference = cell.module("references", cell.config["reference"])
    generator = cell.module("generators", cell.traffic["generator"])
    tokens_per_step = train["batch"] * train["seq"]

    trainer, named = build(cell, reference, cell.seed)
    readings = base.first_steps(cell, reference, generator, trainer, named,
                                cell.seed)
    jax.block_until_ready(readings)
    pairs_before, counts_before = held_pairs(), counts()

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
    observed = {"steps": steps, "tokens_per_step": tokens_per_step,
                "elapsed_s": elapsed, "seq": train["seq"],
                "batch": train["batch"], "expert_loads": loads,
                "held_pairs_per_token": sum(map(sum, loads.values()))
                / (layers * max(1, steps) * tokens_per_step),
                "counted": {name: after - counts_before.get(name, 0)
                            for name, after in counts().items()}}
    print("chipbench: observed %s" % json.dumps(observed), file=sys.stderr,
          flush=True)

    prog = base.fetch(readings, reference.BETA1)
    del trainer, named, readings, pending, losses, loss
    lm.release(cell, "the program's state freed")

    t0 = time.perf_counter()
    ref = lm.reference_first_steps(cell, reference, generator, cell.seed)
    reference_s = time.perf_counter() - t0
    numbers, where = compare.training_numbers(prog, ref)
    observed["where"] = where
    return {
        "attempted": steps, "failed": steps - finite,
        "end_to_end": {
            "train_tokens_per_s": finite * tokens_per_step / elapsed},
        "observed": observed,
        "checks": compare.checks(numbers, cell.config["correct"]["limits"]),
        "numbers": numbers, "where": where, "reference_s": reference_s,
    }


def readings(cell, seed, sides):
    """The compared numbers of one seed with, in the program's place,
    each of ``sides``: "program", "control" (the reference at the
    configuration's lower precision) and each fault the configuration
    lists.  For setting limits (PERF.md) and for the tests; a benchmark
    run never calls it."""
    base = cell.module("runners", "train")
    lm = cell.module("runners", "train_lm")
    reference = cell.module("references", cell.config["reference"])
    generator = cell.module("generators", cell.traffic["generator"])
    ref = lm.reference_first_steps(cell, reference, generator, seed)
    lm.release(cell, "reference freed")
    out = {}
    for side in sides:
        if side == "program":
            trainer, named = build(cell, reference, seed)
            got = base.fetch(base.first_steps(cell, reference, generator,
                                              trainer, named, seed),
                             reference.BETA1)
            del trainer, named
        elif side == "control":
            got = lm.reference_first_steps(
                cell, reference, generator, seed,
                matmul=cell.config["correct"]["control"])
        elif side in cell.config["correct"].get("faults", ()):
            sound = cell.config
            cell.config = dict(sound, fault=side)
            try:
                got = lm.reference_first_steps(cell, reference, generator,
                                               seed)
            finally:
                cell.config = sound
        else:
            raise ValueError("unknown side %r" % side)
        lm.release(cell, "side %s freed" % side)
        numbers, where = compare.training_numbers(got, ref)
        out[side] = {"numbers": numbers, "where": where}
    return out
