"""Work that is always waiting: the generator of the throughput cells.

A mix's file gives the parameters; this reads them and makes the work
from the seed.  ``kind: "train_batches"`` — a fresh batch of token ids
and labels for every optimizer step, uniform over the vocabulary, made
on the host; batch ``n`` of a seed is the same in every run.
"""

import numpy as np


def _expect(traffic, kind):
    if traffic["kind"] != kind:
        raise ValueError("this mix is of kind %r, not %r"
                         % (traffic["kind"], kind))


def train_batch(traffic, cfg, rows, seed, n):
    """Batch ``n`` (from 0): (tokens, labels), int32 (rows, seq)."""
    _expect(traffic, "train_batches")
    rng = np.random.default_rng([int(seed), int(n)])
    shape = (rows, traffic["seq"])
    tokens = rng.integers(0, cfg["vocab_size"], shape, dtype=np.int32)
    labels = rng.integers(0, cfg["vocab_size"], shape, dtype=np.int32)
    return tokens, labels


# ----------------------------------------------------------------- serving

def _quantiles(n):
    return (np.arange(n) + 0.5) / n


def lognormal_sizes(spec, n):
    """``n`` sizes on the quantile grid of a lognormal with the given
    median and sigma, clipped: the same multiset in every run."""
    from statistics import NormalDist

    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    sizes = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(sizes), spec["min"], spec["max"]).astype(int)


def uniform_sizes(spec, n):
    sizes = spec["min"] + (spec["max"] - spec["min"]) * _quantiles(n)
    return np.rint(sizes).astype(int)


def sizes(spec, n):
    return {"lognormal": lognormal_sizes,
            "uniform": uniform_sizes}[spec["distribution"]](spec, n)


def request_cycle(traffic, seed, cycle):
    """One cycle of ``traffic["cycle"]`` (prompt length, output length)
    pairs.  The pairs are the mix's own — the quantile grids of its two
    distributions, paired by a permutation fixed in the mix's file — so
    every seed and every cycle offers the same requests' sizes; the seed
    decides the order they come in (and the token ids).  No seed changes
    the amount of work or the shapes the server meets."""
    n = traffic["cycle"]
    pairing = np.random.default_rng(traffic["pairing"]).permutation(n)
    pairs = list(zip(sizes(traffic["prompt"], n).tolist(),
                     sizes(traffic["output"], n)[pairing].tolist()))
    order = np.random.default_rng([int(seed), int(cycle), 1]).permutation(n)
    return [pairs[i] for i in order]


def requests(traffic, cfg, seed):
    """The endless stream of a serving mix: yields (n, prompt, output)
    with prompt an int32 (1, length) array of token ids uniform over
    the vocabulary and output the number of tokens to generate, greedy.
    No two requests within a vocabulary's worth of each other share a
    prefix, not even by chance: the first token counts up from a start
    drawn from the seed (a chance match of one token is already a prefix
    hit to a paged server, and changes the work it does)."""
    _expect(traffic, "requests")
    first = int(np.random.default_rng([int(seed), 6]).integers(
        cfg["vocab_size"]))
    n = cycle = 0
    while True:
        for length, output in request_cycle(traffic, seed, cycle):
            rng = np.random.default_rng([int(seed), n, 2])
            prompt = rng.integers(0, cfg["vocab_size"], (1, length),
                                  dtype=np.int32)
            prompt[0, 0] = (first + n) % cfg["vocab_size"]
            yield n, prompt, output
            n += 1
        cycle += 1
