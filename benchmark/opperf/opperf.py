#!/usr/bin/env python
"""Per-operator benchmark harness (parity: benchmark/opperf/opperf.py —
`run_op_benchmarks` walking every registered op with generated inputs,
reporting forward/backward time).

TPU-native differences from the reference: each op is timed three ways —
eager dispatch (the imperative path), jit-compiled (the hybridized path —
this is what a CachedOp/production step sees), and jit value+grad — and
timings block on device completion via a host transfer.  On the chip
``block_until_ready`` is an equally good barrier (re-checked in PR 22: it
returned after the device's 48 ms of work, and the host read after it took
2 ms — PERF.md Findings); the transfer is kept because it is also what a
training loop's loss read does.

Input generation reuses the registry-wide case table that the op sweep
test maintains (tests/test_op_sweep.py CASES — kept complete by its
enforced-coverage test), optionally scaled up with --scale for
bandwidth-meaningful shapes.

Usage:
  python benchmark/opperf/opperf.py                 # all covered ops
  python benchmark/opperf/opperf.py --ops relu dot  # subset
  python benchmark/opperf/opperf.py --scale 32 --output opperf.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def _load_cases():
    sys.path.insert(0, os.path.join(_REPO, "tests"))
    import test_op_sweep as sweep
    return sweep.CASES, sweep.SKIP


def _scale_arrays(args, scale):
    """Tile the case's toy inputs up to benchmark-meaningful sizes by
    repeating along axis 0 (keeps every op's shape constraints valid)."""
    import jax.numpy as jnp

    if scale <= 1:
        return args
    out = []
    for a in args:
        if hasattr(a, "ndim") and a.ndim >= 1:
            out.append(jnp.tile(a, (scale,) + (1,) * (a.ndim - 1)))
        else:
            out.append(a)
    return tuple(out)


def _time(fn, *args, warmup=2, runs=10):
    import numpy as np

    def block(res):
        import jax
        leaf = jax.tree_util.tree_leaves(res)[0]
        np.asarray(leaf)  # host transfer: the reliable device barrier

    for _ in range(warmup):
        block(fn(*args))
    ts = []
    for _ in range(runs):
        t0 = time.perf_counter()
        block(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e3)
    ts.sort()
    return ts[len(ts) // 2]  # median ms


def benchmark_op(name, case, scale=1, runs=10):
    import jax
    import jax.numpy as jnp
    from mxtpu.base import get_op

    spec = get_op(name)
    args = _scale_arrays(case.args(), scale)
    kwargs = dict(case.kwargs)
    fn = lambda *a: spec.fn(*a, **kwargs)

    rec = {"op": name,
           "shapes": [list(getattr(a, "shape", ())) for a in args]}
    rec["eager_ms"] = _time(fn, *args, runs=runs)
    jfn = jax.jit(fn)
    rec["jit_ms"] = _time(jfn, *args, runs=runs)

    if case.grad:
        gidx = case.grad_args or tuple(
            i for i, a in enumerate(args)
            if hasattr(a, "dtype") and jnp.issubdtype(a.dtype,
                                                      jnp.floating))
        if gidx:
            def loss(*a):
                out = fn(*a)
                leaves = jax.tree_util.tree_leaves(out)
                return sum(jnp.sum(l) for l in leaves
                           if jnp.issubdtype(l.dtype, jnp.floating))
            gfn = jax.jit(jax.value_and_grad(loss, argnums=gidx))
            try:
                rec["fwd_bwd_ms"] = _time(gfn, *args, runs=runs)
            except Exception as e:  # non-differentiable in practice
                rec["fwd_bwd_ms"] = None
                rec["bwd_error"] = type(e).__name__
    return rec


def run_op_benchmarks(ops=None, scale=1, runs=10, verbose=True):
    """Benchmark registered ops; returns list of per-op records (parity:
    opperf.run_op_benchmarks)."""
    cases, skip = _load_cases()
    names = ops or sorted(cases)
    results = []
    for name in names:
        if name in skip:
            continue
        case = cases.get(name)
        if case is None:
            if verbose:
                print("skip %s: no case" % name, file=sys.stderr)
            continue
        try:
            rec = benchmark_op(name, case, scale=scale, runs=runs)
        except Exception as e:
            rec = {"op": name, "error": "%s: %s" % (type(e).__name__, e)}
        results.append(rec)
        if verbose and "error" not in rec:
            print("%-28s eager %8.3f ms   jit %8.3f ms   fwd+bwd %s"
                  % (rec["op"], rec["eager_ms"], rec["jit_ms"],
                     ("%8.3f ms" % rec["fwd_bwd_ms"])
                     if rec.get("fwd_bwd_ms") else "       —"))
        elif verbose:
            print("%-28s ERROR %s" % (rec["op"], rec["error"]),
                  file=sys.stderr)
    return results


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--ops", nargs="*", default=None)
    ap.add_argument("--scale", type=int, default=1,
                    help="tile inputs along axis 0 by this factor")
    ap.add_argument("--runs", type=int, default=10)
    ap.add_argument("--output", default=None, help="write JSON here")
    args = ap.parse_args()

    sys.path.insert(0, _REPO)
    results = run_op_benchmarks(args.ops, scale=args.scale, runs=args.runs)
    ok = [r for r in results if "error" not in r]
    print("\n%d ops benchmarked, %d errors"
          % (len(ok), len(results) - len(ok)))
    if args.output:
        with open(args.output, "w") as f:
            json.dump(results, f, indent=1)
        print("wrote", args.output)


if __name__ == "__main__":
    main()
