"""``python -m chipbench.readings --workload <name> --seeds 12``: the
numbers ``correct`` compares, read over many seeds in one process, with
the program in its place and with each control or fault in its place
(the runner's ``readings``).  The limits in a configuration's file are
set from these (PERF.md); a benchmark run never calls this.

Prints one JSON line per seed and a last line of summaries, and keeps
them in ``chiprun_out/readings-<workload>.json``.
"""

import argparse
import json
import os
import sys

from chipbench import harness


def main(argv=None):
    parser = argparse.ArgumentParser(prog="python -m chipbench.readings")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--first-seed", type=int, default=2 ** 31 - 6)
    parser.add_argument("--seconds", type=float, default=5.0)
    parser.add_argument("--sides", nargs="+", default=["program"])
    parser.add_argument("--other-sides", nargs="*", default=[],
                        help="read on the first --other-seeds seeds only")
    parser.add_argument("--other-seeds", type=int, default=3)
    parser.add_argument("--base", default=harness.HERE)
    parser.add_argument("--cpu", action="store_true",
                        help="rehearsal: do not look for a chip")
    args = parser.parse_args(argv)

    import jax

    bench = harness.load_json(
        harness.ROOT if args.base == harness.HERE else args.base,
        "BENCHMARK.json")
    entry = next(w for w in bench["workloads"] if w["name"] == args.workload)
    config = harness.load_json(args.base, "configs",
                               entry["config"] + ".json")
    traffic = harness.load_json(args.base, "traffic",
                                entry["traffic"] + ".json")
    devices = (jax.devices()[:entry["chips"]] if args.cpu
               else harness.find_chips(entry["chips"]))
    if not args.cpu:
        harness.enable_compile_cache()
    cell = harness.Cell(args.workload, entry, config, traffic, args.base, 0,
                        args.seconds, False, devices)
    runner = cell.module("runners", config["runner"])
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 1009 * i
        sides = args.sides + (args.other_sides if i < args.other_seeds
                              else [])
        row = {"seed": seed, "sides": runner.readings(cell, seed, sides)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for row in rows:
        for side, got in row["sides"].items():
            for name, value in got["numbers"].items():
                summary.setdefault(side, {}).setdefault(name, []).append(value)
    summary = {side: {name: {"min": min(v), "max": max(v), "n": len(v)}
                      for name, v in numbers.items()}
               for side, numbers in summary.items()}
    print(json.dumps({"summary": summary}), flush=True)
    out = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "readings-%s.json" % args.workload),
              "w") as f:
        json.dump({"rows": rows, "summary": summary}, f, indent=1)


if __name__ == "__main__":
    sys.exit(main())
