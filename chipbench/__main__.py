"""``python -m chipbench --workload <name> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell of BENCHMARK.json, on the chip this
process finds.  No TPU is a non-zero exit with no result."""

import argparse
import sys

from chipbench import harness


def main(argv=None):
    parser = argparse.ArgumentParser(prog="python -m chipbench",
                                     description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    harness.run_cell(bench, args.workload, args.seed, args.seconds,
                     args.trace)


if __name__ == "__main__":
    sys.exit(main())
