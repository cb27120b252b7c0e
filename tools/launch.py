#!/usr/bin/env python
"""Distributed job launcher (parity: tools/launch.py → dmlc_tracker).

The reference forked worker/server/scheduler processes wired by DMLC_* env
(ssh/mpi/yarn/local trackers). The TPU-native equivalent launches one
process per host with jax.distributed coordinates; `--launcher local`
forks N processes on localhost with a shared coordinator — the same trick
the reference's local tracker used, and what tests/nightly-style
multi-process CI runs use (SURVEY §4 fixture 5).

A chip belongs to one process at a time, and local workers start with
identical environments: on a host with TPU chips every one of N > 1
workers would reach for every chip — N workers a chip — and all but the
first would fail or hang.  Giving each its own chip AND joining them
into one jax.distributed world needs libtpu's multi-process slice
settings, which nothing here has run with yet; one process drives every
chip of a host (``make_mesh`` over ``jax.devices()``).  So there
`--launcher local` with N > 1 is for CPU rehearsal only and is refused
unless JAX_PLATFORMS=cpu.  The launcher itself never imports JAX — that
would take the chips from its own workers.

Usage:
    python tools/launch.py -n 4 --launcher local python train.py ...
"""

import argparse
import glob
import os
import signal
import subprocess
import sys

def tpu_chips(dev="/dev"):
    """Accelerator chips this host exposes, counted from its device
    nodes without JAX: one ``/dev/accel<N>`` or one VFIO group
    ``/dev/vfio/<N>`` per chip, whichever driver the host runs."""
    return (len(glob.glob(os.path.join(dev, "accel[0-9]*")))
            + len(glob.glob(os.path.join(dev, "vfio", "[0-9]*"))))


def main():
    parser = argparse.ArgumentParser(description="Launch a distributed job")
    parser.add_argument("-n", "--num-workers", type=int, required=True,
                        help="number of worker processes")
    parser.add_argument("-s", "--num-servers", type=int, default=0,
                        help="accepted for parity; mxtpu has no parameter "
                        "servers (collectives replace them)")
    parser.add_argument("--launcher", type=str, default="local",
                        choices=["local", "ssh", "mpi"],
                        help="local: fork on this host; ssh/mpi: print the "
                        "per-host command (TPU pods launch one process per "
                        "host via their own runtime)")
    parser.add_argument("-H", "--hostfile", type=str, default=None)
    parser.add_argument("--port", type=int, default=9357)
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    if args.num_servers:
        print("note: -s/--num-servers ignored — mxtpu replaces parameter "
              "servers with XLA collectives (dist_tpu_sync)")
    if not args.command:
        parser.error("no command given")

    if args.launcher != "local":
        print("Run on each host (process_id = host index):")
        for i in range(args.num_workers):
            print("  DMLC_PS_ROOT_URI=<host0-addr> DMLC_PS_ROOT_PORT=%d "
                  "DMLC_NUM_WORKER=%d DMLC_WORKER_ID=%d %s" % (
                      args.port, args.num_workers, i,
                      " ".join(args.command)))
        return

    chips = tpu_chips()
    if args.num_workers > 1 and chips \
            and os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.exit(
            "launch.py: refusing %d local workers on a host with %d "
            "accelerator chip(s): they would start with identical "
            "environments and all reach for the same chips, and a chip "
            "belongs to one process.  Run ONE process — it drives every "
            "chip of the host — or set JAX_PLATFORMS=cpu for a CPU "
            "rehearsal."
            % (args.num_workers, chips))

    procs = []
    try:
        for i in range(args.num_workers):
            env = dict(os.environ)
            env.update({
                "DMLC_PS_ROOT_URI": "127.0.0.1",
                "DMLC_PS_ROOT_PORT": str(args.port),
                "DMLC_NUM_WORKER": str(args.num_workers),
                "DMLC_WORKER_ID": str(i),
                "DMLC_ROLE": "worker",
            })
            procs.append(subprocess.Popen(args.command, env=env))
        code = 0
        for p in procs:
            p.wait()
            code = code or p.returncode
        sys.exit(code)
    except KeyboardInterrupt:
        for p in procs:
            p.send_signal(signal.SIGTERM)
        sys.exit(1)


if __name__ == "__main__":
    main()
