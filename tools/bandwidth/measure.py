#!/usr/bin/env python
"""All-reduce bandwidth benchmark (parity: the reference's
tools/bandwidth/measure.py, its KVStore all-reduce GB/s).

The reference measured KVStore push+pull bandwidth across GPUs (ps-lite or
NCCL transport). Here the measured path is the compiled XLA all-reduce over
the device mesh (psum riding ICI) — the transport that dist_tpu_sync and
SPMDTrainer actually use. Reports algorithmic bus bandwidth with the
standard 2(n-1)/n ring correction.

Usage:
    python tools/bandwidth/measure.py --size 64 --iters 20
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--size", type=float, default=64.0,
                        help="tensor size in MiB (fp32)")
    parser.add_argument("--iters", type=int, default=20)
    parser.add_argument("--warmup", type=int, default=3)
    parser.add_argument("--devices", type=int, default=0,
                        help="0 = all visible devices")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as onp
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()
    n = args.devices or len(devices)
    devices = devices[:n]
    mesh = Mesh(onp.asarray(devices), ("x",))
    num_elems = int(args.size * (1 << 20) / 4)
    x = jnp.ones((n, num_elems), jnp.float32)
    x = jax.device_put(x, NamedSharding(mesh, P("x")))

    @jax.jit
    def allreduce(x):
        return shard_map(lambda s: jax.lax.psum(s, "x"), mesh=mesh,
                         in_specs=P("x"), out_specs=P("x"))(x)

    for _ in range(args.warmup):
        allreduce(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(args.iters):
        out = allreduce(x)
    out.block_until_ready()
    dt = (time.perf_counter() - t0) / args.iters

    bytes_ = num_elems * 4
    # ring all-reduce moves 2(n-1)/n of the payload per device
    algbw = bytes_ / dt / 1e9
    busbw = algbw * 2 * (n - 1) / n
    print("devices=%d payload=%.1fMiB time=%.3fms algbw=%.2fGB/s "
          "busbw=%.2fGB/s" % (n, args.size, dt * 1e3, algbw, busbw))


if __name__ == "__main__":
    main()
