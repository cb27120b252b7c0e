"""Roofline share of the flash-attention kernels of a training step:
the sum over their trace events of the least time the chip could take
(the larger of operations / peak and bytes / bandwidth, from shapes),
over the sum of the events' device durations.

The metric's file gives ``args.kernels``: for each of the algorithm's
kernels (``fwd``, ``dq``, ``dkv``) the regular expression its trace
events' names match.  No matching event: nothing to read.
"""

import re

from chipbench import flops, peaks, xplane

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}


def read(cell, spec, observed, trace):
    cfg = cell.config
    heads = cfg["num_attention_heads"]
    head_dim = cfg["hidden_size"] // heads
    chip = peaks.peaks_for(cell.devices[0].device_kind)
    lo, hi = xplane.window_of(trace)
    events = xplane.clip(trace["devices"][sorted(trace["devices"])[0]],
                         lo, hi)
    least = spent = 0.0
    for kernel, pattern in spec["args"]["kernels"].items():
        ops, moved = flops.flash_attention_cost(
            kernel, observed["batch"] * heads, observed["seq"],
            observed["seq"], head_dim,
            ITEMSIZE[cell.config["train"]["dtype"]])
        for name, _, dur in events:
            if re.search(pattern, name):
                least += flops.least_time(ops, moved, chip)
                spent += dur / 1e9
    return 100.0 * least / spent if spent else None
