"""Roofline share of a family of the indexed attention's kernels in a
training step: the sum over their trace events of the least time the
chip could take (the larger of operations / peak and bytes / bandwidth,
at the model's shapes: ``flops_keye_vl``) over the sum of the events'
device durations.

The metric's file gives ``args.family`` ("attention": the kernels that
attend over the kept keys; "indexer": the indexer's scores, their
backward and the loss's pass) and ``args.kernels``: for each of the
family's kernels the regular expression its trace events' names match.
No matching event (a program without the kernel): nothing to read.
"""

import re

from chipbench import flops, flops_keye_vl, peaks, xplane

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}
COST = {"attention": flops_keye_vl.sparse_attention_cost,
        "indexer": flops_keye_vl.indexer_cost}


def read(cell, spec, observed, trace):
    cost = COST[spec["args"]["family"]]
    chip = peaks.peaks_for(cell.devices[0].device_kind)
    lo, hi = xplane.window_of(trace)
    events = xplane.clip(trace["devices"][sorted(trace["devices"])[0]],
                         lo, hi)
    least = spent = 0.0
    for kernel, pattern in spec["args"]["kernels"].items():
        ops, moved = cost(kernel, observed["batch"], cell.config,
                          observed["seq"],
                          ITEMSIZE[cell.config["train"]["dtype"]])
        for name, _, dur in events:
            if re.search(pattern, name):
                least += flops.least_time(ops, moved, chip)
                spent += dur / 1e9
    return 100.0 * least / spent if spent else None
