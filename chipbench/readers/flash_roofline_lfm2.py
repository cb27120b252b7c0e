"""Roofline share of the flash-attention kernels in an LFM2 training
step: the sum over their trace events of the least time the chip could
take (the larger of operations / peak and bytes / bandwidth, at the
model's grouped-query geometry: ``flops_lfm2_moe.flash_cost``) over the
sum of the events' device durations.

``kernel_roofline.py`` prices latent attention's geometry from keys this
configuration has not, ``flash_roofline.py`` has no fused backward and no
causal pairs, ``sparse_attention_roofline.py`` prices the kept pairs of
an indexer, and none may be edited: this reader stands beside them.  The
metric's file gives ``args.kernels``: for each of the kernels (``fwd``,
``bwd``) the regular expression its trace events' names match.  No
matching event (a program without the kernel): nothing to read.
"""

import re

from chipbench import flops, flops_lfm2_moe, peaks, xplane

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}


def read(cell, spec, observed, trace):
    chip = peaks.peaks_for(cell.devices[0].device_kind)
    lo, hi = xplane.window_of(trace)
    events = xplane.clip(trace["devices"][sorted(trace["devices"])[0]],
                         lo, hi)
    least = spent = 0.0
    for kernel, pattern in spec["args"]["kernels"].items():
        ops, moved = flops_lfm2_moe.flash_cost(
            kernel, observed["batch"], cell.config, observed["seq"],
            ITEMSIZE[cell.config["train"]["dtype"]])
        for name, _, dur in events:
            if re.search(pattern, name):
                least += flops.least_time(ops, moved, chip)
                spent += dur / 1e9
    return 100.0 * least / spent if spent else None
