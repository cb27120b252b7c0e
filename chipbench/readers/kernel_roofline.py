"""Roofline share of a family of named kernels in a training step: the
sum over their trace events of the least time the chip could take (the
larger of operations / peak and bytes / bandwidth, from shapes) over the
sum of the events' device durations.

The metric's file gives ``args.family`` ("flash" or "kda") and
``args.kernels``: for each of the family's kernels the regular expression
its trace events' names match.  Shapes come from the configuration's own
keys.  No matching event (a program without the kernel): nothing to read.
"""

import importlib
import re

from chipbench import flops, flops_kimi_linear, peaks, xplane

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}


def _flash(cell, observed):
    cfg = cell.config
    key_dim = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    heads = observed["batch"] * cfg["num_attention_heads"]
    itemsize = ITEMSIZE[cfg["train"]["dtype"]]
    return lambda kernel: flops_kimi_linear.flash_cost(
        kernel, heads, observed["seq"], key_dim, cfg["v_head_dim"], itemsize)


def _kda(cell, observed):
    try:        # by its path: the package exports the function by this name
        kda = importlib.import_module("mxtpu.ops.pallas.kda")
    except ImportError:         # a program without the kernel
        return None
    lin = cell.config["linear_attn_config"]
    # the program takes the heads in groups, a call of the kernel each
    heads = observed["batch"] * kda.heads_per_call(lin["num_heads"])
    def cost(kernel):
        family, _, which = kernel.partition(".")
        count = {"state": flops_kimi_linear.kda_state_cost,
                 "chunk": flops_kimi_linear.kda_chunk_cost}[family]
        return count(which, heads, observed["seq"], lin["head_dim"],
                     lin["head_dim"], kda.CHUNK)
    return cost


def read(cell, spec, observed, trace):
    cost = {"flash": _flash, "kda": _kda}[
        spec["args"]["family"]](cell, observed)
    if cost is None:
        return None
    chip = peaks.peaks_for(cell.devices[0].device_kind)
    lo, hi = xplane.window_of(trace)
    events = xplane.clip(trace["devices"][sorted(trace["devices"])[0]],
                         lo, hi)
    least = spent = 0.0
    for kernel, pattern in spec["args"]["kernels"].items():
        ops, moved = cost(kernel)
        for name, _, dur in events:
            if re.search(pattern, name):
                least += flops.least_time(ops, moved, chip)
                spent += dur / 1e9
    return 100.0 * least / spent if spent else None
