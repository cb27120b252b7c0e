"""``kernel_time_share`` for a family one of whose members the trace
does not name: the summed device durations of the events that match any
of ``args.patterns`` or ``args.counted.pattern`` over the device's busy
time in the traced window (first chip).

A Pallas kernel's event carries the kernel's name; a loop that XLA
lowers has only the compiler's (``while.112``) and is told by the shapes
it carries.  So that a loop that merely looks alike is never counted,
and one that has come to look different is not dropped from a number
that still reads, the events that match ``args.counted.pattern`` must
number ``args.counted.per_layer_step`` x the configuration's layers x
the window's steps: otherwise nothing is read, and stderr says what was
found.  No matching event at all: nothing to read.
"""

import re
import sys

from chipbench import xplane


def read(cell, spec, observed, trace):
    args = spec["args"]
    lo, hi = xplane.window_of(trace)
    events = xplane.clip(trace["devices"][sorted(trace["devices"])[0]],
                         lo, hi)
    patterns = [re.compile(p) for p in args["patterns"]]
    counted = re.compile(args["counted"]["pattern"])
    named = [dur for name, _, dur in events
             if any(p.search(name) for p in patterns)]
    told = [dur for name, _, dur in events if counted.search(name)]
    expected = (args["counted"]["per_layer_step"]
                * cell.config["num_hidden_layers"] * observed["steps"])
    if told and len(told) != expected:
        print("chipbench: %s: %d events match the counted pattern, %d "
              "expected (%d a layer and step): not read"
              % (spec["name"], len(told), expected,
                 args["counted"]["per_layer_step"]), file=sys.stderr)
        return None
    spent, busy = sum(named) + sum(told), xplane.busy_ns(events)
    return 100.0 * spent / busy if spent and busy else None
