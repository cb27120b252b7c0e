"""How much of the device's busy time in the traced window went to a
family of named operations: the summed device durations of the trace
events whose names match any of ``args.patterns`` (regular expressions)
over the union of all the device's operation intervals in the window
(first chip).  No matching event: nothing to read."""

import re

from chipbench import xplane


def read(cell, spec, observed, trace):
    lo, hi = xplane.window_of(trace)
    events = xplane.clip(trace["devices"][sorted(trace["devices"])[0]],
                         lo, hi)
    patterns = [re.compile(p) for p in spec["args"]["patterns"]]
    spent = sum(dur for name, _, dur in events
                if any(p.search(name) for p in patterns))
    busy = xplane.busy_ns(events)
    return 100.0 * spent / busy if spent and busy else None
