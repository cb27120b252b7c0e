"""From the JAX profiler's ``.xplane.pb`` to busy intervals, per-op
durations and idle gaps labelled by what the host was doing.

Read with nothing but JAX (``jax.profiler.ProfileData``).  A TPU trace
has one plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` holds
one event per executed HLO operation (a Pallas kernel is one such event,
named after its custom call); host threads are lines of ``/host:CPU``,
where the harness's own spans appear as ``chipbench.<name>`` annotations
on the same clock.
"""

import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = SPAN_PREFIX + "window"


def find(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return paths[-1]


def load(path):
    """{"devices": {plane: [(name, start_ns, dur_ns)]}, "spans": [...]}:
    every device's executed operations and the harness's host spans."""
    from jax.profiler import ProfileData

    devices, spans = {}, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, float(e.start_ns), float(e.duration_ns))
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


def window_of(trace):
    """(start_ns, end_ns) of the harness's ``window`` span."""
    for name, start, dur in trace["spans"]:
        if name == WINDOW_SPAN:
            return start, start + dur
    raise ValueError("the trace holds no %s span" % WINDOW_SPAN)


def clip(events, lo, hi):
    """The parts of ``events`` that lie inside [lo, hi]."""
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(events):
    """Merged, sorted [start, end] intervals covered by ``events``."""
    merged = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return merged


def busy_ns(events):
    return sum(b - a for a, b in union(events))


def op_totals(events):
    """{op name: summed duration in ns}.  Operations that enclose others
    (a ``while`` around its body) are counted whole, as the trace has
    them: read a total as that op's span, not as exclusive time."""
    totals = {}
    for name, _, dur in events:
        totals[name] = totals.get(name, 0.0) + dur
    return totals


def span_at(spans, t):
    """The innermost harness span open at time ``t`` ("none" if none),
    the ``window`` span itself aside."""
    best = None
    for name, start, dur in spans:
        if name != WINDOW_SPAN and start <= t < start + dur:
            if best is None or dur < best[1]:
                best = (name, dur)
    return best[0][len(SPAN_PREFIX):] if best else "none"


def idle_gaps(events, spans, lo, hi):
    """[(label, seconds)] for each gap between busy intervals in
    [lo, hi], labelled by the harness span open when the gap began."""
    gaps, at = [], lo
    for a, b in union(events) + [[hi, hi]]:
        if a > at:
            gaps.append((span_at(spans, at), (a - at) / 1e9))
        at = max(at, b)
    return gaps


def short(name, limit=120):
    """An event's name is its whole HLO instruction; keep its head (the
    instruction's name, its result's shape and its kind)."""
    return name if len(name) <= limit else name[:limit - 3] + "..."


def summary(trace, top=10):
    """What the result line's ``device`` and ``breakdown`` carry: busy
    seconds averaged over the chips, the traced window's length, the
    ``top`` operations by time (first chip) and the ``top`` longest idle
    gaps summed by label (first chip)."""
    lo, hi = window_of(trace)
    if not trace["devices"]:
        raise ValueError("the trace holds no %s* plane with an %r line"
                         % (DEVICE_PLANE, OPS_LINE))
    clipped = {p: clip(ev, lo, hi) for p, ev in trace["devices"].items()}
    busy = sum(busy_ns(ev) for ev in clipped.values()) / len(clipped)
    first = clipped[sorted(clipped)[0]]
    ops = sorted(op_totals(first).items(), key=lambda kv: -kv[1])[:top]
    by_label = {}
    for label, seconds in idle_gaps(first, trace["spans"], lo, hi):
        by_label[label] = by_label.get(label, 0.0) + seconds
    gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[short(name), ns / 1e9] for name, ns in ops],
        "idle_gaps": [[label, s] for label, s in gaps],
    }
