"""Metrics read from the program's own spans: the ring of finished
boundary spans that ``mxtpu.observability`` keeps in the run's process
(``get_tracer().boundary_spans()``: type, begin tick, parent's tick,
start and end on ``time.perf_counter_ns``, fields), read after the
window.  ``time.perf_counter`` is the clock of ``cell.spans`` too, so
the window's own span says which ring spans ended before it opened and
which began inside it, and the same span in the profiler's trace
(``chipbench.window``) shifts the ring onto the device's timeline.

The metric's file gives ``args``:

    span     the span type selected
    where    "before_window" (ended before it opened) or "in_window"
             (began inside it)
    fields   optional {field: value} every selected span must carry
    measure  what one span counts for: "duration"; "self" (its duration
             less its direct children of the types in ``minus``, "*" for
             all); "descendants" (the durations of the spans of the types
             in ``of`` that lie under it, at any depth); "device_idle"
             (see below)
    reduce   "mean", "sum", or "covered" (the length of the union of the
             spans' intervals: spans that overlap or lie inside one
             another, as a traced jit's inner traces and a cache fetch
             inside its compilation do, are counted once)

The value is in the metric's ``unit`` ("s" or "ms").  A program without
the ring (the parent of the PR that brought it), or a ring with no span
selected: nothing to read.

``measure: "device_idle"`` reads the device trace too: of the seconds
in the window in which no operation ran on the first chip, the share
(%) that falls, after the shift, inside spans of each type, innermost
span first; the largest share is the value and the whole table goes to
standard error.  Gaps under ``short_gap_ms`` are counted apart
("short"): the trace's device clock has been seen a millisecond ahead
of the host's, so a shorter gap may carry its neighbour's label.
"""

import sys

from chipbench import xplane

SCALE = {"s": 1e-9, "ms": 1e-6}


def ring_spans():
    """The program's finished boundary spans, oldest end first; None
    where the program keeps none."""
    try:
        from mxtpu.observability import get_tracer
        return get_tracer().boundary_spans()
    except (ImportError, AttributeError):
        return None


def window_ns(cell):
    """(start, end) of the last window on ``time.perf_counter_ns``."""
    found = [(s, e) for name, s, e in cell.spans if name == "window"]
    if not found:
        return None
    return found[-1][0] * 1e9, found[-1][1] * 1e9


def select(spans, args, lo, hi):
    want = args.get("fields", {})
    out = []
    for s in spans:
        if s.etype != args["span"]:
            continue
        if any(s.fields.get(k) != v for k, v in want.items()):
            continue
        if args["where"] == "before_window" and s.end_ns <= lo:
            out.append(s)
        elif args["where"] == "in_window" and lo <= s.start_ns <= hi:
            out.append(s)
    return out


def children_of(spans):
    """{parent tick: [spans]} over the whole ring."""
    under = {}
    for s in spans:
        if s.parent is not None:
            under.setdefault(s.parent, []).append(s)
    return under


def measure(span, args, under):
    own = span.end_ns - span.start_ns
    kind = args.get("measure", "duration")
    if kind == "duration":
        return own
    if kind == "self":
        minus = args["minus"]
        return own - sum(c.end_ns - c.start_ns
                         for c in under.get(span.tick, ())
                         if minus == "*" or c.etype in minus)
    if kind == "descendants":
        total, todo = 0, list(under.get(span.tick, ()))
        while todo:
            c = todo.pop()
            if c.etype in args["of"]:
                total += c.end_ns - c.start_ns
            if c.tick is not None:
                todo += under.get(c.tick, ())
        return total
    raise ValueError("unknown measure %r" % kind)


def covered_ns(spans):
    return xplane.busy_ns([(s.etype, s.start_ns, s.end_ns - s.start_ns)
                           for s in spans])


def idle_by_type(spans, gaps, short_ns):
    """{type: ns} of the idle ``gaps`` [(start, end)] that lie inside
    ``spans``, each stretch given to the innermost span open there;
    "none" outside every span, "short" for gaps under ``short_ns``."""
    out = {}

    def add(label, ns):
        if ns > 0:
            out[label] = out.get(label, 0.0) + ns

    marks = []                      # (time, order, what, item)
    for s in spans:
        marks.append((s.start_ns, 1, "open", s))
        marks.append((s.end_ns, 0, "close", s))
    for a, b in gaps:
        if b - a < short_ns:
            add("short", b - a)
        else:
            marks.append((a, 2, "idle", None))
            marks.append((b, -1, "busy", None))
    marks.sort(key=lambda m: (m[0], m[1]))
    opened, idle, at = [], False, None
    for t, _, what, s in marks:
        if idle:
            inner = min(opened, key=lambda o: o.end_ns - o.start_ns,
                        default=None)
            add(inner.etype if inner else "none", t - at)
        at = t
        if what == "open":
            opened.append(s)
        elif what == "close":
            opened.remove(s)
        else:
            idle = what == "idle"
    return out


def device_idle(spans, args, trace, lo, hi):
    t_lo, t_hi = xplane.window_of(trace)
    shift = t_lo - lo               # ring clock -> trace clock
    first = sorted(trace["devices"])[0]
    busy = xplane.union(xplane.clip(trace["devices"][first], t_lo, t_hi))
    gaps, at = [], t_lo
    for a, b in busy + [[t_hi, t_hi]]:
        if a > at:
            gaps.append((at - shift, a - shift))
        at = max(at, b)
    total = sum(b - a for a, b in gaps)
    if total <= 0:
        return None
    inside = [s for s in spans
              if s.etype != "xla.compile" and s.end_ns > lo
              and s.start_ns < hi]
    table = idle_by_type(inside, gaps,
                         args.get("short_gap_ms", 2.0) * 1e6)
    shares = {k: 100.0 * v / total for k, v in table.items()}
    print("chipbench: device idle %.6f s of the window; clock skew %+.3f "
          "ms over it (trace window %.6f s, host %.6f s); %% by innermost "
          "program span: %s" % (
              total / 1e9, ((t_hi - t_lo) - (hi - lo)) / 1e6,
              (t_hi - t_lo) / 1e9, (hi - lo) / 1e9,
              ", ".join("%s %.2f" % kv for kv in sorted(
                  shares.items(), key=lambda kv: -kv[1]))),
          file=sys.stderr)
    named = [v for k, v in shares.items() if k not in ("none", "short")]
    return max(named) if named else None


def read(cell, spec, observed, trace):
    spans, window = ring_spans(), window_ns(cell)
    if not spans or window is None:
        return None
    args = spec["args"]
    lo, hi = window
    if args.get("measure") == "device_idle":
        return device_idle(spans, args, trace, lo, hi)
    chosen = select(spans, args, lo, hi)
    if not chosen:
        return None
    if args["reduce"] == "covered":
        return covered_ns(chosen) * SCALE[spec["unit"]]
    under = children_of(spans)
    values = [measure(s, args, under) for s in chosen]
    total = sum(values) * SCALE[spec["unit"]]
    return total / len(values) if args["reduce"] == "mean" else total
