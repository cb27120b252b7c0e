"""The caller's share of ``setup_s``: the seconds between the kernel's
record of the process's start and the window's opening that lie in no
span of the program's ring.

``cell.setup_s`` is read from ``/proc`` where the window opens, and the
window's own span starts a few lines later on ``time.perf_counter``, the
ring's clock: so [window's start - setup_s, window's start] is set-up on
the ring's clock, to within those lines.  Every span of the ring counts,
clipped to that stretch, overlaps once: ``process.start`` (the
interpreter, and whatever the caller did before it imported the
program), the program's imports, ``block.initialize``, the trainer's
spans, every ``xla.compile``.  What is left is time the caller spent
after the import with the program doing nothing it has a span for: the
reference's seeded weights, reads of the trainer's state, the runner's
own imports, the profiler's start.

Nothing to read without ``process.start`` in the ring (a program from
before it): the stretch before the import would read as the caller's.
"""

from chipbench import xplane


def read(cell, spec, observed, trace):
    ring = cell.module("readers", "program_span")
    spans, window = ring.ring_spans(), ring.window_ns(cell)
    if not spans or window is None or cell.setup_s is None:
        return None
    if not any(s.etype == "process.start" for s in spans):
        return None
    hi = window[0]
    lo = hi - cell.setup_s * 1e9
    covered = xplane.busy_ns(xplane.clip(
        [(s.etype, s.start_ns, s.end_ns - s.start_ns) for s in spans],
        lo, hi))
    return cell.setup_s - covered * ring.SCALE[spec["unit"]]
