"""Seconds before the window in which XLA's back end compiled what the
persistent cache did not hold: the union of the ring's ``xla.compile``
spans of kind ``compile`` whose ``fetched`` is false (``fetched``: a
cache fetch ended inside the span on its thread, so it compiled
nothing; ``mxtpu/observability/trace.py`` ``compile_seen``).

A warm run selects none and reads 0.0, which is a reading: every
compilation said it was fetched.  Nothing to read only where no
``compile`` span before the window carries the field at all (a program
from before the field, or a ring without compilations).
"""


def read(cell, spec, observed, trace):
    ring = cell.module("readers", "program_span")
    spans, window = ring.ring_spans(), ring.window_ns(cell)
    if not spans or window is None:
        return None
    said = [s for s in spans
            if s.etype == "xla.compile" and s.fields.get("kind") == "compile"
            and "fetched" in s.fields and s.end_ns <= window[0]]
    if not said:
        return None
    return ring.covered_ns([s for s in said if not s.fields["fetched"]]) \
        * ring.SCALE[spec["unit"]]
