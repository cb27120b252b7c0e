"""Mean duration, in milliseconds, of the harness's host spans named
``args.span`` that began inside the window (traced runs record them
around the program's calls; the program itself is not changed)."""


def read(cell, spec, observed, trace):
    window = [(s, e) for name, s, e in cell.spans if name == "window"]
    if not window:
        return None
    lo, hi = window[-1]
    spans = [e - s for name, s, e in cell.spans
             if name == spec["args"]["span"] and lo <= s <= hi]
    return 1e3 * sum(spans) / len(spans) if spans else None
