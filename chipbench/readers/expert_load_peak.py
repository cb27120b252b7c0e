"""How unevenly the window's tokens fell on the experts this share
holds: over the window's steps, the busiest held expert's pairs over
the held experts' mean, in the layer where that is worst.  1 is an even
load.  From the program's ``moe`` counters (``ExpertShare.load_sum``),
read before and after the window by the runner."""


def read(cell, spec, observed, trace):
    loads = observed.get("expert_loads")
    if not loads:
        return None
    worst = None
    for held in loads.values():
        mean = sum(held) / len(held)
        if mean > 0:
            worst = max(worst or 0.0, max(held) / mean)
    return worst
