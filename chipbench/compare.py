"""The arithmetic of ``correct``: how far the program's readings lie
from the plain reference's.  Limits are data (the configuration's file);
nothing here knows a model.
"""

import statistics


def relative_gap(got, want):
    return abs(got - want) / abs(want)


def worst_leaf_gap(got, want, keep=None):
    """The worst leaf's gap between two norms — the program's and the
    reference's, not the norm of their difference — measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some leaves' norms are all but zero).  ``keep`` names the
    leaves compared (default all).  Returns (gap, leaf)."""
    names = sorted(want if keep is None else keep)
    median = statistics.median(want[n] for n in names)
    worst, where = 0.0, None
    for n in names:
        gap = abs(got[n] - want[n]) / max(want[n], median)
        if not gap <= worst:            # a NaN gap is the worst
            worst, where = gap, n
    return worst, where


def moved_leaves(ref_grad_norms, floor=1e-3):
    """Leaves whose reference gradient is at least ``floor`` of the
    median leaf's: the others (a key's bias under softmax) are nought to
    rounding and move under Adam by round-off alone, so their change is
    not compared.  A rule on the reference's gradient, not on names."""
    median = statistics.median(ref_grad_norms.values())
    return [n for n, g in ref_grad_norms.items() if g >= floor * median]


def training_numbers(prog, ref):
    """{name: value} from two sets of readings of the first steps:
    ``loss`` (one per step), ``grad`` ({leaf: norm} of the first
    gradient), ``change`` ({leaf: norm} of the parameters' change)."""
    numbers = {}
    for i, (got, want) in enumerate(zip(prog["loss"], ref["loss"])):
        numbers["loss_gap_step%d" % (i + 1)] = relative_gap(got, want)
    numbers["grad_norm_gap"], grad_leaf = worst_leaf_gap(
        prog["grad"], ref["grad"])
    moved = moved_leaves(ref["grad"])
    numbers["change_norm_gap"], change_leaf = worst_leaf_gap(
        prog["change"], ref["change"], keep=moved)
    return numbers, {"grad_norm_gap": grad_leaf,
                     "change_norm_gap": change_leaf,
                     "leaves_compared_for_change": len(moved),
                     "leaves": len(ref["grad"])}


def checks(numbers, limits):
    """[{"name", "value", "limit"}] for every number that has a limit."""
    return [{"name": name, "value": numbers.get(name), "limit": limit}
            for name, limit in limits.items()]
