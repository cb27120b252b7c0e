"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / window, averaged over
the chips used."""


def read(cell, spec, observed, trace):
    summary = trace["summary"]      # xplane.summary, made once by the harness
    if summary["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])
