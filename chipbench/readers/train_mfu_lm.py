"""The whole training step's share of the chip's peak, for the language
models: model operations of the tokens trained in the traced window
(``flops_kimi_linear.train_flops_per_token``: forward and backward,
matrix products only, nothing recomputed, the held experts at the pairs
the window's steps routed to them) per second, over chips x peak."""

from chipbench import flops_kimi_linear, peaks


def read(cell, spec, observed, trace):
    if not observed.get("steps"):
        return None
    pairs = observed.get("held_pairs_per_token")
    per_token = flops_kimi_linear.train_flops_per_token(
        cell.config, observed["seq"], pairs)
    rate = observed["steps"] * observed["tokens_per_step"] \
        / observed["elapsed_s"]
    peak = peaks.peaks_for(cell.devices[0].device_kind)["bf16_flops"]
    return 100.0 * per_token * rate / (len(cell.devices) * peak)
