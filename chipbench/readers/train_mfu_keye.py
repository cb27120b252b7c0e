"""The whole training step's share of the chip's peak, for Keye-VL-2.0's
language model: model operations of the tokens trained in the traced
window (``flops_keye_vl.train_flops_per_token``: forward and backward,
matrix products only, nothing recomputed, attention at the pairs the
program's ``dsa.selected_pairs`` counted in the window, the indexer's
scores at the causal pairs, the held experts at the pairs the window's
steps routed to them) per second, over chips x peak."""

from chipbench import flops_keye_vl, peaks


def read(cell, spec, observed, trace):
    if not observed.get("steps"):
        return None
    tokens = observed["steps"] * observed["tokens_per_step"]
    per_token = flops_keye_vl.train_flops_per_token(
        cell.config, observed["seq"], observed.get("held_pairs_per_token"),
        observed.get("kept_pairs_per_token"))
    peak = peaks.peaks_for(cell.devices[0].device_kind)["bf16_flops"]
    return 100.0 * per_token * tokens / observed["elapsed_s"] \
        / (len(cell.devices) * peak)
