"""The whole training step's share of the chip's peak, for an LFM2
expert decoder: model operations of the tokens trained in the traced
window (``flops_lfm2_moe.train_flops_per_token``: forward and backward,
matrix products only, nothing recomputed, the convolution mixers'
projections at the positions the program's ``conv.positions`` counted in
the window, the held experts at the pairs the window's steps routed to
them) per second, over chips x peak."""

from chipbench import flops_lfm2_moe, peaks


def read(cell, spec, observed, trace):
    if not observed.get("steps"):
        return None
    tokens = observed["steps"] * observed["tokens_per_step"]
    positions = observed.get("counted", {}).get("positions")
    per_token = flops_lfm2_moe.train_flops_per_token(
        cell.config, observed["seq"], observed.get("held_pairs_per_token"),
        None if positions is None else positions / tokens)
    peak = peaks.peaks_for(cell.devices[0].device_kind)["bf16_flops"]
    return 100.0 * per_token * tokens / observed["elapsed_s"] \
        / (len(cell.devices) * peak)
