"""The whole training step's share of the chip's peak, for GLM-4.7-Flash:
model operations of the tokens trained in the traced window
(``flops_glm4_moe_lite.train_flops_per_token``: forward and backward,
matrix products only, nothing recomputed, the held experts at the pairs
the window's steps routed to them, the prediction module's products at
the positions the program's ``mtp.positions`` counted) per second, over
chips x peak."""

from chipbench import flops_glm4_moe_lite, peaks


def read(cell, spec, observed, trace):
    if not observed.get("steps"):
        return None
    tokens = observed["steps"] * observed["tokens_per_step"]
    mtp = observed.get("mtp_positions")
    per_token = flops_glm4_moe_lite.train_flops_per_token(
        cell.config, observed["seq"], observed.get("held_pairs_per_token"),
        None if mtp is None else mtp / tokens)
    peak = peaks.peaks_for(cell.devices[0].device_kind)["bf16_flops"]
    return 100.0 * per_token * tokens / observed["elapsed_s"] \
        / (len(cell.devices) * peak)
