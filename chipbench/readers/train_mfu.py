"""The whole training step's share of the chip's peak: model operations
of the tokens trained in the traced window (forward and backward, matrix
products only, nothing recomputed) per second, over chips x peak."""

from chipbench import flops, peaks


def read(cell, spec, observed, trace):
    if not observed.get("steps"):
        return None
    per_token = flops.encoder_train_flops_per_token(cell.config,
                                                    observed["seq"])
    rate = observed["steps"] * observed["tokens_per_step"] \
        / observed["elapsed_s"]
    peak = peaks.peaks_for(cell.devices[0].device_kind)["bf16_flops"]
    return 100.0 * per_token * rate / (len(cell.devices) * peak)
