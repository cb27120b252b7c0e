"""The served model's share of the chip's peak: model operations (matrix
products of the forward pass, from shapes) of every prompt and generated
token credited to the traced window, per second, over chips x peak."""

from chipbench import peaks


def read(cell, spec, observed, trace):
    if not observed.get("model_ops"):
        return None
    peak = peaks.peaks_for(cell.devices[0].device_kind)["bf16_flops"]
    return 100.0 * observed["model_ops"] / observed["elapsed_s"] \
        / (len(cell.devices) * peak)
