"""Published peaks of the chips the benchmark may run on, keyed by
``jax.Device.device_kind``.  An unknown kind is an error, not a default.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(one v5e chip: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s,
1,600 Gbit/s inter-chip interconnect).  JAX reports that chip as
"TPU v5 lite".
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            "chipbench/peaks.py has no published peaks for device kind %r; "
            "add a row with its source" % (device_kind,)) from None
