"""Published peaks of the chips the benchmark may run on, keyed by
`device_kind` as JAX reports it. A device that is not here is an error,
never a default: a share of an invented peak means nothing.

Copied from `rocm_apex_tpu/monitor/flops.py` `CHIP_PEAKS` (PR 21) so that
a later PR can change the program's table and not this yardstick.
"""

# Google Cloud documentation, "TPU v5e" system architecture page: 197
# TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip,
# 1,600 Gbit/s inter-chip interconnect per chip.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 200e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e system architecture)",
    },
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def chip_peaks(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a "
            f"row with its source to benchmarks/harness/peaks.py"
        ) from None
