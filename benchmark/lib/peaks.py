"""Published peaks of the chips the benchmark runs on, keyed by jax's
``device_kind``. A device that is not here is an error, not a default.
Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB of
HBM at 819 GB/s per chip)."""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks_for(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add it to benchmark/lib/peaks.py with its source")
    return PEAKS[device_kind]
