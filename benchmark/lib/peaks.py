"""Peaks of one chip, keyed by `device_kind` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(cloud.google.com/tpu/docs/v5e): per chip 197 TFLOP/s in bf16 and
16 GB of HBM2e at 819 GB/s. A device that is not in
the table is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}: add it to"
            " benchmark/lib/peaks.py with its source"
        )
    return PEAKS[device_kind]
