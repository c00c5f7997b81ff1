"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Copied from bench.py ``DEVICE_PEAKS`` (the original is listed for deletion in
PERF.md, Open questions). Source: Google Cloud documentation, "TPU v5e":
197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s. A device that is not in the table
is an error, not a default.
"""

DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197.0e12, "hbm_bytes_per_s": 819.0e9, "hbm_bytes": 16.0e9},
}


def device_peak(kind: str, key: str) -> float:
    if kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peak for device_kind {kind!r}: add the device with "
            "its source before reporting a share of a peak"
        )
    return DEVICE_PEAKS[kind][key]
