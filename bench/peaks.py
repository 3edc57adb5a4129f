"""Published per-chip peaks, keyed by ``device_kind`` as JAX reports it.

TPU v5e: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
16 GB of HBM at 819 GB/s. A device that is not in the table is an
error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {"peak_flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
