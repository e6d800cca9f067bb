"""Published peaks of one chip, keyed by the ``device_kind`` jax reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
A device that is not in the table is an error, never a default.
"""
PEAKS = {
    "TPU v5 lite": {"bf16_flop_s": 197e12, "hbm_byte_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; add them to PEAKS with a source")
    return PEAKS[device_kind]


def least_time_s(flops: float, nbytes: float, device_kind: str) -> float:
    """The roofline's least time: the larger of compute and memory time."""
    p = peaks(device_kind)
    return max(flops / p["bf16_flop_s"], nbytes / p["hbm_byte_s"])
