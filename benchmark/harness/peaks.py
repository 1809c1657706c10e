"""The chips' published peaks, keyed by ``device_kind`` as JAX reports it.

One table for every roofline and utilization the benchmark prints. A device
that is not in it is an error, never a default.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e" system architecture: 197 TFLOP/s
    # bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, a chip.
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
    "TPU v5e": {"bf16_flops": 197e12, "int8_ops": 393e12,
                "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}. Add a row with its source.")
    return PEAKS[device_kind]


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak operations per second and bytes over peak bytes per second."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
