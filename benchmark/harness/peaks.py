"""The chip's published peaks, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 16 GB of HBM2e at 819 GB/s per chip. A device that is
not in this table is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"device kind {device_kind!r} is not in benchmark/harness/peaks.py; "
            "add its row with a source before measuring on it"
        ) from None
