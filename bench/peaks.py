"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A kind missing here is an error, never a
default: a share of an unknown peak is not a number."""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB of HBM at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes": 16e9,
        "source": "Google Cloud TPU v5e documentation",
    },
}


def peak(kind: str) -> dict:
    """The peak table row of ``kind``; raises ``KeyError`` for a chip that
    is not in the table."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; add "
                       "them to bench/peaks.py with their source") from None
