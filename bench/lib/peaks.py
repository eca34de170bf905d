"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A device that is not here is an error: a
share of a peak needs the peak."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud TPU v5e documentation"},
}


def for_kind(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"know {sorted(PEAKS)}") from None
