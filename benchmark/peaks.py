"""Per-chip peak rates, keyed by the exact ``device_kind`` JAX reports.

Source: Google Cloud TPU documentation, "TPU v5e" system architecture page,
per-chip rows: 197 TFLOP/s peak compute in bf16, 819 GB/s HBM bandwidth,
16 GB HBM.  (The int8 peak, 393 TOP/s, is not here: the served int8 path
multiplies in bf16, so nothing divides by it.)  A kind that is not in the
table is an error, never a default.  Copied from ``tpustack/utils/peaks.py``
so that a later change to the program's table cannot move a roofline.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},   # v5e, as libtpu names it
    "TPU v5e": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"no published peaks for device_kind {device_kind!r}: add its "
            "row, with its source, to benchmark/peaks.py") from None
