"""Per-chip peak rates, shared by the benches and the live roofline gauges.

One table, keyed by the EXACT ``device_kind`` string JAX reports (both
spellings ``jax._src.pallas.mosaic.tpu_info`` enumerates per generation).
Source: Google Cloud TPU documentation, the per-chip "peak compute (bf16)"
and "HBM bandwidth" rows of the v4 / v5e / v5p / v6e system-architecture
pages.  A kind that is not here has no peak: the live ``/metrics`` gauges
omit their samples (``device_peaks`` → None), and a measurement refuses to
print a result against a guessed wall (``measurement_peaks`` raises).
"""

from __future__ import annotations

from typing import Optional, Tuple

#: device_kind → (bf16 matmul FLOP/s, HBM bytes/s)
PEAKS = {
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": (197e12, 819e9),   # v5e, as libtpu names it
    "TPU v5e": (197e12, 819e9),
    "TPU v5": (459e12, 2765e9),       # v5p
    "TPU v5p": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),  # v6e (Trillium)
    "TPU v6e": (918e12, 1640e9),
}


def device_peaks(device) -> Optional[Tuple[float, float]]:
    """``(bf16 FLOP/s, HBM bytes/s)`` for a PJRT device, or None when its
    ``device_kind`` is not in the table (callers then omit rooflines)."""
    return PEAKS.get(getattr(device, "device_kind", ""))


def measurement_peaks(device) -> Optional[Tuple[float, float]]:
    """Peaks for a benchmark's utilization numbers.  None on the CPU (a
    rehearsal has no roofline to report); an accelerator that is not in
    the table raises — a result with ``mfu: null`` on a chip nobody looked
    up is a measurement that silently lost its meaning."""
    peaks = device_peaks(device)
    if peaks is None and device.platform != "cpu":
        raise LookupError(
            f"no peak rates for device_kind {device.device_kind!r}: add its "
            "published per-chip figures to tpustack/utils/peaks.py")
    return peaks
