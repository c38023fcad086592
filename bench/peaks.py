"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s per chip.

No float32 peak is published for the v5e.  The compute bound of a roofline
therefore uses the bf16 peak: the least time of float32 work is then
understated, so a roofline share of float32 work can read low but never
high.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peak:
    flops_per_s: float      # bf16 matrix peak, operations per second
    hbm_bytes_per_s: float  # HBM bandwidth, bytes per second
    hbm_bytes: int          # HBM capacity
    source: str


PEAKS = {
    "TPU v5 lite": Peak(flops_per_s=197e12, hbm_bytes_per_s=819e9,
                        hbm_bytes=16 * 10**9,
                        source='Google Cloud documentation, "TPU v5e"'),
}


def peak_of(device_kind: str) -> Peak:
    """The peaks of ``device_kind``; a device not in the table is an error,
    never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def least_time_s(ops: int, nbytes: int, peak: Peak) -> tuple[float, str]:
    """The least time the chip could take for ``ops`` operations and
    ``nbytes`` bytes of HBM traffic, and which of the two bounds it."""
    t_ops = ops / peak.flops_per_s
    t_bytes = nbytes / peak.hbm_bytes_per_s
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
