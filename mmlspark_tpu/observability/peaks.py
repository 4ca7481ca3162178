"""Published peak rates per accelerator: the ONE denominator table.

Every utilization the repo reports (``bench.py``'s ``mfu``) divides by a
row of this table, looked up by the ``device_kind`` string jax reports for
the attached device. A device that is not listed has no peak:
:func:`peaks_for` raises — nothing assumes a v5e.
"""
from __future__ import annotations

from typing import Dict, NamedTuple


class DevicePeaks(NamedTuple):
    bf16_tflops: float   # dense bf16 matmul peak, TFLOP/s per chip
    hbm_gbps: float      # HBM bandwidth, GB/s per chip
    hbm_gb: float        # HBM capacity, GB per chip
    source: str


DEVICE_PEAKS: Dict[str, DevicePeaks] = {
    # jax names a v5e chip "TPU v5 lite"
    "TPU v5 lite": DevicePeaks(
        197.0, 819.0, 16.0, 'Google Cloud documentation, "TPU v5e"'),
}


def peaks_for(device_kind: str) -> DevicePeaks:
    """The table row for ``device_kind``; an unknown device is an error,
    not a default."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a "
            f"sourced row to {__name__}.DEVICE_PEAKS (have "
            f"{sorted(DEVICE_PEAKS)})") from None
