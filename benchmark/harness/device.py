"""The device a run measures on: it has to be an accelerator that the
benchmark's own peaks table knows. No run measures on a CPU."""
from __future__ import annotations

import json
import os
from typing import Any, Dict

HERE = os.path.dirname(os.path.abspath(__file__))


class NoAccelerator(RuntimeError):
    """jax found no accelerator, too few chips, or an unknown device."""


def peaks_for(device_kind: str) -> Dict[str, float]:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    row = table.get(device_kind)
    if not isinstance(row, dict):
        raise NoAccelerator(
            f"no published peaks for device kind {device_kind!r} in "
            f"benchmark/harness/peaks.json; add a sourced row")
    return row


def require(chips: int) -> Dict[str, Any]:
    """The devices of this run as jax reports them; raises unless the
    default backend is an accelerator with at least ``chips`` devices."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform == "cpu":
        raise NoAccelerator("jax found no accelerator (platform cpu)")
    if len(devices) < chips:
        raise NoAccelerator(
            f"the cell asks for {chips} chips, jax found {len(devices)}")
    kind = devices[0].device_kind
    return {"platform": platform, "kind": kind, "count": len(devices),
            "peaks": peaks_for(kind), "devices": devices}


def memory_peak_bytes(devices) -> int:
    """Peak bytes on the fullest chip, from ``memory_stats()``: the larger
    of the peak of the buffers in use and the peak reserved for running
    programs. The TPU runtime counts the two apart (a train step's saved
    activations are only under ``peak_bytes_reserved``), and the two peaks
    need not fall on one instant, so they are not added: the reading is a
    floor of the true peak, never above it."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)),
                   int(stats.get("peak_bytes_reserved", 0)))
    return peak
