"""Operations and bytes that a kernel's algorithm needs for one call, from
its shapes alone. Kept with the benchmark: the numerator of every roofline
share. Each function takes the call's shape dict and returns
``(flops, bytes)``."""
from __future__ import annotations

from typing import Any, Dict, Tuple


def normalize(call: Dict[str, Any]) -> Tuple[float, float]:
    """uint8 rows -> (x - mean) * inv_std: one subtract and one multiply
    per element; reads the rows once and the two per-column float32 vectors
    once, and writes ``out_bytes`` per element to HBM. ``out_bytes`` is 0
    where the compiler keeps the result on the chip for its consumer (the
    trainer's step: the call's result carries memory space ``S(1)`` in the
    trace), so that only the traffic the chip cannot avoid is counted."""
    n = float(call["rows"]) * float(call["width"])
    return 2.0 * n, n * (1.0 + float(call["out_bytes"])) \
        + 2.0 * 4.0 * float(call["width"])


def min_seconds(cost: Tuple[float, float], peaks: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    flops, nbytes = cost
    return max(flops / (peaks["bf16_tflops"] * 1e12),
               nbytes / (peaks["hbm_gbps"] * 1e9))
