"""A kernel's share of its roofline, from the device trace: the least time
the chip could take for the traced calls (their shapes through
``harness/costs.py``) over the time the kernel's events took."""
from benchmark.harness import costs, trace


def read(rin, kernel, pattern, cost):
    """``rin.work["kernel_calls"][kernel]`` is the one shape every traced
    call of the kernel has; each matching event is one such call."""
    call = rin.work.get("kernel_calls", {}).get(kernel)
    if not call or rin.events is None:
        return None
    hits = trace.matching(rin.events, "ops", pattern)
    if not hits:
        return None
    measured = sum(b - a for _n, a, b in hits) / 1e9
    need = len(hits) * costs.min_seconds(getattr(costs, cost)(call),
                                         rin.peaks)
    return 100.0 * need / measured
