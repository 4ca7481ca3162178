"""Share of the traced window in which no operation ran on the device."""
from benchmark.harness import trace


def read(rin):
    if rin.events is None:
        return None
    busy = trace.device_busy(rin.events)
    return 100.0 * (1.0 - busy["busy_s"] / busy["window_s"])
