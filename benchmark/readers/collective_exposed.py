"""Milliseconds per step in collective operations during which no compute
ran on that chip (device trace, averaged over the chips)."""
from benchmark.harness import trace


def read(rin, per="steps_in_window"):
    steps = rin.counters.get(per)
    if rin.events is None or not steps or len(rin.events["devices"]) < 2:
        return None
    return trace.collective_exposed_s(rin.events) * 1e3 / float(steps)
