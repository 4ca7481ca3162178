"""Milliseconds per training step in the device operations whose HLO text
matches a pattern: their total time inside the window (every chip's) over
the step programs in it (every chip's; one cut by the window's edge counts
by the part of it that lies inside, as its operations do)."""
import re

from benchmark.harness import trace


def read(rin, pattern, module=r"^jit_step\("):
    ev = rin.events
    if ev is None:
        return None
    hits = trace.matching(ev, "ops", pattern)
    if not hits:
        return None
    lo, hi = trace.window(ev)
    rx = re.compile(module)
    steps = sum(max(0, min(start + dur, hi) - max(start, lo)) / dur
                for dev in ev["devices"].values()
                for name, start, dur in dev["modules"]
                if dur > 0 and rx.search(name))
    if not steps:
        return None
    return sum(b - a for _n, a, b in hits) / 1e6 / steps
