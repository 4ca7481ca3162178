"""How many steps the host is ahead of the device when the device asks for
work: at each start of the step program on the first chip, the program's
dispatch spans that had ended by then minus the step programs that had
started before; the median over the steady starts inside the window. Both
are counted from the start of the trace, where the device is idle and
nothing is dispatched, so the k-th start runs the k-th dispatch. 1 is a
device that gets each step just in time; under it the device waits for the
host.

Steady: the runner stops dispatching at its deadline and the device then
works off what is queued (16, 15, ..., 1 ahead), which says how the window
ends and nothing of the program. The steps dispatched inside the LAST host
event called ``last`` (the runner's ``bench:dispatch_segment``) are that
drain and are left out; a trace with no such event keeps every start."""
import bisect
import re

from benchmark.harness import stats, trace


def read(rin, span="trainer:dispatch", module=r"^jit_step\(", last=None):
    ev = rin.events
    if ev is None or not ev["devices"]:
        return None
    spans = sorted((s, s + d) for n, s, d, *_ in ev["host"] if n == span)
    if not spans:
        return None
    ended = sorted(e for _s, e in spans)
    first = ev["devices"][sorted(ev["devices"])[0]]
    rx = re.compile(module)
    starts = sorted(s for n, s, _d in first["modules"] if rx.search(n))
    final = [s for n, s, *_ in ev["host"] if n == last]
    if final:     # keep the dispatches begun before the last such event
        begun = [s for s, _e in spans]
        starts = starts[:bisect.bisect_left(begun, max(final))]
    lo, hi = trace.window(ev)
    ahead = [bisect.bisect_right(ended, t) - k
             for k, t in enumerate(starts) if lo <= t < hi]
    return stats.median(ahead) if ahead else None
