"""The program's own spans, from the profiler's trace: with
``observability.annotate`` set (every ``--trace 1`` run) each span of the
program opens a ``jax.profiler.TraceAnnotation`` under its name
(``trainer:dispatch``, ``input:put``, ...), so it lies in
``rin.events["host"]`` as ``[name, start_ns, duration_ns, thread]`` on the
clock of the device's operations. Read here, in milliseconds, from the
spans of one name that lie wholly inside the window (a span cut by the
window's edge has no duration of its own): their median."""
from benchmark.harness import stats, trace


def read(rin, span):
    if rin.events is None:
        return None
    lo, hi = trace.window(rin.events)
    ms = [d / 1e6 for n, s, d, *_ in rin.events["host"]
          if n == span and s >= lo and s + d <= hi]
    return stats.median(ms) if ms else None
