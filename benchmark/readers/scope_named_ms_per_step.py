"""Milliseconds per training step under ONE ``jax.named_scope`` that
``scope_ms_per_step.PARTS`` does not list, every phase (forward, recomputed
forward, backward), and that time as a share of a roofline.

The rules are ``scope_ms_per_step``'s, by import: self time (a container
keeps what its children leave), an operation is its program's by where it
starts, a child under none of the step's phases takes its nearest
container's path, a path of several joined by ``;`` counts by its first,
wrappers (``jvp(x)``, ``transpose(jvp(x))``) are taken off each component.
What differs is the question: an operation counts when ``scope`` is ANY
component of its path, so an outer scope holds its inner ones
(``short_conv`` holds ``short_conv/gate_conv``). ``None`` where the
program publishes no table or no operation in the window lies under the
scope (a program without the part).

With ``reference``, ``cost`` and ``kernel`` the result is a share in %: the
step's REQUIRED work (``references/<reference>.<cost>`` of
``rin.work["kernel_calls"][kernel]``) at the chip's peaks
(``harness/costs.min_seconds``) over the time under the scope, which holds
whatever the compiler made of the scope's operations, recomputation
included.
"""
import bisect
import re

from benchmark.harness import costs, spec, trace
from benchmark.readers import scope_ms_per_step as rules


def paths(events, table, module=r"^jit_step\("):
    """``({path: ns}, steps)``: the self time of the operations of the
    step programs in the window, by the scope path each counts under."""
    lo, hi = trace.window(events)
    rx = re.compile(module)
    out, steps = {}, 0.0

    def path_of(name):
        return (table.get(rules._instruction(name)) or ("",))[0]

    for dev in events["devices"].values():
        steps += sum(max(0, min(s + d, hi) - max(s, lo)) / d
                     for n, s, d in dev["modules"] if d > 0 and rx.search(n))
        programs = sorted((a, b) for n, a, b in
                          trace._clip(dev["modules"], lo, hi)
                          if rx.search(n))
        starts = [a for a, _b in programs]
        ops = [((name, a), a, b)
               for name, a, b in trace._clip(dev["ops"], lo, hi)]
        segments, inside = rules.self_times(ops)
        for (name, a), ns in segments:
            i = bisect.bisect_right(starts, a) - 1
            if i < 0 or a >= programs[i][1]:
                continue
            path, up = path_of(name), (name, a)
            while rules.phase_of(path) == "unscoped" and up in inside:
                up = inside[up]
                path = path_of(up[0])
            out[path] = out.get(path, 0) + ns
    return out, steps


def read(rin, scope, reference=None, cost=None, kernel=None,
         program="jit_step", module=r"^jit_step\("):
    if rin.events is None:
        return None
    if not hasattr(rin, "scope_paths"):
        table = rules._table(program)
        rin.scope_paths = table and paths(rin.events, table, module)
    if not rin.scope_paths:
        return None
    found, steps = rin.scope_paths
    under = [ns for path, ns in found.items()
             if scope in rules._components(path)]
    if not under or not steps:
        return None
    ms = sum(under) / 1e6 / steps
    if cost is None:
        return ms
    call = rin.work.get("kernel_calls", {}).get(kernel)
    if not call or not ms:
        return None
    need = costs.min_seconds(
        getattr(spec.load_plugin("references", reference), cost)(call),
        rin.peaks)
    return 100.0 * need / (ms / 1e3)
