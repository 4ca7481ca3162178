"""A kernel's share of its roofline where its operations and bytes come
from the configuration's reference module (``references/<reference>.py``,
a function ``cost(call) -> (flops, bytes)``): the least time the chip could
take for the work over the time the matching device operations took.

``per="call"``: ``rin.work["kernel_calls"][kernel]`` is the shape of ONE
call and every matching event is one such call (a forward call that the
step runs again to recompute a block is as much a call as the first).
``per="step"``: the shape is one training step's REQUIRED work, set against
all the time the matching operations took per step program in the window
(``op_ms_per_step``), so recomputed calls count against the share."""
from benchmark.harness import costs, spec, trace
from benchmark.readers import op_ms_per_step


def read(rin, reference, cost, kernel, pattern, per="call"):
    call = rin.work.get("kernel_calls", {}).get(kernel)
    if not call or rin.events is None:
        return None
    need = costs.min_seconds(
        getattr(spec.load_plugin("references", reference), cost)(call),
        rin.peaks)
    if per == "step":
        ms = op_ms_per_step.read(rin, pattern)
        return None if not ms else 100.0 * need / (ms / 1e3)
    hits = trace.matching(rin.events, "ops", pattern)
    if not hits:
        return None
    return 100.0 * need * len(hits) / (sum(b - a for _n, a, b in hits) / 1e9)
