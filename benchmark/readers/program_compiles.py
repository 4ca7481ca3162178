"""What a start is made of, from the program's own ledger of what jax
compiled (``mmlspark_tpu/observability/compiles.py``): one row a program
with its trace, lowering and backend seconds, what the persistent cache
said of it, and the span that waited for it. ``None`` where the program
has no such module, or never built ``program``.

The boundary is the program's own: the rows up to and including the FIRST
row of ``program`` (``jit_step``: the step's first build or load) are the
start; what follows is the runner's (the reference's walk, the routing
pass, the scope table's thunk lowering the step again) and moves no
``setup_s``. ``key`` picks the number:

- ``step_trace_s``, ``step_lower_s``, ``step_backend_s``: that row's
  stages (both traces of a loss with aux scalars; XLA's compile, or the
  cache's read and the executable's load);
- ``step_cache_hit``: 1 where that row's outcome is ``hit``, else 0;
- ``misses_to_first_step``: rows with outcome ``miss`` up to that row;
- ``backend_s_to_first_step``: ``backend_s`` summed over the same rows.

The first call in a run prints one ``# startup`` note: every row by its
seconds (name, parent span, the three stages in ms, outcome, and when its
first stage opened, in seconds after the first row's: the order they ran
in), the totals before and after the boundary, and the trainer's two
gauges beside them.
"""
from benchmark.harness.report import note
from benchmark.readers import program_metric

_STAGES = ("trace_s", "lower_s", "backend_s")


def _rows():
    try:
        from mmlspark_tpu.observability import compiles
    except ImportError:             # a program from before the ledger
        return None
    return compiles.rows()


def _totals(rows):
    out = {"programs": len(rows)}
    out.update({s: round(sum(getattr(r, s) for r in rows), 3)
                for s in _STAGES})
    for outcome in ("hit", "miss", "uncached"):
        out[outcome] = sum(r.outcome == outcome for r in rows)
    return out


def startup(rows, program="jit_step"):
    """The numbers the metrics read and the note's totals, or ``None``
    where ``program`` has no row."""
    at = next((i for i, r in enumerate(rows) if r.name == program), None)
    if at is None:
        return None
    step, upto = rows[at], rows[:at + 1]
    return {
        "step_trace_s": step.trace_s, "step_lower_s": step.lower_s,
        "step_backend_s": step.backend_s,
        "step_cache_hit": 1.0 if step.outcome == "hit" else 0.0,
        "misses_to_first_step": float(sum(
            r.outcome == "miss" for r in upto)),
        "backend_s_to_first_step": sum(r.backend_s for r in upto),
        "to_first_step": _totals(upto), "after": _totals(rows[at + 1:]),
        "step": {"start": round(step.start, 3), "parent": step.parent,
                 "outcome": step.outcome,
                 "retrieval_s": round(step.retrieval_s, 3)}}


def _note(rows, found, program):
    by_seconds = sorted(rows, key=lambda r: -r.total_s)
    first = min(r.start for r in rows)
    gauges = {name: program_metric.read(None, name)
              for name in ("trainer.init_s", "trainer.first_step_s")}
    note("startup", program=program, step=found["step"],
         step_stages_s={k: round(found["step_" + k], 3) for k in _STAGES},
         to_first_step=found["to_first_step"], after=found["after"],
         gauges_s={k: v if v is None else round(v, 3)
                   for k, v in gauges.items()},
         rows_name_parent_trace_lower_backend_ms_outcome_at_s=[
             [r.name, r.parent, round(r.trace_s * 1e3, 1),
              round(r.lower_s * 1e3, 1), round(r.backend_s * 1e3, 1),
              r.outcome, round(r.start - first, 2)] for r in by_seconds])


def read(rin, key, program="jit_step"):
    if not hasattr(rin, "startup"):
        rows = _rows()
        rin.startup = startup(rows, program) if rows else None
        if rin.startup:
            _note(rows, rin.startup, program)
    return rin.startup and rin.startup[key]
