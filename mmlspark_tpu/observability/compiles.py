"""What jax compiled, program by program: the ledger of a start.

A process that takes a minute and a half to reach its first step spent it
in jax: tracing Python into a jaxpr, lowering the jaxpr to an MLIR module
(the Mosaic kernels are serialised here), and building the executable or
loading it from the persistent cache. jax announces each of the three
stages on ``jax.monitoring`` and says of every program whether the cache
held it. :func:`install` listens, once a process, and keeps one
:class:`Row` a program:

- ``name``: as a device trace shows the program (``jit_step``);
- ``trace_s``, ``lower_s``, ``backend_s``: the three stages' host seconds.
  ``backend_s`` is XLA's compile where ``outcome`` is ``miss`` or
  ``uncached``, and the cache's read and the executable's load where it
  is ``hit`` (``retrieval_s`` is the read alone, as jax reports it);
- ``start``: when the program's first stage opened, on ``events.wall()``;
- ``parent``: the innermost open span when the backend stage ended.

What jax 0.9.0 gives, and what is made of it. A stage is announced at its
start by a scalar event and at its end by a duration event of the same
name and ``fun_name`` (``step`` for a trace, ``jit(step)`` for the other
two), on the thread that compiles. The cache's hit, miss and retrieval
time carry no name and fire between the backend stage's start and its
end. So every thread has a stack of open stages: a hit or a miss belongs
to the thread's open backend stage; a trace that opens inside another
stage (``matmul`` and ``tanh`` inside ``step``, a kernel's body inside a
lowering) is its parent's time and adds nothing; a whole program built
inside another's trace (an eager ``convert_element_type``) gets its own
row, and its lowering and backend seconds are taken off the trace they
interrupted, so that the stages of all rows sum to host time once. A
function traced twice before it is lowered (``jax.eval_shape`` and then
the jitted call; a step whose loss reports scalars, traced again once the
ring has room for them) has both traces in its ``trace_s``.

Each finished row adds to the always-on counters ``compile.programs``,
``compile.trace_s``, ``compile.lower_s``, ``compile.backend_s``,
``compile.cache_hits`` and ``compile.cache_misses`` (jax's own cache for
every jit path; ``compile_cache.*`` are the AOT layer's, the serving
path's own files) and emits one retroactive span event
``compile:<name>`` whose parent is the open span, so that the flight
recorder, the event log and ``mmlspark-tpu report`` show a program under
the ``trainer:first_step`` that waited for it. The listeners fire on
compile events only, never per step.
"""
from __future__ import annotations

import re
import threading
from collections import deque
from typing import Deque, Dict, List, NamedTuple, Optional

from mmlspark_tpu.observability import events
from mmlspark_tpu.observability import metrics as obsmetrics
from mmlspark_tpu.observability import spans

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_OUTCOMES = {"/jax/compilation_cache/cache_hits": "hit",
             "/jax/compilation_cache/cache_misses": "miss"}
_STAGES = {_TRACE: "trace", _LOWER: "lower", _BACKEND: "backend"}

MAX_ROWS = 1024     # programs kept; a server compiles a few a bucket
_MAX_OPEN = 64      # names traced, or lowered, and not built yet, a thread

_API_CALL = re.compile(r"^(\w+)\((.*)\)$")
_NOT_IN_A_MODULE_NAME = re.compile(r"[^\w.-]")


class Row(NamedTuple):
    name: str
    start: float
    trace_s: float
    lower_s: float
    backend_s: float
    outcome: str            # "hit" | "miss" | "uncached"
    retrieval_s: float
    parent: str             # the innermost open span's name, "" at the root

    @property
    def total_s(self) -> float:
        return self.trace_s + self.lower_s + self.backend_s


class _Stage:
    __slots__ = ("kind", "fun_name", "start", "others_s", "outcome",
                 "retrieval_s")

    def __init__(self, kind: str, fun_name: str, start: float):
        self.kind, self.fun_name, self.start = kind, fun_name, start
        self.others_s = 0.0     # other programs' stages inside this one
        self.outcome = "uncached"
        self.retrieval_s = 0.0


class _Thread(threading.local):
    def __init__(self):
        self.stack: List[_Stage] = []
        # fun_name -> [seconds, first start] of traces not lowered yet
        self.traced: Dict[str, List[float]] = {}
        # fun_name -> (start, trace_s, lower_s) of modules not built yet
        self.lowered: Dict[str, tuple] = {}


_lock = threading.Lock()
_installed = False
_rows: Deque[Row] = deque(maxlen=MAX_ROWS)
_thread = _Thread()


def program_name(fun_name: str) -> str:
    """``jit(step)`` -> ``jit_step``, ``jit(<lambda>)`` -> ``jit__lambda_``:
    the module's name as jax makes it and a device trace shows it."""
    m = _API_CALL.match(fun_name)
    if m:
        fun_name = f"{m.group(1)}_{m.group(2)}"
    return _NOT_IN_A_MODULE_NAME.sub("_", fun_name)


def _bound(names: dict) -> None:
    while len(names) > _MAX_OPEN:     # traced, never lowered: oldest out
        del names[next(iter(names))]


def _on_start(event: str, _value, fun_name: str = "", **_kw) -> None:
    kind = _STAGES.get(event)
    if kind is None:
        return
    stack = _thread.stack
    # a trace inside another stage is its parent's time: no clock read
    nested = kind == "trace" and stack
    stack.append(_Stage(kind, fun_name, 0.0 if nested else events.wall()))


def _close(stack: List[_Stage], kind: str, fun_name: str,
           secs: float) -> _Stage:
    """Pop the open stage this duration ends, and whatever was left open
    above it; a stage whose start nobody heard starts ``secs`` ago."""
    for i in range(len(stack) - 1, -1, -1):
        if stack[i].kind == kind and stack[i].fun_name == fun_name:
            stage = stack[i]
            del stack[i:]
            return stage
    return _Stage(kind, fun_name, events.wall() - secs)


def _on_duration(event: str, secs: float, fun_name: str = "",
                 **_kw) -> None:
    t = _thread
    if event == _RETRIEVAL:
        if t.stack and t.stack[-1].kind == "backend":
            t.stack[-1].retrieval_s += secs
        return
    kind = _STAGES.get(event)
    if kind is None:
        return
    stage = _close(t.stack, kind, fun_name, secs)
    own = max(0.0, secs - stage.others_s)
    if kind == "trace":
        if t.stack:                   # nested: the parent's time
            t.stack[-1].others_s += stage.others_s
            return
        entry = t.traced.setdefault(fun_name, [0.0, stage.start])
        entry[0] += own
        _bound(t.traced)
        return
    if t.stack:                       # a program built inside a stage
        t.stack[-1].others_s += secs
    if kind == "lower":
        m = _API_CALL.match(fun_name)
        trace_s, start = t.traced.pop(m.group(2) if m else fun_name,
                                      (0.0, stage.start))
        t.lowered[fun_name] = (start, trace_s, own)
        _bound(t.lowered)
        return
    start, trace_s, lower_s = t.lowered.pop(fun_name,
                                            (stage.start, 0.0, 0.0))
    parent = spans.current_span()
    _finish(Row(program_name(fun_name), start, trace_s, lower_s, own,
                stage.outcome, stage.retrieval_s,
                parent[0] if parent else ""))


def _on_event(event: str, **_kw) -> None:
    outcome = _OUTCOMES.get(event)
    stack = _thread.stack
    if outcome and stack and stack[-1].kind == "backend":
        stack[-1].outcome = outcome


def _finish(row: Row) -> None:
    with _lock:
        _rows.append(row)
    obsmetrics.counter("compile.programs").inc()
    obsmetrics.counter("compile.trace_s").inc(row.trace_s)
    obsmetrics.counter("compile.lower_s").inc(row.lower_s)
    obsmetrics.counter("compile.backend_s").inc(row.backend_s)
    if row.outcome != "uncached":
        obsmetrics.counter("compile.cache_hits" if row.outcome == "hit"
                           else "compile.cache_misses").inc()
    if events.recording_enabled():
        # retroactive, as serve/'s tail sampling mints its spans: the
        # stages are over, there is no region left to open a span around
        spans.emit_retroactive(
            "compile:" + row.name, row.start, row.total_s,
            trace_s=round(row.trace_s, 6), lower_s=round(row.lower_s, 6),
            outcome=row.outcome)


def install() -> None:
    """Register the listeners on ``jax.monitoring``; idempotent (jax has
    no way to ask who listens, and one to drop every listener: a process
    that calls ``jax.monitoring.clear_event_listeners`` silences this)."""
    global _installed
    with _lock:
        if _installed:
            return
        import jax
        jax.monitoring.register_scalar_listener(_on_start)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _installed = True


def rows() -> List[Row]:
    """Every program kept, in the order their backend stages ended (the
    newest :data:`MAX_ROWS`)."""
    with _lock:
        return list(_rows)


def first(name: str) -> Optional[Row]:
    """The first row of a program's name (``jit_step``): the build or load
    a start waited for, not a later lowering of the same function."""
    with _lock:
        return next((r for r in _rows if r.name == name), None)


def clear() -> None:
    """Forget the rows and this thread's open stages (tests)."""
    with _lock:
        _rows.clear()
    del _thread.stack[:]
    _thread.traced.clear()
    _thread.lowered.clear()
