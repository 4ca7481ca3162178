"""Wall-time spans with a context-propagated parent stack.

``span(kind, detail)`` times a region and emits one ``"span"`` event to the
event log on exit, carrying its ``span_id``, its parent's id/name, and its
depth — enough to reconstruct the full nesting tree offline
(``mmlspark-tpu report``). The stack lives in a ``contextvars.ContextVar``,
so threads and async tasks each see their own ancestry instead of racing a
global.

Cost discipline: when neither ``observability.events_path`` nor
``observability.annotate`` is set, :func:`span` returns a shared no-op
context manager BEFORE any string is built — the name is assembled from
``(kind, detail)`` only on the enabled path, which is why call sites pass
the two pieces instead of a preformatted f-string. With
``observability.annotate`` on, the span also opens a
``jax.profiler.TraceAnnotation`` so the same names line up in
TensorBoard/Perfetto timelines (via the failure-safe
``utils.profiling.annotate``).

Spans that fire once per step or per batch go through :func:`hot_spans`,
not :func:`span`: live only with ``observability.annotate`` or the event
log on, never for the flight recorder alone (a span per step would evict
the incident timeline the ring exists for within seconds). A call site
calls :func:`hot_spans` once, outside its loop, and holds what it returns:
``None`` (it then enters :data:`NOOP`: one boolean test, nothing
allocated) or the span constructor, which tests no gate again.
"""
from __future__ import annotations

import contextvars
import itertools
import os
import threading
from typing import Any, Callable, Optional, Tuple

from mmlspark_tpu.observability import events
from mmlspark_tpu.utils import config

# (name, span_id) ancestry for the current context; () at the root
_STACK: contextvars.ContextVar[Tuple[Tuple[str, int], ...]] = \
    contextvars.ContextVar("mmlspark_tpu_span_stack", default=())
_ids = itertools.count(1)
_ids_lock = threading.Lock()


def next_span_id() -> int:
    """Allocate a span id from the process counter. Span ids are unique
    only WITHIN a process — every span event therefore carries ``pid``,
    and consumers (report, trace export) key on ``(pid, span_id)`` so
    multi-host/merged logs never collide. Used by the tail-sampling path
    in ``serve/`` to mint ids for retroactively-emitted spans without
    colliding with live ones."""
    with _ids_lock:
        return next(_ids)


class _NoopSpan:
    """Shared disabled-path singleton: zero allocation per use."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False

    def drop(self) -> None:
        pass


_NOOP = _NoopSpan()
NOOP = _NOOP  # what a hot call site enters when its resolved gate is off


class _Span:
    __slots__ = ("name", "attrs", "span_id", "_token", "_start_wall",
                 "_start_perf", "_parent", "_depth", "_annotation",
                 "_dropped")

    def __init__(self, name: str, attrs: dict, annotate: bool):
        self.name = name
        self.attrs = attrs
        self.span_id = next_span_id()
        self._annotation = None
        self._dropped = False
        if annotate:
            from mmlspark_tpu.utils.profiling import annotate as _annotate
            self._annotation = _annotate(name)

    def __enter__(self) -> "_Span":
        stack = _STACK.get()
        self._parent = stack[-1] if stack else None
        self._depth = len(stack)
        self._token = _STACK.set(stack + ((self.name, self.span_id),))
        self._start_wall = events.wall()
        self._start_perf = events.perf()
        if self._annotation is not None:
            self._annotation.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        dur = events.perf() - self._start_perf
        _STACK.reset(self._token)
        if self._dropped:
            return False
        fields = {
            "span_id": self.span_id,
            "pid": os.getpid(),
            "parent_id": self._parent[1] if self._parent else None,
            "parent": self._parent[0] if self._parent else "",
            "depth": self._depth,
            "start": round(self._start_wall, 6),
            "dur_s": round(dur, 9),
        }
        if exc_type is not None:
            fields["error"] = exc_type.__name__
        if self.attrs:
            fields["attrs"] = self.attrs
        events.emit("span", self.name, **fields)
        return False

    def drop(self) -> None:
        """Emit no event for this span: the region turned out not to be
        one (a pull that met the end of its stream)."""
        self._dropped = True


def span(kind: str, detail: str = "", **attrs: Any):
    """Context manager timing ``kind[:detail]`` (e.g. ``span("fit",
    "Featurize")`` -> span name ``fit:Featurize``).

    Returns the shared no-op when telemetry is off — callers may hold the
    result but must not rely on span identity. ``attrs`` ride along on the
    emitted event (keep them small and JSON-friendly).
    """
    annotate = bool(config.get("observability.annotate"))
    # recording_enabled, not events_enabled: the flight recorder (on by
    # default) captures spans too, so an incident dump has the timeline —
    # the true-noop fast path needs ALL three sinks off
    if not (annotate or events.recording_enabled()):
        return _NOOP
    return _Span(f"{kind}:{detail}" if detail else kind, attrs, annotate)


def hot_spans() -> Optional[Callable[..., _Span]]:
    """The entry point for regions that fire once per step or per batch.
    Resolves their gate, ``observability.annotate`` or the event log and
    NOT the flight recorder alone, and returns ``None`` when it is off,
    else ``hot_span(kind, detail="", **attrs)``: the constructor of the
    same span :func:`span` gives (name, parent stack, event,
    ``TraceAnnotation``), with the gate's answer bound, so that a span
    costs no second look at the config. Call it once, outside the loop:

        hot = spans.hot_spans()
        for ...:
            with hot("trainer", "dispatch", step=n) if hot else spans.NOOP:
    """
    annotate = bool(config.get("observability.annotate"))
    if not (annotate or events.events_enabled()):
        return None

    def hot_span(kind: str, detail: str = "", **attrs: Any) -> _Span:
        return _Span(f"{kind}:{detail}" if detail else kind, attrs, annotate)

    return hot_span


def current_span() -> Optional[Tuple[str, int]]:
    """(name, span_id) of the innermost open span, or None at the root."""
    stack = _STACK.get()
    return stack[-1] if stack else None


def emit_retroactive(name: str, start: float, dur_s: float,
                     **attrs: Any) -> None:
    """One span event for a region that is over by the time it is known
    to have been one (a program jax compiled): ``start`` on
    ``events.wall()``, a child of the innermost span open HERE, with an
    id of its own. No ``TraceAnnotation``: there is nothing left to open
    one around."""
    stack = _STACK.get()
    parent = stack[-1] if stack else None
    events.emit("span", name, span_id=next_span_id(), pid=os.getpid(),
                parent_id=parent[1] if parent else None,
                parent=parent[0] if parent else "", depth=len(stack),
                start=round(start, 6), dur_s=round(dur_s, 9), attrs=attrs)
