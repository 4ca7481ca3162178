"""From the profiler's ``.xplane.pb`` to the numbers the readers use.

``Tracer`` captures one window with ``jax.profiler``; :func:`load` reads the
trace with ``jax.profiler.ProfileData`` (nothing but jax) into plain event
lists; the functions below reduce those lists: device busy time, time by
operation, idle gaps labelled by what the host was doing, a kernel's time, and
the part of the collectives during which no compute ran.

All device and host events share the profiler's clock. The window is the
host annotation ``bench:window`` that the tracer opens right after the
profiler has started and closes right before it stops.

An ``Events`` value is JSON-serialisable, so a small recorded trace can be
kept beside the tests (``benchmark/tests/data``).
"""
from __future__ import annotations

import glob
import os
import re
import shutil
from typing import Any, Dict, Iterable, List, Optional, Tuple

WINDOW = "bench:window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute")
# an event: [name, start_ns, duration_ns]
Event = List[Any]
Events = Dict[str, Any]


class Tracer:
    """Captures the measured window of a ``--trace 1`` run; does nothing
    in a ``--trace 0`` run."""

    def __init__(self, enabled: bool, directory: str):
        self.enabled = enabled
        self.directory = directory
        self._annotation = None
        self._started = False

    def start(self) -> None:
        if not self.enabled:
            return
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # no per-call Python events
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self._started = True

    def open(self) -> None:
        """The measured window opens here, on the profiler's clock."""
        if not self.enabled:
            return
        import jax
        self._annotation = jax.profiler.TraceAnnotation(WINDOW)
        self._annotation.__enter__()

    def stop(self) -> None:
        """The window closes and the profiler stops; a second call does
        nothing."""
        if not self.enabled or not self._started:
            return
        import jax
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        self._started = False
        jax.profiler.stop_trace()

    def events(self) -> Optional[Events]:
        if not self.enabled:
            return None
        paths = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")))
        if not paths:
            raise RuntimeError(f"no .xplane.pb under {self.directory}")
        return load(paths[-1])


def _short(text: str) -> str:
    """An operation's HLO text, cut: a custom call (a kernel) keeps enough
    to be told by its target and name, anything else its left-hand side
    and opcode."""
    return text[:600] if "custom-call" in text else text[:120]


def load(path: str) -> Events:
    """Plain event lists from an ``.xplane.pb``: per device its operations
    (line ``XLA Ops``) and programs (line ``XLA Modules``); from the host
    planes every named event with its thread."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out: Events = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [[_short(e.name), int(e.start_ns),
                                 int(e.duration_ns)] for e in line.events]
            out["devices"][m.group(1)] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        out["host"].append([e.name, int(e.start_ns),
                                            int(e.duration_ns), line.name])
    return out


def describe(path: str, sample: int = 12, grep: str = "custom-call"
             ) -> Dict[str, Any]:
    """The structure of a trace, to look at one by hand: planes, lines,
    event counts, the names that took most time, the first events with
    their stats, and every distinct name that matches ``grep``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    rx = re.compile(grep)
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            by_name: Dict[str, List[int]] = {}
            for e in evs:
                acc = by_name.setdefault(e.name[:100], [0, 0])
                acc[0] += 1
                acc[1] += int(e.duration_ns)
            top = sorted(by_name.items(), key=lambda kv: -kv[1][1])[:40]
            found: Dict[str, List[Any]] = {}
            for e in evs:
                if rx.search(e.name):
                    acc = found.setdefault(e.name.split(" = ")[0],
                                           [0, 0, e.name[:700]])
                    acc[0] += 1
                    acc[1] += int(e.duration_ns)
            lines.append({"line": line.name, "events": len(evs),
                          "grep": found,
                          "top_names": [[k, n, d] for k, (n, d) in top],
                          "first": [
                {"name": e.name, "start_ns": int(e.start_ns),
                 "duration_ns": int(e.duration_ns),
                 "stats": {str(k): str(v)[:80] for k, v in e.stats}}
                for e in evs[:sample]]})
        planes.append({"plane": plane.name, "lines": lines})
    return {"planes": planes}


# -- interval arithmetic ---------------------------------------------------

def _merge(spans: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(spans):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(events: Iterable[Event], lo: int, hi: int
          ) -> List[Tuple[str, int, int]]:
    out = []
    for name, start, dur, *_ in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b))
    return out


def _total(spans: List[Tuple[int, int]]) -> int:
    return sum(b - a for a, b in spans)


def _subtract(spans: List[Tuple[int, int]], cover: List[Tuple[int, int]]
              ) -> int:
    """Length of ``spans`` (merged) not covered by ``cover`` (merged)."""
    left = 0
    for a, b in spans:
        cur = a
        for c, d in cover:
            if d <= cur:
                continue
            if c >= b:
                break
            if c > cur:
                left += c - cur
            cur = max(cur, d)
            if cur >= b:
                break
        if cur < b:
            left += b - cur
    return left


def op_kind(name: str) -> str:
    """An operation's name without its instance number, in the characters
    a report may carry: ``%fusion.123 = ...`` -> ``fusion``."""
    name = name.split(" = ")[0].lstrip("%")
    name = re.sub(r"[.\-_]\d+$", "", name)
    return re.sub(r"[^A-Za-z0-9_.:\-]", "_", name)[:60]


# -- reductions ------------------------------------------------------------

def window(events: Events) -> Tuple[int, int]:
    hits = [e for e in events["host"] if e[0] == WINDOW]
    if not hits:
        raise RuntimeError(f"no {WINDOW} annotation in the trace")
    _name, start, dur, *_ = max(hits, key=lambda e: e[2])
    return start, start + dur


def device_busy(events: Events) -> Dict[str, float]:
    """Seconds in which an operation ran, averaged over the devices, and
    the window's length."""
    lo, hi = window(events)
    busy = [_total(_merge((a, b) for _n, a, b in _clip(dev["ops"], lo, hi)))
            for dev in events["devices"].values()]
    if not busy:
        raise RuntimeError("the trace holds no device plane")
    return {"busy_s": sum(busy) / len(busy) / 1e9,
            "window_s": (hi - lo) / 1e9}


def ops_by_time(events: Events, top: int = 10) -> List[List[Any]]:
    """Device operations by total time (seconds, averaged over devices)."""
    lo, hi = window(events)
    acc: Dict[str, int] = {}
    for dev in events["devices"].values():
        for name, a, b in _clip(dev["ops"], lo, hi):
            k = op_kind(name)
            acc[k] = acc.get(k, 0) + (b - a)
    n = max(1, len(events["devices"]))
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / n / 1e9] for k, v in ranked]


def idle_gaps(events: Events, top: int = 10, label_over_ns: int = 20_000
              ) -> List[List[Any]]:
    """The first device's idle gaps inside the window, summed by what the
    host was doing: each gap longer than ``label_over_ns`` takes the name
    of the shortest host event that covers its middle."""
    lo, hi = window(events)
    if not events["devices"]:
        return []
    dev = events["devices"][sorted(events["devices"])[0]]
    busy = _merge((a, b) for _n, a, b in _clip(dev["ops"], lo, hi))
    gaps, cur = [], lo
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if cur < hi:
        gaps.append((cur, hi))
    host = [e for e in events["host"] if e[0] != WINDOW]
    acc: Dict[str, int] = {}
    for a, b in gaps:
        if b - a <= label_over_ns:
            label = "shorter_gaps_not_labelled"
        else:
            mid = (a + b) // 2
            over = [e for e in host if e[1] <= mid < e[1] + e[2]]
            label = (op_kind(min(over, key=lambda e: e[2])[0]) if over
                     else "unattributed")
        acc[label] = acc.get(label, 0) + (b - a)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / 1e9] for k, v in ranked]


def matching(events: Events, line: str, pattern: str
             ) -> List[Tuple[str, int, int]]:
    """Events of every device's ``ops`` or ``modules`` line whose text
    matches ``pattern``, clipped to the window: (name, start, end). An
    operation's text is its whole HLO instruction, operands included, so a
    pattern for a kernel names its own call (the text before `` = `` or
    the custom call's target), not a word its consumers also carry."""
    lo, hi = window(events)
    rx = re.compile(pattern)
    out = []
    for dev in events["devices"].values():
        out += [e for e in _clip(dev[line], lo, hi) if rx.search(e[0])]
    return out


def collective_exposed_s(events: Events) -> float:
    """Seconds, averaged over the devices, in collective operations during
    which no other operation ran on that device."""
    lo, hi = window(events)
    per_dev = []
    for dev in events["devices"].values():
        ops = _clip(dev["ops"], lo, hi)
        coll = _merge((a, b) for n, a, b in ops if COLLECTIVE.search(n))
        rest = _merge((a, b) for n, a, b in ops if not COLLECTIVE.search(n))
        per_dev.append(_subtract(coll, rest))
    return sum(per_dev) / max(1, len(per_dev)) / 1e9
