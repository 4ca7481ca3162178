"""The readers of the program's own spans, on synthetic host and module
events whose answers are known by hand: no span found, spans that
straddle the window's edges, and a trace with four device planes."""
import types

import pytest

from benchmark.harness import trace
from benchmark.readers import program_span, steps_in_flight

US = 1000


def _rin(events):
    return types.SimpleNamespace(events=events)


def _events(devices=1):
    """A window of [100, 1100) us. Four steps are dispatched before the
    trace's device work starts, then one more per step program; spans
    of two other names lie among them."""
    host = [[trace.WINDOW, 100 * US, 1000 * US, "python3"]]
    # dispatch k ends at 10 + 20 k us for k < 4 (before the window), then
    # at 150, 350, 550, 750, 950 and one that straddles the window's end
    ends = [10, 30, 50, 70, 150, 350, 550, 750, 950, 1110]
    durs = [4, 4, 4, 4, 10, 20, 30, 40, 50, 60]
    for end, dur in zip(ends, durs):
        host.append(["trainer:dispatch", (end - dur) * US, dur * US,
                     "python3"])
    # waits: one straddles the window's start [90, 110), two lie inside
    host += [["input:wait", 90 * US, 20 * US, "python3"],
             ["input:wait", 200 * US, 2 * US, "python3"],
             ["input:wait", 400 * US, 6 * US, "python3"]]
    host += [["input:produce", 120 * US, 7 * US, "python3/77"],
             ["input:produce", 220 * US, 9 * US, "python3/77"],
             ["input:produce", 320 * US, 50 * US, "python3/77"]]
    # step programs on every chip: starts at 80 (before the window), 290,
    # 500, 710, 920
    starts = [80, 290, 500, 710, 920]
    lengths = [210, 200, 210, 210, 170]
    devs = {}
    for d in range(devices):
        shift = d * US // 2          # later chips start half a us later
        devs[str(d)] = {
            "modules": [[f"jit_step({d})", s * US + shift, n * US]
                        for s, n in zip(starts, lengths)]
            + [["jit_norms(1)", 85 * US, 1 * US]],
            "ops": [["%fusion.1 = x", s * US + shift, n * US]
                    for s, n in zip(starts, lengths)]}
    return {"host": host, "devices": devs}


def test_no_span_of_the_program_reads_as_nothing():
    ev = _events()
    ev["host"] = [e for e in ev["host"] if e[0] == trace.WINDOW]
    rin = _rin(ev)
    assert program_span.read(rin, "trainer:dispatch") is None
    assert steps_in_flight.read(rin) is None
    assert program_span.read(_rin(None), "trainer:dispatch") is None
    assert steps_in_flight.read(_rin(None)) is None


def test_median_takes_only_spans_wholly_inside_the_window():
    rin = _rin(_events())
    # inside: 10, 20, 30, 40, 50 us; the four before the window and the
    # one that crosses its end are left out
    assert program_span.read(rin, "trainer:dispatch") == \
        pytest.approx(0.030)
    # of the waits, the one that straddles the window's start is left out
    assert program_span.read(rin, "input:wait") == pytest.approx(0.004)
    assert program_span.read(rin, "input:produce") == pytest.approx(0.009)


@pytest.mark.parametrize("devices", [1, 4])
def test_steps_in_flight_counts_from_the_start_of_the_trace(devices):
    # starts inside the window: 290 (1 started before, 5 dispatches ended
    # by then: 4 early ones and the one at 150), 500 (2 / 6), 710 (3 / 7),
    # 920 (4 / 8): 4 ahead at every one; only the first chip is read, and
    # other programs on it (jit_norms) are not steps
    assert steps_in_flight.read(_rin(_events(devices))) == 4
    ev = _events(devices)
    ev["host"] = [e for e in ev["host"]
                  if not (e[0] == "trainer:dispatch" and e[1] < 60 * US)]
    # with one early dispatch, not four, the host is just in time: 1, 1, 1, 1
    assert steps_in_flight.read(_rin(ev)) == 1


@pytest.mark.parametrize("devices", [1, 4])
def test_steps_in_flight_leaves_out_the_runners_drain(devices):
    # the runner's shape at two steps a segment and 100 us a step: the warm
    # segment and segment 0 are dispatched at once, the window opens when
    # the warm one completes (220), every later segment is dispatched when
    # the one before the device's completes, and after the last
    # (dispatched at 425) the device works off what is queued
    host = [[trace.WINDOW, 220 * US, 600 * US, "python3"]]
    for seg, at in enumerate([0, 10, 225, 425]):
        host.append(["bench:dispatch_segment", at * US, 10 * US, "python3"])
        host += [["trainer:dispatch", (at + 1 + 4 * k) * US, 3 * US,
                  "python3"] for k in range(2)]
    devs = {str(d): {"modules": [[f"jit_step({d})", (20 + 100 * k) * US + d,
                                  99 * US] for k in range(8)], "ops": []}
            for d in range(devices)}
    rin = _rin({"host": host, "devices": devs})
    # ahead at the starts inside the window (steps 2..7): 2 3 2 3 2 1; the
    # last segment's two steps are the drain: 2 3 2 3
    assert steps_in_flight.read(rin) == 2
    assert steps_in_flight.read(rin, last="bench:dispatch_segment") == 2.5
    assert steps_in_flight.read(rin, last="bench:no_such_event") == 2
