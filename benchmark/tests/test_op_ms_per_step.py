"""``readers/op_ms_per_step.py`` with the argument files of its two
metrics: on the recorded trace (cut from a chip run of the program before
it had the attention kernel) and on synthetic events whose answers are
known by hand."""
import json
import os
import types

import pytest

from benchmark.harness import spec, trace
from benchmark.readers import op_ms_per_step

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1000


def _args(metric):
    with open(os.path.join(spec.ROOT, "benchmark", "metrics",
                           metric + ".json")) as f:
        body = json.load(f)
    assert body["reader"] == "op_ms_per_step"
    return body["args"]


def _rin(events):
    return types.SimpleNamespace(events=events)


def test_recorded_trace_has_copies_and_no_attention_kernel():
    with open(os.path.join(HERE, "data", "recorded_trace.json")) as f:
        events = json.load(f)["events"]
    lo, hi = trace.window(events)
    (dev,) = events["devices"].values()
    (step,) = dev["modules"]
    # by hand: the 13 `%copy.N` operations (not the 72 copy-start and 70
    # copy-done), clipped to the window, over the part of the one step
    # program that lies in the window
    copies = [(s, s + d) for n, s, d in dev["ops"]
              if n.startswith("%copy.") and " copy(" in n]
    assert len(copies) == 13
    inside = sum(max(0, min(b, hi) - max(a, lo)) for a, b in copies)
    part = (min(step[1] + step[2], hi) - max(step[1], lo)) / step[2]
    assert 0 < part < 1
    got = op_ms_per_step.read(_rin(events), **_args("model.copy_ms"))
    assert got == pytest.approx(inside / 1e6 / part)
    assert op_ms_per_step.read(
        _rin(events), **_args("kernel.attention_ms")) is None


def _events(devices):
    """A window of [100, 1100) us; on every chip a step program [0, 400)
    (three quarters of it inside), one [400, 800) and one [800, 1200)
    (three quarters inside): 2.5 steps a chip."""
    devs = {}
    for d in range(devices):
        devs[str(d)] = {
            "modules": [[f"jit_step({d})", 0, 400 * US],
                        [f"jit_step({d})", 400 * US, 400 * US],
                        [f"jit_step({d})", 800 * US, 400 * US],
                        ["jit_norms(3)", 500 * US, 10 * US]],
            "ops": [
                # forward call cut by the window's start: 20 of 40 us
                ["%short_attention_fwd.3 = (bf16[128,197,768]{2,1,0}, "
                 "f32[128,197,12]{2,1,0}) custom-call(%copy.1, %copy.2)",
                 80 * US, 40 * US],
                ["%short_attention_bwd.4 = (bf16[128,197,768]{2,1,0}, "
                 "bf16[128,197,768]{2,1,0}) custom-call(%a)", 500 * US,
                 60 * US],
                # its consumer carries the name as an operand only
                ["%fusion.9 = bf16[128,197,768] fusion("
                 "%short_attention_fwd.3)", 600 * US, 100 * US],
                ["%copy.391 = bf16[128,197,768]{0,2,1} copy("
                 "%short_attention_fwd.3)", 700 * US, 30 * US],
                ["%copy.727.clone.1 = f32[16,16,3,768] copy(%p)",
                 740 * US, 5 * US],
                ["%copy-start.12 = (bf16[1]) copy-start(%copy.391)",
                 750 * US, 50 * US],
                ["%copy_add_fusion.2 = bf16[1] fusion(%x)", 760 * US,
                 50 * US]]}
    return {"host": [[trace.WINDOW, 100 * US, 1000 * US, "main"]],
            "devices": devs}


@pytest.mark.parametrize("devices", [1, 4])
def test_time_inside_the_window_over_the_steps_inside_it(devices):
    rin = _rin(_events(devices))
    assert op_ms_per_step.read(rin, **_args("kernel.attention_ms")) == \
        pytest.approx((0.020 + 0.060) / 2.5)
    assert op_ms_per_step.read(rin, **_args("model.copy_ms")) == \
        pytest.approx((0.030 + 0.005) / 2.5)


def test_nothing_to_read():
    assert op_ms_per_step.read(_rin(None), pattern="x") is None
    events = _events(1)
    assert op_ms_per_step.read(_rin(events), pattern="^%nothing") is None
    events["devices"]["0"]["modules"] = []
    assert op_ms_per_step.read(
        _rin(events), **_args("model.copy_ms")) is None
