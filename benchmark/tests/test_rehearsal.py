"""CPU rehearsals of each runner at toy size: the result's key set, and
that a measuring run on a CPU fails with no result."""
import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import report
from benchmark.tests import toy

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def _line(parts):
    return json.loads(report.result_line(**parts))


def test_train_runner_at_toy_size(tmp_path, capsys):
    line = _line(toy.run(toy.train_cell(), tmp_path))
    assert set(line) == KEYS and set(line["device"]) == DEVICE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"items_s_chip", "setup_s"}
    out = capsys.readouterr().out
    segments = json.loads(next(l for l in out.splitlines()
                               if l.startswith("# segments"))[11:])
    rates = segments["items_s_chip"]
    assert len(rates) >= 2
    # all the window's items over all its time: with equal work a segment,
    # the harmonic mean of the segments' rates, never their median
    value = line["metrics"]["items_s_chip"]["value"]
    assert value == pytest.approx(segments["total_over_window"], abs=1e-3)
    assert value == pytest.approx(
        len(rates) / sum(1.0 / r for r in rates), rel=1e-4)


def test_a_measuring_run_on_a_cpu_fails_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "vit-b16-train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def test_traced_run_reads_per_layer_metrics_from_recorded_events(
        tmp_path, monkeypatch):
    # no device plane on a CPU: the traced run's line is built from the
    # small trace recorded on the chip
    from benchmark.harness import trace
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "recorded_trace.json")) as f:
        recorded = json.load(f)["events"]
    monkeypatch.setattr(trace.Tracer, "events", lambda self: recorded)
    line = _line(toy.run(toy.train_cell(), tmp_path, traced=True))
    assert set(line) == KEYS | {"breakdown"}
    assert set(line["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert {"trainer.step_ms", "model.mfu", "compile.window_compiles",
            "device.idle_share.train"} <= set(line["metrics"])
    assert "items_s_chip" not in line["metrics"]
    assert len(line["breakdown"]["device_ops"]) <= 10
