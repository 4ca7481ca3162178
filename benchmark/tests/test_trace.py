"""The reduction from trace events to numbers, on synthetic events whose
answers are known by hand, and on the small recorded trace beside this
file (``data/``: event lists cut from a chip run)."""
import json
import os

import pytest

from benchmark.harness import trace

HERE = os.path.dirname(os.path.abspath(__file__))


def _events():
    us = 1000
    return {
        "host": [[trace.WINDOW, 0, 100 * us, "main"],
                 ["fetch", 38 * us, 10 * us, "lane"],
                 ["outer", 30 * us, 40 * us, "lane"]],
        "devices": {
            "0": {"ops": [["%fusion.1 = x", 0, 20 * us],
                          ["%fusion.2 = x", 10 * us, 20 * us],
                          ["all-reduce-done.3", 50 * us, 10 * us],
                          ["copy.7", 55 * us, 25 * us],
                          ["copy.8", 95 * us, 20 * us]],
                  "modules": [["jit_step(1)", 0, 80 * us]]},
            "1": {"ops": [["%fusion.1 = x", 0, 100 * us]], "modules": []},
        }}


def test_busy_is_the_union_clipped_to_the_window_averaged_over_devices():
    busy = trace.device_busy(_events())
    # device 0: [0,30) + [50,80) + [95,100) = 65 us; device 1: 100 us
    assert busy["window_s"] == pytest.approx(100e-6)
    assert busy["busy_s"] == pytest.approx((65e-6 + 100e-6) / 2)


def test_ops_by_time_groups_instances_and_clips():
    ops = dict(trace.ops_by_time(_events()))
    assert ops["fusion"] == pytest.approx((40e-6 + 100e-6) / 2)
    assert ops["copy"] == pytest.approx((25e-6 + 5e-6) / 2)
    assert ops["all-reduce-done"] == pytest.approx(10e-6 / 2)


def test_idle_gaps_take_the_shortest_covering_host_event():
    gaps = dict(trace.idle_gaps(_events(), label_over_ns=5_000))
    # gaps of device 0: [30,50) middle 40 -> inside "fetch" (shorter than
    # "outer"); [80,95) middle 87.5 -> nothing covers it
    assert gaps["fetch"] == pytest.approx(20e-6)
    assert gaps["unattributed"] == pytest.approx(15e-6)
    short = dict(trace.idle_gaps(_events(), label_over_ns=50_000))
    assert short == {"shorter_gaps_not_labelled": pytest.approx(35e-6)}


def test_collective_time_with_no_compute_beside_it():
    # all-reduce-done [50,60) overlaps copy [55,80): 5 us exposed on
    # device 0, none on device 1
    assert trace.collective_exposed_s(_events()) == pytest.approx(5e-6 / 2)


def test_matching_clips_to_the_window():
    ev = _events()
    assert trace.matching(ev, "ops", r"^copy") == [
        ("copy.7", 55000, 80000), ("copy.8", 95000, 100000)]
    assert trace.op_kind("%multiply_add_fusion.12 = bf16[8]") == \
        "multiply_add_fusion"


def test_recorded_trace_reduces_to_the_numbers_read_from_it_by_hand():
    path = os.path.join(HERE, "data", "recorded_trace.json")
    with open(path) as f:
        recorded = json.load(f)
    ev, want = recorded["events"], recorded["expected"]
    busy = trace.device_busy(ev)
    assert busy["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert busy["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    top = trace.ops_by_time(ev, top=3)
    assert [k for k, _ in top] == want["top_ops"]
