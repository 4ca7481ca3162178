"""One run of one cell: set-up, window, check, the result line.

``main`` is what ``benchmark/run.py`` calls: it refuses to measure without
the accelerator the cell asks for. ``run_cell`` is the rest of a run with
the device handed in, which the rehearsals under ``benchmark/tests`` drive
on the CPU at toy sizes.
"""
from __future__ import annotations

import argparse
import os
import time
from typing import Any, Dict, List, Optional

from benchmark.harness import device as devicemod
from benchmark.harness import report, spec, trace
from benchmark.harness.compilemeter import CompileMeter


class Context:
    """What a runner gets: the cell, the run's arguments, the device, the
    compile meter, the tracer and the list of checks."""

    def __init__(self, cell: spec.Cell, seed: int, seconds: float,
                 traced: bool, device: Dict[str, Any], meter: CompileMeter,
                 tracer: trace.Tracer, process_start: float):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.traced, self.device, self.meter = traced, device, meter
        self.tracer, self.process_start = tracer, process_start
        self.checks = report.Checks()
        self.setup_s: Optional[float] = None

    @property
    def window_seconds(self) -> float:
        """A traced run measures (and traces) a short window of its own."""
        if self.traced:
            return min(self.seconds,
                       float(self.cell.traffic["trace_seconds"]))
        return self.seconds

    def window_opens(self, now: float) -> None:
        """Set-up ends here: process start to the first measured instant."""
        self.setup_s = now - self.process_start

    def memory_peak(self) -> int:
        return devicemod.memory_peak_bytes(self.device["devices"])


class ReaderInput:
    """What a per-layer reader gets: the runner's spans, counters and work,
    the reduced trace, the device's peaks, the cell, the compile meter's
    totals."""

    def __init__(self, out: Dict[str, Any], events, ctx: Context,
                 compiles: Dict[str, float]):
        self.spans = out.get("spans", {})
        self.counters = out.get("counters", {})
        self.work = out.get("work", {})
        self.memory_peak_bytes = out.get("memory_peak_bytes", 0)
        self.events = events
        self.peaks = ctx.device["peaks"]
        self.compiles = compiles


def run_cell(cell: spec.Cell, *, seed: int, seconds: float, traced: bool,
             device: Dict[str, Any], process_start: float,
             trace_dir: str) -> Dict[str, Any]:
    """Everything of a run but the look for a chip; returns the parts of
    the result line."""
    meter = CompileMeter()
    tracer = trace.Tracer(traced, trace_dir)
    ctx = Context(cell, seed, seconds, traced, device, meter, tracer,
                  process_start)
    runner = spec.load_plugin("runners", cell.config["runner"])
    out = runner.run(ctx)
    if ctx.setup_s is None:
        raise RuntimeError("the runner never opened its window")
    ctx.checks.print()
    compiles = meter.snapshot()
    report.note("compile", **compiles)

    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": int(out.get("memory_peak_bytes", 0))}
    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    if not traced:
        values = dict(out["end_to_end"], setup_s=ctx.setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    else:
        events = tracer.events()
        rin = ReaderInput(out, events, ctx, compiles)
        for m in cell.per_layer:
            reader = spec.load_plugin("readers", m["reader"])
            value = reader.read(rin, **m.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        busy = trace.device_busy(events)
        dev.update(busy_s=busy["busy_s"], window_s=busy["window_s"])
        breakdown = {"device_ops": trace.ops_by_time(events),
                     "idle_gaps": trace.idle_gaps(events)}
    return {"correct": ctx.checks.correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": dev,
            "breakdown": breakdown}


def main(argv: List[str], process_start: float) -> int:
    ap = argparse.ArgumentParser(prog="benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    # the program under test has to be there: a directory that holds only
    # the benchmark's files fails here, before any result
    from mmlspark_tpu import compile_cache
    from mmlspark_tpu.utils import config as mmlconfig
    # the look for a chip comes first, so that a run that finds none
    # leaves nothing behind; looking compiles nothing, and jax binds its
    # cache at the first compile
    device = devicemod.require(cell.chips)
    compile_cache.enable(os.path.join(spec.ROOT, ".jax_cache"))
    if args.trace:
        mmlconfig.set("observability.annotate", True)
    report.note("device", platform=device["platform"], kind=device["kind"],
                count=device["count"], cell=cell.name, seed=args.seed,
                seconds=args.seconds, trace=args.trace,
                imports_and_device_s=round(
                    time.perf_counter() - process_start, 3))
    parts = run_cell(
        cell, seed=args.seed, seconds=args.seconds, traced=bool(args.trace),
        device=device, process_start=process_start,
        trace_dir=os.path.join(spec.ROOT, ".bench_trace", cell.name))
    report.emit(report.result_line(**parts))
    return 0
