"""Unified telemetry subsystem: spans, event log, metrics registry, reports.

Covers the observability/ package end to end with an INJECTED clock
(events.set_clock), so every duration and timestamp in these tests is
deterministic: span nesting via parent_id/depth, the zero-cost disabled
path (shared no-op span, no event file), Prometheus exposition parsing,
instrumentation in the trainer / checkpointer / downloader / reliability
subsystems, and the `mmlspark-tpu report` renderer over a real captured
fit + train + checkpoint run.
"""
import json
import os

import numpy as np
import pytest

from mmlspark_tpu.observability import events, metrics as obsmetrics
from mmlspark_tpu.observability.spans import _NOOP, span
from mmlspark_tpu.utils import config


def _ticker(start: float, tick: float):
    """Deterministic fake clock: advances by ``tick`` per call."""
    t = [start]

    def clk():
        t[0] += tick
        return t[0]

    return clk


@pytest.fixture
def registry():
    reg = obsmetrics.get_registry()
    reg.reset()
    yield reg
    reg.reset()


@pytest.fixture
def events_file(tmp_path, registry):
    path = str(tmp_path / "events.jsonl")
    config.set("observability.events_path", path)
    try:
        yield path
    finally:
        events.close()
        events.reset_clock()
        config.unset("observability.events_path")


def _load(path):
    with open(path) as f:
        return [json.loads(l) for l in f if l.strip()]


# ---------------------------------------------------------------- events
def test_emit_is_noop_without_path(tmp_path):
    assert not events.events_enabled()
    events.emit("event", "nope", x=1)  # must not create anything
    assert os.listdir(tmp_path) == []


def test_injected_clock_makes_events_deterministic(events_file):
    events.set_clock(wall_fn=_ticker(100.0, 1.0))
    events.emit("event", "a", k=1)
    events.emit("event", "b")
    evs = _load(events_file)
    assert [e["ts"] for e in evs] == [101.0, 102.0]
    assert evs[0] == {"ts": 101.0, "type": "event", "name": "a", "k": 1}


def test_emit_serializes_non_json_fields_via_str(events_file):
    events.emit("event", "odd", arr=np.int64(3))
    assert _load(events_file)[0]["arr"] == "3"


def test_writer_follows_path_change(tmp_path, registry):
    p1, p2 = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    config.set("observability.events_path", p1)
    try:
        events.emit("event", "one")
        config.set("observability.events_path", p2)
        events.emit("event", "two")
    finally:
        events.close()
        config.unset("observability.events_path")
    assert _load(p1)[0]["name"] == "one"
    assert _load(p2)[0]["name"] == "two"


# ---------------------------------------------------------------- spans
def test_disabled_span_is_shared_noop_singleton():
    # the flight recorder (on by default) also records spans; the
    # zero-allocation path requires ALL sinks off
    config.set("observability.flight_recorder_size", 0)
    try:
        assert not events.events_enabled()
        assert not events.recording_enabled()
        s = span("fit", "Anything")
        assert s is _NOOP
        assert span("transform") is s  # no per-call allocation
        with s:
            pass  # usable as a context manager
    finally:
        config.unset("observability.flight_recorder_size")


def test_span_emits_name_duration_and_nesting(events_file):
    events.set_clock(wall_fn=_ticker(0.0, 1.0), perf_fn=_ticker(0.0, 0.5))
    with span("fit", "Outer"):
        with span("fit", "Inner", stage=0):
            pass
    inner, outer = _load(events_file)
    assert inner["name"] == "fit:Inner" and outer["name"] == "fit:Outer"
    assert inner["parent_id"] == outer["span_id"]
    assert inner["parent"] == "fit:Outer"
    assert (inner["depth"], outer["depth"]) == (1, 0)
    assert outer["parent_id"] is None
    assert inner["attrs"] == {"stage": 0}
    # perf ticks 0.5/call: inner enters+exits inside outer -> exact durs
    assert inner["dur_s"] == 0.5
    assert outer["dur_s"] == 1.5


def test_span_records_error_type(events_file):
    with pytest.raises(ValueError):
        with span("fit", "Boom"):
            raise ValueError("x")
    ev = _load(events_file)[0]
    assert ev["error"] == "ValueError"


def test_span_stack_unwinds_after_exception(events_file):
    from mmlspark_tpu.observability.spans import current_span
    with pytest.raises(RuntimeError):
        with span("a"):
            raise RuntimeError
    assert current_span() is None
    with span("b"):
        assert current_span()[0] == "b"


# ---------------------------------------------------------------- registry
def test_counter_gauge_histogram_semantics(registry):
    c = registry.counter("c")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = registry.gauge("g")
    g.set(7)
    g.inc(-2)
    assert g.value == 5.0
    h = registry.histogram("h", buckets=[0.1, 1.0])
    for v in (0.05, 0.1, 0.5, 3.0):
        h.observe(v)
    assert h.count == 4 and h.sum == pytest.approx(3.65)
    # le semantics: 0.1 falls in the le=0.1 bucket; 3.0 only in +Inf
    assert h.cumulative() == {"0.1": 2, "1.0": 3, "+Inf": 4}


def test_registry_rejects_type_conflicts(registry):
    registry.counter("dup")
    with pytest.raises(ValueError, match="already registered"):
        registry.gauge("dup")


def test_histogram_rejects_unsorted_buckets(registry):
    with pytest.raises(ValueError):
        registry.histogram("bad", buckets=[1.0, 0.5])


def test_prometheus_exposition_parses(registry):
    registry.counter("downloader.cache_hits").inc(2)
    registry.gauge("trainer.examples_per_sec").set(123.5)
    h = registry.histogram("step.time", buckets=[0.1, 1.0])
    h.observe(0.05)
    h.observe(5.0)
    text = registry.prometheus_text()
    types, samples = {}, {}
    for line in text.strip().splitlines():
        if line.startswith("# TYPE"):
            _, _, name, mtype = line.split()
            types[name] = mtype
        else:
            name, value = line.rsplit(" ", 1)
            samples[name] = float(value)
    # names sanitized to the Prometheus charset (dots -> underscores)
    assert types == {"downloader_cache_hits": "counter",
                     "trainer_examples_per_sec": "gauge",
                     "step_time": "histogram"}
    assert samples["downloader_cache_hits"] == 2
    assert samples["trainer_examples_per_sec"] == 123.5
    # cumulative buckets are monotone and +Inf == _count
    b1 = samples['step_time_bucket{le="0.1"}']
    b2 = samples['step_time_bucket{le="1.0"}']
    binf = samples['step_time_bucket{le="+Inf"}']
    assert b1 <= b2 <= binf
    assert binf == samples["step_time_count"] == 2
    assert samples["step_time_sum"] == pytest.approx(5.05)


def test_registry_json_dump_roundtrips(registry):
    registry.counter("n").inc()
    registry.histogram("h").observe(0.2)
    dump = json.loads(registry.to_json())
    assert dump["n"] == {"type": "counter", "value": 1}
    assert dump["h"]["type"] == "histogram" and dump["h"]["count"] == 1


def test_metric_name_sanitize():
    assert obsmetrics.sanitize("a.b-c/d") == "a_b_c_d"
    assert obsmetrics.sanitize("9lives") == "_9lives"


# ---------------------------------------------------------------- trainer
def _make_trainer():
    import jax.numpy as jnp
    import optax
    from mmlspark_tpu.parallel.trainer import DistributedTrainer

    def loss_fn(params, batch, rng):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2)

    trainer = DistributedTrainer(loss_fn, optax.sgd(0.1))
    state = trainer.init(lambda: {"w": jnp.zeros((3,), jnp.float32)})
    return trainer, state


def _batches(n, rows=8):
    rng = np.random.default_rng(0)
    return [{"x": rng.normal(size=(rows, 3)).astype(np.float32),
             "y": np.ones((rows,), np.float32)} for _ in range(n)]


def test_trainer_disabled_registers_no_hot_instruments(registry):
    trainer, state = _make_trainer()
    trainer.fit(state, iter(_batches(3)))
    assert "trainer.step_time_seconds" not in registry.to_dict()


def test_trainer_metrics_step_histogram_and_throughput(registry):
    config.set("observability.metrics", True)
    try:
        trainer, state = _make_trainer()
        trainer.fit(state, iter(_batches(5)))
    finally:
        config.unset("observability.metrics")
    dump = registry.to_dict()
    assert dump["trainer.step_time_seconds"]["count"] == 5
    assert dump["trainer.examples_per_sec"]["value"] > 0


# ---------------------------------------------------------------- reliability
def test_retry_attempts_counted_and_logged(events_file, registry):
    from mmlspark_tpu.reliability.retry import RetryPolicy
    calls = [0]

    def flaky():
        calls[0] += 1
        if calls[0] < 3:
            raise ConnectionError("transient")
        return "ok"

    policy = RetryPolicy(max_attempts=4, base_delay=0.01, name="dl",
                         sleep=lambda s: None)
    assert policy.call(flaky) == "ok"
    assert registry.counter("reliability.retry_attempts").value == 2
    evs = [e for e in _load(events_file) if e["name"] == "retry.attempt"]
    assert [e["attempt"] for e in evs] == [1, 2]
    assert all(e["policy"] == "dl" for e in evs)
    assert "ConnectionError" in evs[0]["error"]


def test_fault_hits_counted_and_logged(events_file, registry):
    from mmlspark_tpu.reliability.faults import (
        FaultPlan, FaultSpec, InjectedFault, fault_site,
    )
    with FaultPlan(FaultSpec("unit.site", on_hit=2)):
        fault_site("unit.site")
        with pytest.raises(InjectedFault):
            fault_site("unit.site")
    assert registry.counter("reliability.fault_hits").value == 1
    ev, = [e for e in _load(events_file) if e["name"] == "fault.hit"]
    assert ev["site"] == "unit.site" and ev["hit"] == 2
    assert ev["action"] == "raise"


def test_quarantine_emits_event_and_counter(tmp_path, events_file, registry):
    pytest.importorskip("orbax.checkpoint")
    from mmlspark_tpu.parallel.checkpoint import TrainCheckpointer
    ckpt = TrainCheckpointer(str(tmp_path / "ckpt"))
    try:
        os.makedirs(os.path.join(ckpt.directory, "7"), exist_ok=True)
        dst = ckpt.quarantine_step(7)
    finally:
        ckpt.close()
    assert os.path.isdir(dst) and "corrupt-7" in dst
    assert registry.counter("checkpoint.quarantines").value == 1
    ev, = [e for e in _load(events_file)
           if e["name"] == "checkpoint.quarantine"]
    assert ev["step"] == 7 and ev["path"] == dst


# ---------------------------------------------------------------- downloader
def test_downloader_cache_hit_miss_counters(tmp_path, events_file, registry):
    from mmlspark_tpu.models.downloader import HttpRepo, ModelSchema
    repo = HttpRepo("http://models.example", str(tmp_path / "cache"))
    repo._fetch = lambda url: b"payload-bytes"  # no network in tests
    schema = ModelSchema(name="m1")
    repo.get_model_path(schema)   # cold: miss + download
    repo.get_model_path(schema)   # warm: hit
    assert registry.counter("downloader.cache_misses").value == 1
    assert registry.counter("downloader.downloads").value == 1
    assert registry.counter("downloader.cache_hits").value == 1
    ev, = [e for e in _load(events_file)
           if e["name"] == "downloader.download"]
    assert ev["model"] == "m1" and ev["bytes"] == len(b"payload-bytes")


# ---------------------------------------------------------------- MetricLogger
def test_metric_logger_history_is_bounded():
    from mmlspark_tpu.utils.logging import MetricLogger
    ml = MetricLogger(every=1, name="test", history_max=3)
    for step in range(1, 11):
        ml(step, {"loss": 0.5}, batch_rows=4)
    assert [h["step"] for h in ml.history] == [8, 9, 10]


def test_metric_logger_forwards_to_registry_and_events(events_file, registry):
    from mmlspark_tpu.utils.logging import MetricLogger
    events.set_clock(perf_fn=_ticker(0.0, 1.0))
    ml = MetricLogger(every=1, name="test")
    ml(1, {"loss": 0.5}, batch_rows=10)
    ml(2, {"loss": 0.25}, batch_rows=10)
    assert registry.gauge("train.loss").value == 0.25
    # interval is one fake-clock tick (1s) per call: 10 rows/s exactly
    assert registry.gauge("train.examples_per_sec").value == 10.0
    evs = [e for e in _load(events_file) if e["name"] == "train.step"]
    assert [e["step"] for e in evs] == [1, 2]
    assert evs[0]["examples_per_sec"] == 0.0  # no baseline on first call
    assert evs[1]["examples_per_sec"] == 10.0
    assert evs[1]["values"] == {"loss": 0.25}


# ---------------------------------------------------------------- core metrics
def test_metric_value_routes_through_registry_and_events(events_file,
                                                         registry):
    from mmlspark_tpu.core import metrics as metric_data
    metric_data.create("auc", 0.91, model_uid="M7").log()
    assert registry.gauge("metrics.auc").value == 0.91
    ev, = [e for e in _load(events_file) if e["name"] == "auc"]
    assert ev["value"] == 0.91 and ev["model"] == "M7"


def test_metric_table_to_frame_and_log(events_file, registry):
    from mmlspark_tpu.core import metrics as metric_data
    table = metric_data.create_table(
        "confusion", ["predicted", "actual"],
        np.array([[3, 1], [0, 4]]), model_uid="M7")
    f = table.to_frame()
    assert f.columns == ["predicted", "actual"] and f.count() == 2
    assert list(f.column("predicted")) == [3, 0]
    table.log()
    ev, = [e for e in _load(events_file) if e["name"] == "confusion"]
    assert ev["rows"] == 2 and ev["columns"] == ["predicted", "actual"]


# ---------------------------------------------------------------- profiling
def test_nested_trace_is_warned_noop_not_crash(tmp_path, caplog):
    import logging
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.utils.logging import get_logger
    from mmlspark_tpu.utils.profiling import trace
    root = get_logger()
    root.propagate = True
    try:
        with caplog.at_level(logging.WARNING,
                             logger="mmlspark_tpu.profiling"):
            with trace(str(tmp_path / "outer")):
                with trace(str(tmp_path / "inner")):  # must not raise
                    jax.jit(lambda x: x + 1)(jnp.ones(4)).block_until_ready()
    finally:
        root.propagate = False
    assert any("nested trace" in r.getMessage() for r in caplog.records)
    # the OUTER capture stayed alive through the nested no-op
    found = [f for _, _, fs in os.walk(tmp_path / "outer") for f in fs]
    assert found


def test_annotate_degrades_to_nullcontext(monkeypatch):
    import contextlib
    import jax
    from mmlspark_tpu.utils import profiling

    class Broken:
        def __init__(self, name):
            raise RuntimeError("profiler backend unavailable")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Broken)
    ctx = profiling.annotate("step")
    assert isinstance(ctx, contextlib.nullcontext)
    with ctx:
        pass


def test_trace_survives_broken_profiler(tmp_path, monkeypatch):
    import jax
    from mmlspark_tpu.utils import profiling

    def broken(target):
        raise RuntimeError("no backend")

    monkeypatch.setattr(jax.profiler, "trace", broken)
    ran = []
    with profiling.trace(str(tmp_path / "t")):
        ran.append(True)  # body still runs
    assert ran == [True]


# ---------------------------------------------------------------- bench
def test_bench_emits_config_results_through_event_log(events_file):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_under_test",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    bench._emit_bench_event("train", {"value": 100.0,
                                      "unit": "images/sec/chip",
                                      "vs_baseline": 1.2})
    ev, = [e for e in _load(events_file) if e["name"] == "bench.config"]
    assert ev["config"] == "train"
    assert ev["result"]["vs_baseline"] == 1.2


# ---------------------------------------------------------------- end to end
def test_fit_train_checkpoint_report_end_to_end(tmp_path, events_file,
                                                registry, capsys):
    """The acceptance walk: a Pipeline.fit, 20 trainer steps, one
    checkpoint save — all with an injected clock — produce a JSONL log
    whose spans nest correctly, a parsable Prometheus exposition, and a
    report the CLI renders."""
    pytest.importorskip("orbax.checkpoint")
    from mmlspark_tpu.core.frame import Frame
    from mmlspark_tpu.core.pipeline import Estimator, Pipeline, Transformer
    from mmlspark_tpu.observability.report import render_report
    from mmlspark_tpu.parallel.checkpoint import TrainCheckpointer

    config.set("observability.metrics", True)
    events.set_clock(wall_fn=_ticker(1_000.0, 0.25),
                     perf_fn=_ticker(0.0, 0.125))

    class AddOne(Transformer):
        def transform(self, frame):
            return frame

    class Lift(Estimator):
        def fit(self, frame):
            return AddOne()

    try:
        frame = Frame.from_dict({"x": np.arange(8.0)})
        Pipeline(stages=[AddOne(), Lift()]).fit(frame)

        trainer, state = _make_trainer()
        state, losses = trainer.fit(state, iter(_batches(20)))
        assert len(losses) == 20

        ckpt = TrainCheckpointer(str(tmp_path / "ckpt"))
        try:
            ckpt.save(state, wait=True)
        finally:
            ckpt.close()
    finally:
        config.unset("observability.metrics")
        events.close()
        events.reset_clock()

    evs = _load(events_file)
    # but for ``compile:<name>``: retroactive, with the seconds jax itself
    # reported of its stages (observability/compiles.py)
    spans = {e["span_id"]: e for e in evs if e["type"] == "span"
             and not e["name"].startswith("compile:")}
    by_name = {}
    for s in spans.values():
        by_name.setdefault(s["name"], []).append(s)

    # pipeline spans nest: fit:Pipeline is the root; the per-stage
    # transform/fit spans are its direct children
    root, = by_name["fit:Pipeline"]
    assert root["parent_id"] is None and root["depth"] == 0
    for child_name in ("transform:AddOne", "fit:Lift"):
        child, = by_name[child_name]
        assert child["parent_id"] == root["span_id"]
        assert child["parent"] == "fit:Pipeline"
        assert child["depth"] == 1
    # checkpoint save span is a root of its own
    save, = by_name["checkpoint:save"]
    assert save["parent_id"] is None
    # injected clock: every span duration is an exact perf-tick multiple
    for s in spans.values():
        assert (s["dur_s"] / 0.125) == pytest.approx(
            round(s["dur_s"] / 0.125))

    # trainer summary event with deterministic throughput fields
    fit_ev, = [e for e in evs if e.get("name") == "train.fit"]
    assert fit_ev["steps"] == 20
    assert fit_ev["rows"] == 20 * 8
    assert fit_ev["wall_s"] > 0 and fit_ev["examples_per_sec"] > 0

    # registry collected the hot-path instruments + the save counter
    dump = registry.to_dict()
    assert dump["trainer.step_time_seconds"]["count"] == 20
    assert dump["checkpoint.saves"]["value"] == 1
    # the Prometheus exposition of the same run parses
    text = registry.prometheus_text()
    assert "# TYPE trainer_step_time_seconds histogram" in text
    assert 'trainer_step_time_seconds_bucket{le="+Inf"} 20' in text

    # offline report renders the breakdown from the captured log
    report = render_report(events_file)
    assert "per-stage wall time" in report
    assert "fit:Pipeline" in report
    assert "train.fit: 20 steps" in report

    # and the installed CLI path renders the same thing
    from mmlspark_tpu.cli import main
    assert main(["report", events_file]) == 0
    assert "per-stage wall time" in capsys.readouterr().out


def test_report_tolerates_malformed_lines(tmp_path):
    from mmlspark_tpu.observability.report import load_events, render_report
    p = tmp_path / "ev.jsonl"
    p.write_text('{"ts": 1, "type": "event", "name": "x"}\n'
                 '{"truncated...\n')
    assert len(load_events(str(p))) == 1
    out = render_report(str(p))
    assert "run report" in out


def test_report_on_empty_log(tmp_path):
    from mmlspark_tpu.observability.report import render_report
    p = tmp_path / "empty.jsonl"
    p.write_text("")
    out = render_report(str(p))
    assert "no spans" in out
