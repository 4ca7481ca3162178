"""Performance attribution layer (ISSUE 6): Chrome-trace export,
request-scoped serve tracing, host-sync accounting, the flight recorder,
and the bench regression gate.

Everything runs with injected clocks (events.set_clock, the serve
Server's ``clock=``, the watchdog's ``set_clock``), so no test sleeps and
every duration is deterministic. The acceptance spine:

- a REAL fit run (Pipeline.fit + trainer steps) exports a valid
  Chrome-trace: every ``B`` closed by an ``E``, timestamps monotone per
  track, sync points and the ``train.fit`` summary as instant marks;
- a slow serve request yields ONE trace_id correlated across the request
  event, the tail-sampled span timeline, the latency-histogram exemplar,
  and the caller's future;
- the flight recorder dumps a non-empty timeline on a watchdog stall and
  on a CLI crash with ``observability.events_path`` UNSET — the whole
  point of the default-on ring;
- ``bench.py --baseline`` exits 0 on parity and 2 on an injected 20%
  step-time regression, via the pure benchgate comparison.
"""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

from mmlspark_tpu.observability import (
    events, flightrec, metrics as obsmetrics, syncs,
)
from mmlspark_tpu.observability.benchgate import compare, gate, load_baseline
from mmlspark_tpu.observability.report import build_report, render_report
from mmlspark_tpu.observability.spans import span
from mmlspark_tpu.observability.trace import (
    build_trace, export_trace, validate_trace,
)
from mmlspark_tpu.utils import config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_slate():
    """Fresh registry + empty flight-recorder ring + zeroed sync counter
    around every test — all three are process-global."""
    obsmetrics.get_registry().reset()
    flightrec.clear()
    syncs.reset()
    yield
    obsmetrics.get_registry().reset()
    flightrec.clear()
    syncs.reset()


@pytest.fixture
def registry():
    return obsmetrics.get_registry()


@pytest.fixture
def events_file(tmp_path):
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


def _ticker(start: float, tick: float):
    """Fake clock advancing ``tick`` per call (the test_telemetry idiom)."""
    t = [start]

    def clk():
        t[0] += tick
        return t[0]

    return clk


def _adv_ticker(start=0.0):
    """Fake clock advanced explicitly (the test_serving idiom)."""
    state = {"now": float(start)}

    def clock():
        return state["now"]
    clock.advance = lambda dt: state.__setitem__("now", state["now"] + dt)
    return clock


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


# ------------------------------------------------------------ trace export
def test_trace_export_from_real_fit_run(events_file, tmp_path):
    """A captured Pipeline.fit + trainer run exports a Chrome trace that
    passes the schema check: every B has an E, ts monotone per track."""
    from mmlspark_tpu.core.frame import Frame
    from mmlspark_tpu.core.pipeline import Estimator, Pipeline, Transformer

    events.set_clock(wall_fn=_ticker(1_000.0, 0.25),
                     perf_fn=_ticker(0.0, 0.125))

    class AddOne(Transformer):
        def transform(self, frame):
            return frame

    class Lift(Estimator):
        def fit(self, frame):
            return AddOne()

    frame = Frame.from_dict({"x": np.arange(8.0)})
    Pipeline(stages=[AddOne(), Lift()]).fit(frame)
    trainer, state = _make_trainer()
    trainer.fit(state, iter(_batches(5)))
    events.close()

    out = str(tmp_path / "out.trace.json")
    stats = export_trace(events_file, out)
    assert stats["out"] == out and stats["spans"] >= 3

    with open(out) as f:
        trace = json.load(f)
    assert validate_trace(trace) == []      # B/E pairing + monotone ts
    evs = trace["traceEvents"]
    bs = [e for e in evs if e["ph"] == "B"]
    es = [e for e in evs if e["ph"] == "E"]
    assert len(bs) == len(es) == stats["spans"]
    names = {e["name"] for e in bs}
    assert {"fit:Pipeline", "transform:AddOne", "fit:Lift"} <= names
    # every B carries its span identity for cross-referencing the log
    assert all("span_id" in e["args"] for e in bs)
    # the pipeline children share the root's track (they nest, not race)
    root, = [e for e in bs if e["name"] == "fit:Pipeline"]
    kids = [e for e in bs if e["name"] in ("transform:AddOne", "fit:Lift")]
    assert all((k["pid"], k["tid"]) == (root["pid"], root["tid"])
               for k in kids)
    # instant marks: the trainer's sync points and its fit summary
    inames = {e["name"] for e in evs if e["ph"] == "i"}
    assert "sync.point" in inames and "train.fit" in inames
    # Perfetto metadata names the process and tracks
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in evs)


def test_trace_keys_spans_on_pid_and_span_id(tmp_path):
    """Satellite (a): a merged two-process log whose span_ids collide must
    produce one span per (pid, span_id), not a scrambled tree."""
    p = tmp_path / "merged.jsonl"
    rows = [
        {"ts": 1.5, "type": "span", "name": "fit:A", "span_id": 1,
         "pid": 100, "parent_id": None, "depth": 0,
         "start": 1.0, "dur_s": 0.5},
        {"ts": 1.4, "type": "span", "name": "fit:B", "span_id": 1,
         "pid": 200, "parent_id": None, "depth": 0,
         "start": 1.1, "dur_s": 0.3},
        # same id as A's child in pid 200: must attach to B, not A
        {"ts": 1.3, "type": "span", "name": "fit:B.child", "span_id": 2,
         "pid": 200, "parent_id": 1, "depth": 1,
         "start": 1.15, "dur_s": 0.1},
    ]
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    trace = build_trace(_load(str(p)))
    assert validate_trace(trace) == []
    bs = [e for e in trace["traceEvents"] if e["ph"] == "B"]
    assert len(bs) == 3
    assert {e["pid"] for e in bs} == {100, 200}
    child, = [e for e in bs if e["name"] == "fit:B.child"]
    root_b, = [e for e in bs if e["name"] == "fit:B"]
    assert (child["pid"], child["tid"]) == (root_b["pid"], root_b["tid"])


def test_trace_orphan_parent_becomes_root(tmp_path):
    p = tmp_path / "partial.jsonl"
    p.write_text(json.dumps(
        {"ts": 2.0, "type": "span", "name": "fit:orphan", "span_id": 7,
         "pid": 1, "parent_id": 99, "depth": 1,
         "start": 1.0, "dur_s": 1.0}) + "\n")
    trace = build_trace(_load(str(p)))
    assert validate_trace(trace) == []
    assert sum(1 for e in trace["traceEvents"] if e["ph"] == "B") == 1


def test_report_cli_trace_and_json(events_file, tmp_path, capsys):
    """Satellite (b): ``report --json`` emits the structured report;
    ``--trace`` writes the Perfetto file alongside it."""
    events.set_clock(wall_fn=_ticker(0.0, 1.0), perf_fn=_ticker(0.0, 0.5))
    with span("fit", "Thing"):
        pass
    events.close()

    from mmlspark_tpu.cli import main
    out = str(tmp_path / "run.trace.json")
    assert main(["report", events_file, "--trace", out, "--json"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines[0].startswith("trace: ") and "perfetto" in lines[0]
    rep = json.loads(lines[-1])                 # one JSON object, parseable
    assert rep["spans"] == 1
    assert rep["stages"][0]["span"] == "fit:Thing"
    with open(out) as f:
        assert validate_trace(json.load(f)) == []


# ------------------------------------------------------------ host syncs
def test_sync_wrappers_count_and_attribute_to_spans(events_file, registry):
    import jax.numpy as jnp

    with span("fit", "Collect"):
        got = syncs.device_get(jnp.arange(3), "test.site")
    np.testing.assert_array_equal(np.asarray(got), np.arange(3))
    syncs.block_until_ready(jnp.ones(2), "test.wait")

    assert syncs.total() == 2
    dump = registry.to_dict()
    assert dump["observability.sync_points"]["value"] == 2
    assert dump["observability.sync_points.test.site"]["value"] == 1
    assert dump["observability.sync_points.test.wait"]["value"] == 1

    evs = [e for e in _load(events_file) if e.get("name") == "sync.point"]
    assert [e["site"] for e in evs] == ["test.site", "test.wait"]
    assert evs[0]["kind"] == "device_get"
    assert evs[0]["span"] == "fit:Collect"       # attributed to the phase
    assert evs[0]["span_id"] is not None
    assert evs[1]["span"] is None                # outside any span


def test_trainer_publishes_sync_points_per_step_gauge(registry):
    config.set("observability.metrics", True)
    try:
        trainer, state = _make_trainer()
        trainer.fit(state, iter(_batches(4)))
    finally:
        config.unset("observability.metrics")
    g = registry.to_dict()["train.sync_points_per_step"]
    assert g["type"] == "gauge"
    # sync-free steady state: metrics ride the device ring, the gauge is
    # sampled before the epoch-end telemetry wait, and ring flushes are
    # excluded — stepping itself performs ZERO host round trips
    assert g["value"] == 0.0
    assert registry.to_dict()["observability.sync_points"]["value"] \
        == syncs.total()


def test_report_renders_sync_section(events_file):
    with span("fit", "X"):
        syncs.sync_point("unit.site", "device_get")
        syncs.sync_point("unit.site")
    events.emit("metric", "train.step", step=2)
    events.close()

    rep = build_report(events_file)
    assert rep["syncs"]["total"] == 2
    assert rep["syncs"]["by_site"] == {"unit.site": 2}
    assert rep["syncs"]["by_span"] == {"fit:X": 2}
    assert rep["syncs"]["per_step"] == 1.0
    text = render_report(events_file)
    assert "host syncs:" in text and "per train step: 1.00" in text


# ------------------------------------------------------------ flight recorder
def test_ring_captures_with_events_path_unset(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    assert not events.events_enabled()
    assert events.recording_enabled()            # the default-on ring
    events.emit("event", "incident.context", k=1)
    assert [e["name"] for e in flightrec.snapshot()] == ["incident.context"]
    assert os.listdir(tmp_path) == []            # in-memory only, no I/O

    path = flightrec.dump(reason="unit")
    assert path is not None and os.path.dirname(path) == str(tmp_path)
    lines = _load(path)
    header, body = lines[0], lines[1:]
    assert header["name"] == "flightrec.dump" and header["reason"] == "unit"
    assert header["events"] == len(body) == 1
    assert body[0]["name"] == "incident.context" and body[0]["k"] == 1


def test_ring_is_bounded_and_counts_drops():
    config.set("observability.flight_recorder_size", 4)
    try:
        for i in range(10):
            events.emit("event", f"e{i}")
        snap = flightrec.snapshot()
        assert [e["name"] for e in snap] == ["e6", "e7", "e8", "e9"]
    finally:
        config.unset("observability.flight_recorder_size")


def test_ring_off_means_no_capture_and_no_dump():
    config.set("observability.flight_recorder_size", 0)
    try:
        assert not events.recording_enabled()
        events.emit("event", "dropped")
        assert flightrec.snapshot() == []
        assert flightrec.dump(reason="nothing") is None
    finally:
        config.unset("observability.flight_recorder_size")


def test_watchdog_stall_dumps_flight_recorder(tmp_path, monkeypatch):
    """ISSUE acceptance: a stall produces a non-empty flight-recorder file
    with observability.events_path UNSET."""
    from mmlspark_tpu.reliability import watchdog as wd

    monkeypatch.chdir(tmp_path)
    assert not events.events_enabled()
    now = [0.0]
    wd.set_clock(lambda: now[0])
    hb = wd.register("train.loop")
    try:
        events.emit("event", "step.progress", step=1)   # ring context
        dog = wd.Watchdog(stall_timeout_s=5.0, start=False)
        now[0] = 60.0
        stalls = dog.check()
        assert "train.loop" in [s.name for s in stalls]
    finally:
        hb.close()
        wd.set_clock(None)

    dumps = [f for f in os.listdir(tmp_path) if f.startswith("flightrec-")]
    assert len(dumps) == 1
    lines = _load(str(tmp_path / dumps[0]))
    assert lines[0]["reason"] == "watchdog.stall.train.loop"
    assert lines[0]["events"] == len(lines) - 1 >= 2
    names = [e["name"] for e in lines[1:]]
    # the timeline up to the incident AND the incident itself
    assert "step.progress" in names and "watchdog.stall" in names
    # the dump is a valid event log: report + trace both read it
    rep = build_report(str(tmp_path / dumps[0]))
    assert rep["liveness"]["stalls"]["total"] == 1
    assert rep["liveness"]["stalls"]["by_heartbeat"] == {"train.loop": 1}


def test_cli_crash_dumps_flight_recorder(tmp_path, monkeypatch, capsys):
    from mmlspark_tpu.cli import main

    monkeypatch.chdir(tmp_path)
    events.emit("event", "about.to.crash")
    with pytest.raises(FileNotFoundError):
        main(["report", str(tmp_path / "missing.jsonl")])
    dumps = [f for f in os.listdir(tmp_path) if f.startswith("flightrec-")]
    assert len(dumps) == 1
    lines = _load(str(tmp_path / dumps[0]))
    assert lines[0]["reason"] == "crash"
    assert any(e["name"] == "about.to.crash" for e in lines[1:])
    assert "flight recorder dumped" in capsys.readouterr().err


# ------------------------------------------------------------ serve tracing
def _make_model(dim=8, classes=3, seed=0):
    from mmlspark_tpu.models.jax_model import JaxModel
    m = JaxModel(inputCol="x", outputCol="y", miniBatchSize=8)
    m.set_model("mlp_tabular", input_dim=dim, hidden=[16],
                num_classes=classes, seed=seed)
    return m


def test_slow_request_one_trace_id_everywhere(events_file, registry):
    """ISSUE acceptance: a slow request's trace_id correlates the request
    event, the tail-sampled spans, the histogram exemplar, and the
    caller's future."""
    from mmlspark_tpu.serve import Server

    config.set("observability.trace_slow_ms", 5.0)
    config.set("observability.metrics", True)
    clock = _adv_ticker()
    try:
        srv = Server({"mlp": _make_model()}, max_batch=4, clock=clock,
                     start=False)
        fut = srv.submit_async("mlp", np.zeros(8, np.float32))
        clock.advance(0.05)                 # 50ms queued >= 5ms threshold
        srv.close(drain=True)
        assert fut.result(0).shape == (1, 3)
    finally:
        config.unset("observability.trace_slow_ms")
        config.unset("observability.metrics")

    tid = fut.trace_id
    assert tid.startswith("t-")
    evs = _load(events_file)
    req, = [e for e in evs if e.get("name") == "request"]
    assert req["slow"] is True and req["trace_id"] == tid

    # the programs jax built on the way have spans too (``compile:<name>``,
    # observability/compiles.py): not this request's
    sp = [e for e in evs if e["type"] == "span"
          and not e["name"].startswith("compile:")]
    assert {e["name"] for e in sp} == \
        {"serve:request", "serve:queue", "serve:pad", "serve:compute"}
    assert all(e["attrs"]["trace_id"] == tid for e in sp)
    root, = [e for e in sp if e["name"] == "serve:request"]
    assert root["parent_id"] is None and root["depth"] == 0
    assert root["dur_s"] == pytest.approx(0.05)
    kids = [e for e in sp if e["name"] != "serve:request"]
    assert all(k["parent_id"] == root["span_id"] for k in kids)
    queue, = [e for e in sp if e["name"] == "serve:queue"]
    assert queue["dur_s"] == pytest.approx(0.05)   # all the time was queue

    # exemplar: /metrics points at the exact slow request
    dump = registry.to_dict()
    assert dump["serving.total_ms"]["exemplar"]["trace_id"] == tid
    assert dump["serving.queue_ms"]["exemplar"]["trace_id"] == tid

    # the synthetic timeline exports as a valid nested trace
    assert validate_trace(build_trace(evs)) == []
    # and the report lists the tail-sampled trace id
    rep = build_report(events_file)
    assert rep["serving"]["slow_traces"][0]["trace_id"] == tid


def test_fast_request_is_not_tail_sampled(events_file):
    from mmlspark_tpu.serve import Server

    config.set("observability.trace_slow_ms", 10_000.0)
    try:
        srv = Server({"mlp": _make_model()}, max_batch=4,
                     clock=_adv_ticker(), start=False)
        fut = srv.submit_async("mlp", np.zeros(8, np.float32))
        srv.close(drain=True)
        fut.result(0)
    finally:
        config.unset("observability.trace_slow_ms")
    evs = _load(events_file)
    req, = [e for e in evs if e.get("name") == "request"]
    assert req["slow"] is False and req["trace_id"].startswith("t-")
    assert [e for e in evs if e["type"] == "span"
            and not e["name"].startswith("compile:")] == []  # no span detail


def test_shed_and_expired_events_carry_trace_id(events_file):
    from mmlspark_tpu.serve import RequestExpired, Server, ServerOverloaded

    srv = Server({"mlp": _make_model()}, queue_depth=1, start=False)
    srv.submit_async("mlp", np.zeros(8, np.float32))
    with pytest.raises(ServerOverloaded):
        srv.submit_async("mlp", np.zeros(8, np.float32))
    srv.close(drain=False)

    clock = _adv_ticker()
    srv2 = Server({"mlp": _make_model()}, clock=clock, start=False)
    late = srv2.submit_async("mlp", np.zeros(8, np.float32),
                             deadline_ms=1.0)
    clock.advance(1.0)
    srv2.close(drain=True)
    with pytest.raises(RequestExpired):
        late.result(0)

    evs = _load(events_file)
    shed, = [e for e in evs if e.get("name") == "shed"]
    assert shed["trace_id"].startswith("t-")
    expired, = [e for e in evs if e.get("name") == "expired"]
    assert expired["trace_id"] == late.trace_id


# ------------------------------------------------------------ exposition
def test_escape_label_value_per_exposition_format():
    assert obsmetrics.escape_label_value('a"b') == 'a\\"b'
    assert obsmetrics.escape_label_value("a\\b") == "a\\\\b"
    assert obsmetrics.escape_label_value("a\nb") == "a\\nb"
    # backslash escaped FIRST, or the quote escape gets double-escaped
    assert obsmetrics.escape_label_value('\\"') == '\\\\\\"'
    assert obsmetrics.escape_label_value(123) == "123"


def test_histogram_exemplar_last_wins(registry):
    h = registry.histogram("lat_ms")
    h.observe(1.0)
    assert h.exemplar is None
    h.observe(2.0, exemplar="t-aa-1")
    h.observe(3.0, exemplar="t-aa-2")
    h.observe(4.0)                      # no exemplar: keeps the last one
    assert h.exemplar == {"trace_id": "t-aa-2", "value": 3.0}
    assert registry.to_dict()["lat_ms"]["exemplar"]["trace_id"] == "t-aa-2"


def test_prometheus_buckets_cumulative_and_parseable(registry):
    h = registry.histogram("q", buckets=[0.1, 1.0, 10.0])
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    text = registry.prometheus_text()
    buckets = []
    for line in text.splitlines():
        if line.startswith("q_bucket{"):
            label, value = line.rsplit(" ", 1)
            buckets.append(int(value))
            assert label.count('"') == 2          # le="..." stays quoted
    assert buckets == sorted(buckets)             # cumulative: monotone
    assert buckets[-1] == 4                       # +Inf == count
    assert "q_count 4" in text
    assert 'le="+Inf"' in text


def test_sanitize_metric_names():
    assert obsmetrics.sanitize("serving.total_ms") == "serving_total_ms"
    assert obsmetrics.sanitize("9lives") == "_9lives"


# ------------------------------------------------------------ bench gate
def _lane(value=100.0, step_ms=10.0, mfu=0.5):
    return {"value": value, "unit": "rows/sec", "vs_baseline": 1.0,
            "step_ms": step_ms, "mfu": mfu}


def _line(**lanes):
    head = next(iter(lanes.values()))
    return {"metric": "bench", "value": head.get("value", 0),
            "unit": head.get("unit", "u"),
            "vs_baseline": head.get("vs_baseline", 1.0), "configs": lanes}


def test_gate_green_on_parity():
    v = compare(_line(train=_lane()), _line(train=_lane()))
    assert v["green"] is True and v["red"] == []
    assert v["lanes"]["train"]["status"] == "green"
    assert [c["metric"] for c in v["lanes"]["train"]["checks"]] == \
        ["value", "step_ms", "mfu"]


def test_gate_red_on_20pct_step_time_regression():
    v = compare(_line(train=_lane(step_ms=12.0)), _line(train=_lane()))
    assert v["green"] is False and v["red"] == ["train"]
    reasons = v["lanes"]["train"]["reasons"]
    assert len(reasons) == 1 and "step_ms" in reasons[0]


def test_gate_red_on_value_or_mfu_drop_green_on_improvement():
    base = _line(train=_lane())
    assert compare(_line(train=_lane(value=80.0)), base)["red"] == ["train"]
    assert compare(_line(train=_lane(mfu=0.4)), base)["red"] == ["train"]
    # faster + higher throughput is never a regression
    better = _lane(value=150.0, step_ms=7.0, mfu=0.8)
    assert compare(_line(train=better), base)["green"] is True
    # within tolerance (5% slower at 10% tolerance) stays green
    assert compare(_line(train=_lane(step_ms=10.5)), base)["green"] is True


def test_gate_skipped_lanes_never_red():
    base = _line(train=_lane(), eval={"skipped": True, "reason": "budget"})
    fresh = _line(train={"skipped": True, "reason": "terminated"},
                  extra=_lane())
    v = compare(fresh, base)
    assert v["green"] is True and v["red"] == []
    assert v["lanes"]["train"]["status"] == "skipped"      # fresh skipped
    assert v["lanes"]["eval"]["status"] == "skipped"       # baseline skipped
    assert v["lanes"]["extra"]["status"] == "skipped"      # no baseline lane
    assert sorted(v["skipped"]) == ["eval", "extra", "train"]


def test_gate_missing_fields_skip_that_check_only():
    base = _line(train={"value": 100.0, "unit": "u", "vs_baseline": 1.0})
    v = compare(_line(train=_lane(value=95.0)), base)
    assert v["green"] is True                  # no step_ms/mfu to compare
    assert [c["metric"] for c in v["lanes"]["train"]["checks"]] == ["value"]


def test_gate_ttft_p99_gated_lower_is_better():
    base = _line(decode=dict(_lane(), ttft_p99_ms=50.0))
    # 20% higher tail TTFT is a regression
    v = compare(_line(decode=dict(_lane(), ttft_p99_ms=60.0)), base)
    assert v["red"] == ["decode"]
    assert any("ttft_p99_ms" in r for r in v["lanes"]["decode"]["reasons"])
    # lower tail TTFT is never a regression
    v = compare(_line(decode=dict(_lane(), ttft_p99_ms=30.0)), base)
    assert v["green"] is True


def test_gate_prefix_and_spec_rates_informational_never_red():
    base = _line(decode=dict(_lane(), prefix_hit_rate=0.99,
                             spec_accept_rate=1.0))
    # a cache-defeating change craters both rates — reported, not red
    fresh = _line(decode=dict(_lane(), prefix_hit_rate=0.05,
                              spec_accept_rate=0.1))
    v = compare(fresh, base)
    assert v["green"] is True
    info = {c["metric"]: c for c in v["lanes"]["decode"]["checks"]
            if c.get("informational")}
    assert info["prefix_hit_rate"]["ok"] is True
    assert info["spec_accept_rate"]["fresh"] == 0.1


def test_percentile_from_buckets_ex_reports_overflow_clip():
    # rank lands inside a finite bucket: interpolated, not clipped
    cum = {"0.1": 50, "0.5": 90, "+Inf": 100}
    v, clipped = obsmetrics.percentile_from_buckets_ex(cum, 50)
    assert 0.0 < v <= 0.5 and clipped is False
    assert v == obsmetrics.percentile_from_buckets(cum, 50)
    # rank in the +Inf overflow: the highest finite bound is a FLOOR
    v, clipped = obsmetrics.percentile_from_buckets_ex(cum, 99)
    assert v == 0.5 and clipped is True
    # empty histogram: zero, and honestly not clipped
    assert obsmetrics.percentile_from_buckets_ex({}, 99) == (0.0, False)


def test_clipped_predicate_exact_deadline_equality_only():
    from mmlspark_tpu.observability.benchgate import clipped
    lane = {"spike_p99_ms": 90000.0, "deadline_ms": 90000.0}
    assert clipped(lane, "spike_p99_ms") is True
    # an honest open-loop measurement ABOVE the deadline is a real (bad)
    # number, not a clip — gating it is the whole point
    assert clipped({"arrival_p99_ms": 210000.0, "deadline_ms": 90000.0},
                   "arrival_p99_ms") is False
    assert clipped({"arrival_p99_ms": 100.0, "deadline_ms": 90000.0},
                   "arrival_p99_ms") is False
    # the explicit flag wins even without a deadline field
    assert clipped({"ttft_p99_ms": 5.0, "ttft_p99_ms_clipped": True},
                   "ttft_p99_ms") is True
    assert clipped({"spike_p99_ms": 100.0}, "spike_p99_ms") is False


def test_gate_fresh_clipped_against_unclipped_baseline_is_red():
    base = _line(ap=dict(_lane(), spike_p99_ms=40000.0,
                         deadline_ms=90000.0))
    fresh = _line(ap=dict(_lane(), spike_p99_ms=90000.0,
                          deadline_ms=90000.0))
    v = compare(fresh, base)
    assert v["red"] == ["ap"]
    assert any("clipped at the deadline" in r
               for r in v["lanes"]["ap"]["reasons"])


def test_gate_clipped_vs_clipped_is_never_parity_evidence():
    # the r08 blind spot: 90000 vs 90000 proves nothing — the check is
    # demoted to informational with the refusal spelled out
    lane = dict(_lane(), spike_p99_ms=90000.0, deadline_ms=90000.0)
    v = compare(_line(ap=dict(lane)), _line(ap=dict(lane)))
    assert v["green"] is True
    c = {c["metric"]: c for c in v["lanes"]["ap"]["checks"]}
    sp = c["spike_p99_ms"]
    assert sp["informational"] is True
    assert sp["clipped"] is True and sp["baseline_clipped"] is True
    assert "not parity evidence" in sp["note"]


def test_gate_legacy_closed_loop_baseline_is_informational():
    # an r08-era lane: spike_p99_ms but no deadline_ms/arrival_p99_ms —
    # its latency cannot even be tested for clipping, so the transition
    # to the open-loop driver can never false-fail against it
    base = _line(ap=dict(_lane(), spike_p99_ms=90000.0))
    fresh = _line(ap=dict(_lane(), spike_p99_ms=170000.0,
                          deadline_ms=90000.0, arrival_p99_ms=170000.0))
    v = compare(fresh, base)
    assert v["green"] is True
    c = {c["metric"]: c for c in v["lanes"]["ap"]["checks"]}
    assert c["spike_p99_ms"]["informational"] is True
    assert "legacy closed-loop" in c["spike_p99_ms"]["note"]


def test_gate_goodput_and_arrival_p99_are_gated_fields():
    base = _line(sv=dict(_lane(), goodput=0.95, arrival_p99_ms=100.0,
                         deadline_ms=250.0))
    # goodput is higher-is-better
    v = compare(_line(sv=dict(_lane(), goodput=0.5, arrival_p99_ms=100.0,
                              deadline_ms=250.0)), base)
    assert v["red"] == ["sv"]
    assert any("goodput" in r for r in v["lanes"]["sv"]["reasons"])
    # arrival_p99_ms is lower-is-better, un-clipped values gate normally
    v = compare(_line(sv=dict(_lane(), goodput=0.95,
                              arrival_p99_ms=200.0, deadline_ms=250.0)),
                base)
    assert v["red"] == ["sv"]
    assert any("arrival_p99_ms" in r for r in v["lanes"]["sv"]["reasons"])
    # improvements on both axes stay green
    v = compare(_line(sv=dict(_lane(), goodput=0.99, arrival_p99_ms=50.0,
                              deadline_ms=250.0)), base)
    assert v["green"] is True


def test_gate_latency_noise_guards_absorb_sub_jitter_rises_only():
    def sv(**kw):
        return _line(sv=dict(_lane(), goodput=1.0, deadline_ms=250.0, **kw))

    # resolution floor: +4.5 ms on a 40 ms p99 fails the 10% ratio but
    # is beneath what the host can resolve (and 44.5 ms is outside the
    # 25 ms deep-headroom band, so the floor is what saves it)
    v = compare(sv(arrival_p99_ms=44.5), sv(arrival_p99_ms=40.0))
    assert v["green"] is True
    c = next(c for c in v["lanes"]["sv"]["checks"]
             if c["metric"] == "arrival_p99_ms")
    assert c["ok"] and c["floor_ms"] == 5.0
    # past the floor and outside the headroom band the ratio gate bites
    v = compare(sv(arrival_p99_ms=48.0), sv(arrival_p99_ms=40.0))
    assert v["red"] == ["sv"]
    # deep headroom: 8 -> 19 ms under a 250 ms deadline is host noise
    # far from the knee (both sides within 10% of the deadline)
    v = compare(sv(arrival_p99_ms=19.0), sv(arrival_p99_ms=8.0))
    assert v["green"] is True
    c = next(c for c in v["lanes"]["sv"]["checks"]
             if c["metric"] == "arrival_p99_ms")
    assert c["ok"] and c["headroom_ms"] == 25.0
    # crossing OUT of the band still reds
    v = compare(sv(arrival_p99_ms=30.0), sv(arrival_p99_ms=8.0))
    assert v["red"] == ["sv"]
    # the guards are for tail percentiles only: a small absolute
    # step_ms rise (a mean, where 2 ms IS signal) and a throughput
    # drop both stay red
    v = compare(sv(arrival_p99_ms=8.0, step_ms=12.0),
                sv(arrival_p99_ms=8.0))
    assert v["red"] == ["sv"]
    v = compare(sv(arrival_p99_ms=8.0, value=80.0), sv(arrival_p99_ms=8.0))
    assert v["red"] == ["sv"]


def test_load_baseline_accepts_wrapper_and_raw_forms(tmp_path):
    raw = _line(train=_lane())
    p_raw = tmp_path / "raw.json"
    p_raw.write_text(json.dumps(raw))
    p_wrap = tmp_path / "wrap.json"
    p_wrap.write_text(json.dumps({"n": 5, "rc": 0, "parsed": raw}))
    assert load_baseline(str(p_raw)) == load_baseline(str(p_wrap)) == raw
    p_bad = tmp_path / "bad.json"
    p_bad.write_text(json.dumps({"hello": 1}))
    with pytest.raises(ValueError):
        load_baseline(str(p_bad))


def test_gate_against_committed_baseline_is_self_parity():
    baseline = load_baseline(os.path.join(REPO, "BENCH_r05.json"))
    v = gate(baseline, os.path.join(REPO, "BENCH_r05.json"))
    assert v["green"] is True and v["red"] == []
    assert v["baseline"].endswith("BENCH_r05.json")
    assert "train" in v["lanes"]


def test_bench_baseline_gate_exit_codes(tmp_path, monkeypatch, capsys):
    """End to end through bench.py's main(): exit 0 on parity, 2 on an
    injected 20% step-time regression, verdict as the second JSON line."""
    import signal

    spec = importlib.util.spec_from_file_location(
        "bench_gate_under_test", os.path.join(REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    lane = _lane()
    bp = tmp_path / "BENCH_base.json"
    bp.write_text(json.dumps({"n": 1, "rc": 0, "parsed": _line(train=lane)}))

    prev = signal.getsignal(signal.SIGTERM)
    monkeypatch.setattr(sys, "argv", ["bench.py", "--configs", "train",
                                      "--baseline", str(bp)])
    # main() turns the compile cache on: place it from outside, or this
    # test process stays bound to <checkout>/.jax_cache for every later test
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    try:
        monkeypatch.setattr(bench, "CONFIGS", {"train": lambda: dict(lane)})
        assert bench.main() == 0
        line, verdict = map(json.loads,
                            capsys.readouterr().out.strip().splitlines())
        assert line["configs"]["train"]["value"] == 100.0
        assert verdict["green"] is True

        slow = dict(lane, step_ms=12.0)
        monkeypatch.setattr(bench, "CONFIGS", {"train": lambda: dict(slow)})
        assert bench.main() == 2
        line2, verdict2 = map(json.loads,
                              capsys.readouterr().out.strip().splitlines())
        assert verdict2["green"] is False and verdict2["red"] == ["train"]
        assert verdict2["lanes"]["train"]["reasons"]
    finally:
        # bench.main leaves SIGTERM ignored (its epilogue guard); restore
        signal.signal(signal.SIGTERM, prev)
