"""Per-step spans and counts inside the trainer and its input path.

``spans.hot_spans`` is the entry point for spans that fire once per step or
per batch: it gives their constructor with ``observability.annotate`` or the
event log on, and ``None`` for the flight recorder alone. Covered here: the gate off (nothing allocated,
nothing emitted, the recorder's ring untouched after 300 steps), the chain
of one step under ``fit`` (``input:produce`` -> ``input:wait`` ->
``input:put`` -> ``trainer:dispatch``, joined by ``batch`` == ``step``, all
under ``trainer:fit``), the three unconditional counters, and that ``fit``
with ``observability.metrics`` on compiles the step once.
"""
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from mmlspark_tpu.data.prefetch import DevicePrefetcher
from mmlspark_tpu.observability import events, flightrec
from mmlspark_tpu.observability import metrics as obsmetrics
from mmlspark_tpu.observability import spans
from mmlspark_tpu.parallel.mesh import mesh_from_config
from mmlspark_tpu.parallel.trainer import DistributedTrainer
from mmlspark_tpu.utils import config

ROWS = 8
BATCH_BYTES = ROWS * 3 * 4 + ROWS * 4        # float32 x (8, 3) and y (8,)


def _trainer(devices=None):
    def loss_fn(params, batch, rng):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)

    trainer = DistributedTrainer(
        loss_fn, optax.sgd(0.1),
        mesh=mesh_from_config(devices) if devices else None)
    state = trainer.init(lambda: {"w": jnp.zeros((3,), jnp.float32)})
    return trainer, state


def _batches(n):
    rng = np.random.default_rng(0)
    return [{"x": rng.normal(size=(ROWS, 3)).astype(np.float32),
             "y": np.ones((ROWS,), np.float32)} for _ in range(n)]


@pytest.fixture
def events_file(tmp_path):
    path = str(tmp_path / "events.jsonl")
    config.set("observability.events_path", path)
    try:
        yield path
    finally:
        events.close()
        config.unset("observability.events_path")


def _spans(path):
    if not os.path.exists(path):
        return [], []
    with open(path) as f:
        lines = [json.loads(l) for l in f if l.strip()]
    return [e for e in lines if e["type"] == "span"], lines


# ------------------------------------------------------------- the gate
def test_gate_is_annotate_or_event_log_never_the_recorder_alone(tmp_path):
    assert flightrec.active() and events.recording_enabled()
    assert spans.hot_spans() is None
    assert spans.span("fit", "Cold") is not spans.NOOP   # cold spans record
    for key, value in (("observability.annotate", True),
                       ("observability.events_path",
                        str(tmp_path / "e.jsonl"))):
        config.set(key, value)
        try:
            made = spans.hot_spans()("trainer", "dispatch", step=1)
            assert (made.name, made.attrs) == \
                ("trainer:dispatch", {"step": 1})
        finally:
            events.close()
            config.unset(key)


def test_gate_off_300_steps_allocate_no_span_and_leave_the_ring_alone():
    # one device and a ring longer than the run: no throttle flush, whose
    # (cold, counted) sync.point event would be the only thing recorded
    config.set("train.metrics_flush_steps", 512)
    try:
        trainer, state = _trainer(jax.devices()[:1])
        batch = trainer.put_batch(_batches(1)[0])
        rng = jax.random.PRNGKey(0)
        state, _ = trainer.train_step(state, batch, rng)     # compiles
        feed = DevicePrefetcher(iter(_batches(300)), trainer.put_batch)
        ring_before = flightrec.snapshot()
        ids_before = spans.next_span_id()
        for _ in range(300):
            state, _ = trainer.train_step(state, batch, rng)
            next(feed)
        with pytest.raises(StopIteration):
            next(feed)
        assert spans.next_span_id() == ids_before + 1        # none minted
        assert flightrec.snapshot() == ring_before
        jax.block_until_ready(state)
    finally:
        config.unset("train.metrics_flush_steps")


def test_a_dropped_span_emits_nothing_and_unwinds_the_stack(events_file):
    hot = spans.hot_spans()
    with hot("input", "produce", batch=0) as sp:
        sp.drop()
    assert spans.current_span() is None
    with hot("input", "produce", batch=1):
        pass
    names = [(e["name"], e["attrs"]) for e in _spans(events_file)[0]]
    assert names == [("input:produce", {"batch": 1})]


# ------------------------------------------------- one step's chain in fit
def test_fit_emits_one_chain_per_step_joined_by_batch_and_step(
        events_file, monkeypatch):
    threads = {}
    real_emit = events.emit

    def emit(etype, name, **fields):
        threads.setdefault(name, set()).add(threading.current_thread().name)
        real_emit(etype, name, **fields)

    monkeypatch.setattr(events, "emit", emit)
    n = 20                       # crosses one throttle flush (every 16)
    trainer, state = _trainer()
    trainer.fit(state, iter(_batches(n)), prefetch=3)
    found, lines = _spans(events_file)
    by_name = {}
    for e in found:
        by_name.setdefault(e["name"], []).append(e)
    (fit,) = by_name["trainer:fit"]
    assert fit["attrs"] == {"prefetch": 3} and fit["depth"] == 0

    dispatch = by_name["trainer:dispatch"]
    assert [e["attrs"]["step"] for e in dispatch] == list(range(n))
    assert all(e["attrs"]["donate"] is True for e in dispatch)
    for kind in ("input:wait", "input:put", "input:produce"):
        assert sorted(e["attrs"]["batch"] for e in by_name[kind]) == \
            list(range(n)), kind
    assert {e["attrs"]["bytes"] for e in by_name["input:put"]} == \
        {BATCH_BYTES}
    # the consumer's order: wait k, put k, dispatch k
    consumer = [(e["name"], e["attrs"].get("batch", e["attrs"].get("step")))
                for e in found if e["name"] in (
                    "input:wait", "input:put", "trainer:dispatch")]
    assert consumer == [(name, k) for k in range(n) for name in (
        "input:wait", "input:put", "trainer:dispatch")]
    (flush,) = by_name["trainer:flush"]
    assert flush["attrs"] == {"steps": 16}

    # every per-step span is a child of trainer:fit, the producer's too
    for name in ("trainer:dispatch", "trainer:flush", "input:wait",
                 "input:put", "input:produce"):
        assert {(e["parent_id"], e["parent"], e["depth"])
                for e in by_name[name]} == \
            {(fit["span_id"], "trainer:fit", 1)}, name
    assert threads["input:produce"] == {"mmlspark-tpu-prefetch"}
    assert threads["trainer:dispatch"] == threads["input:put"] == \
        threads["input:wait"] == {threading.current_thread().name}

    (fit_event,) = [e for e in lines if e["name"] == "train.fit"]
    assert fit_event["steps"] == n and fit_event["rows"] == n * ROWS
    assert "mfu" not in fit_event


def test_step_restarts_with_each_fit_and_runs_on_for_direct_callers(
        events_file):
    trainer, state = _trainer()
    state, _ = trainer.fit(state, iter(_batches(3)))
    state, _ = trainer.fit(state, iter(_batches(2)))
    batch = trainer.put_batch(_batches(1)[0])
    for _ in range(2):
        state, _ = trainer.train_step(state, batch, jax.random.PRNGKey(0))
    steps = [(e["attrs"]["step"], e["attrs"]["donate"])
             for e in _spans(events_file)[0]
             if e["name"] == "trainer:dispatch"]
    assert steps == [(0, True), (1, True), (2, True), (0, True), (1, True),
                     (2, False), (3, False)]


def test_a_trainer_built_with_the_gate_off_stays_silent_until_a_fit(
        tmp_path):
    trainer, state = _trainer()
    batch = trainer.put_batch(_batches(1)[0])
    state, _ = trainer.train_step(state, batch, jax.random.PRNGKey(0))
    path = str(tmp_path / "late.jsonl")
    config.set("observability.events_path", path)
    try:
        # resolved at the first step: a direct caller pays no lookup
        state, _ = trainer.train_step(state, batch, jax.random.PRNGKey(0))
        assert _spans(path)[0] == []
        trainer.fit(state, iter(_batches(2)))       # a fit resolves anew
        names = [e["name"] for e in _spans(path)[0]]
        assert names.count("trainer:dispatch") == 2
    finally:
        events.close()
        config.unset("observability.events_path")


# ------------------------------------------------------------- the counts
def test_counters_count_with_every_sink_off():
    names = ("trainer.steps_dispatched", "input.batches_put",
             "input.bytes_put")
    before = [obsmetrics.counter(n).value for n in names]
    trainer, state = _trainer()
    trainer.fit(state, iter(_batches(7)))
    after = [obsmetrics.counter(n).value for n in names]
    assert [a - b for a, b in zip(after, before)] == \
        [7, 7, 7 * BATCH_BYTES]


def test_prefetcher_counts_nested_batches_and_numbers_them_from_zero(
        events_file):
    items = [{"a": np.zeros((4, 2), np.float32),
              "b": [np.zeros((4,), np.int32), (np.zeros((3,), np.uint8),)]}
             for _ in range(3)]
    before = obsmetrics.counter("input.bytes_put").value
    out = list(DevicePrefetcher(iter(items), lambda b: b, depth=1))
    assert len(out) == 3
    assert obsmetrics.counter("input.bytes_put").value - before == \
        3 * (32 + 16 + 3)
    puts = [e["attrs"] for e in _spans(events_file)[0]
            if e["name"] == "input:put"]
    assert puts == [{"batch": k, "bytes": 51} for k in range(3)]


# ------------------------------------- no second trace or compile in fit
def test_fit_with_metrics_on_traces_and_compiles_the_step_once():
    # the MFU gauge this replaces lowered the step a second time inside the
    # loop: on this jax one more trace (its lowering and compile were
    # served from jax's in-memory caches), so every compile-path event of
    # jax's counts here, not backend compiles alone
    seen = []

    def listener(name, secs, **_kw):
        if name.startswith("/jax/core/compile/"):
            seen.append(name)

    def fit_once():
        start = len(seen)
        trainer, state = _trainer()
        trainer.fit(state, iter(_batches(4)))
        return sorted(seen[start:])

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        fit_once()                       # helper programs compile here
        off = fit_once()
        config.set("observability.metrics", True)
        try:
            on = fit_once()
        finally:
            config.unset("observability.metrics")
            obsmetrics.get_registry().reset()
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert "/jax/core/compile/backend_compile_duration" in off
    assert on == off


# ---------------------------------------------- the start: init, first step
def _start_spans(path):
    return [e for e in _spans(path)[0]
            if e["name"] in ("trainer:init", "trainer:first_step")
            or e["name"].startswith("compile:jit_")]


def test_init_once_and_first_step_once_a_variant_and_on_no_later_step(
        events_file):
    from mmlspark_tpu.observability import compiles
    compiles.clear()
    for name in ("trainer.init_s", "trainer.first_step_s"):
        obsmetrics.gauge(name).set(-1.0)
    trainer, state = _trainer()
    batch = trainer.put_batch(_batches(1)[0])
    for _ in range(3):
        state, _ = trainer.train_step(state, batch, jax.random.PRNGKey(0))
    first_variant_s = obsmetrics.gauge("trainer.first_step_s").value
    state, _ = trainer.fit(state, iter(_batches(3)))    # the donating one
    found = _start_spans(events_file)
    (init,) = [e for e in found if e["name"] == "trainer:init"]
    firsts = [e for e in found if e["name"] == "trainer:first_step"]
    assert [e["attrs"] for e in firsts] == [{"donate": False},
                                            {"donate": True}]
    assert init["depth"] == 0 and "attrs" not in init
    # each the one child of its variant's first trainer:dispatch
    dispatches = {e["span_id"]: e for e in _spans(events_file)[0]
                  if e["name"] == "trainer:dispatch"}
    parents = [dispatches[e["parent_id"]]["attrs"] for e in firsts]
    assert parents == [{"step": 0, "donate": False},
                       {"step": 0, "donate": True}]
    # and the programs jax built lie under the span that waited for them
    under = {e["name"]: e["parent"] for e in found
             if e["name"].startswith("compile:")}
    assert under["compile:jit_full_init"] == "trainer:init"
    assert under["compile:jit_step"] == "trainer:first_step"
    steps = [e for e in found if e["name"] == "compile:jit_step"]
    assert [e["parent_id"] for e in steps] == \
        [e["span_id"] for e in firsts]
    assert compiles.first("jit_step").parent == "trainer:first_step"
    # both gauges set, the first step's by the first variant alone
    assert obsmetrics.gauge("trainer.init_s").value == pytest.approx(
        init["dur_s"], abs=5e-3)
    assert first_variant_s == pytest.approx(firsts[0]["dur_s"], abs=5e-3)
    assert obsmetrics.gauge("trainer.first_step_s").value == \
        first_variant_s
    assert first_variant_s >= compiles.first("jit_step").backend_s > 0


def test_first_step_spans_the_aux_keys_retrace_once(events_file):
    from mmlspark_tpu.observability import compiles
    compiles.clear()
    traces = []

    def listener(name, secs, fun_name="", **_kw):
        if name.endswith("jaxpr_trace_duration") and fun_name == "step":
            traces.append(secs)

    def loss_fn(params, batch, rng):
        loss = jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)
        return loss, {"test.half_loss": loss / 2}

    trainer = DistributedTrainer(loss_fn, optax.sgd(0.1))
    state = trainer.init(lambda: {"w": jnp.zeros((3,), jnp.float32)})
    batch = trainer.put_batch(_batches(1)[0])
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        for _ in range(3):
            state, m = trainer.train_step(state, batch,
                                          jax.random.PRNGKey(0))
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert "test.half_loss" in m and len(traces) == 2     # _AuxKeys, again
    found = _start_spans(events_file)
    (first,) = [e for e in found if e["name"] == "trainer:first_step"]
    (step,) = [e for e in found if e["name"] == "compile:jit_step"]
    assert step["parent_id"] == first["span_id"]
    row = compiles.first("jit_step")
    assert row.trace_s == pytest.approx(sum(traces))      # both traces
    assert first["dur_s"] >= row.total_s * 0.99
    assert first["start"] <= step["start"]


def test_gauges_are_set_with_every_sink_off():
    config.set("observability.flight_recorder_size", 0)
    try:
        assert spans.span("trainer", "init") is spans.NOOP
        for name in ("trainer.init_s", "trainer.first_step_s"):
            obsmetrics.gauge(name).set(-1.0)
        trainer, state = _trainer()
        assert obsmetrics.gauge("trainer.init_s").value > 0
        assert obsmetrics.gauge("trainer.first_step_s").value == -1.0
        batch = trainer.put_batch(_batches(1)[0])
        trainer.train_step(state, batch, jax.random.PRNGKey(0))
        assert obsmetrics.gauge("trainer.first_step_s").value > 0
    finally:
        config.unset("observability.flight_recorder_size")


def test_a_later_step_pays_one_boolean_and_builds_nothing_of_the_start(
        monkeypatch):
    from mmlspark_tpu.parallel import trainer as trainer_module
    config.set("train.metrics_flush_steps", 512)
    try:
        trainer, state = _trainer(jax.devices()[:1])
        batch = trainer.put_batch(_batches(1)[0])
        rng = jax.random.PRNGKey(0)
        assert trainer._starting is False        # nothing built yet
        state, _ = trainer.train_step(state, batch, rng)
        assert trainer._starting is False        # taken back by that call

        def never(*_a, **_kw):
            raise AssertionError("a later step looked something up")

        # everything the start's spans are made of, and every lookup a
        # per-step path may not make: the config, the registry, the clock
        monkeypatch.setattr(trainer_module, "_StartSpan", never)
        monkeypatch.setattr(spans, "span", never)
        monkeypatch.setattr(spans, "hot_spans", never)
        monkeypatch.setattr(events, "perf", never)
        monkeypatch.setattr(events, "wall", never)
        monkeypatch.setattr(obsmetrics, "gauge", never)
        monkeypatch.setattr(obsmetrics, "counter", never)
        monkeypatch.setattr(config, "get", never)
        ids = spans.next_span_id()
        for _ in range(50):
            state, _ = trainer.train_step(state, batch, rng)
        assert spans.next_span_id() == ids + 1
        jax.block_until_ready(state)
    finally:
        config.unset("train.metrics_flush_steps")
