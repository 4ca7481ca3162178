"""The program's ledger of what jax compiled
(``observability/compiles.py``): one row a program with its three stages,
what the persistent cache said of it and the span that waited for it.

Covered here: a jitted function gives one row under the name a device
trace shows; traces inside a trace add nothing; a program built inside
another's trace is taken off that trace; with a persistent cache, a build
then a load (``miss`` then ``hit`` with the retrieval's seconds); two
threads compiling at once keep their outcomes apart; the counters' deltas
equal the rows; the retroactive ``compile:<name>`` span carries the open
span as its parent; the deque is bounded; ``install()`` twice registers
once; stages whose start nobody heard still make a row.
"""
import json
import threading

import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring as jax_monitoring

from mmlspark_tpu.observability import compiles, events, flightrec
from mmlspark_tpu.observability import metrics as obsmetrics
from mmlspark_tpu.observability import spans
from mmlspark_tpu.utils import config

COUNTERS = ("compile.programs", "compile.trace_s", "compile.lower_s",
            "compile.backend_s", "compile.cache_hits",
            "compile.cache_misses")


@pytest.fixture
def ledger():
    compiles.install()
    compiles.clear()
    try:
        yield compiles
    finally:
        compiles.clear()


def _counts():
    return {n: obsmetrics.counter(n).value for n in COUNTERS}


def _named(name):
    return [r for r in compiles.rows() if r.name == name]


# ------------------------------------------------------------ one program
def test_a_jitted_function_gives_one_row_with_three_stages(ledger):
    def step_of_this_test(x):
        return jnp.tanh(x @ x) + jnp.sin(x)

    x = jnp.ones((8, 8))
    before = events.wall()
    fn = jax.jit(step_of_this_test)
    fn(x)
    fn(x)                                  # the second call compiles nothing
    (row,) = _named("jit_step_of_this_test")
    assert row.trace_s > 0 and row.lower_s > 0 and row.backend_s > 0
    assert row.outcome == "uncached" and row.retrieval_s == 0
    assert row.parent == "" and before <= row.start <= events.wall()
    assert row.total_s == row.trace_s + row.lower_s + row.backend_s
    assert ledger.first("jit_step_of_this_test") is row
    assert ledger.first("jit_nobody_made_this") is None


@pytest.mark.parametrize("fun_name, name", [
    ("jit(step)", "jit_step"),
    ("jit(<lambda>)", "jit__lambda_"),
    ("pmap(f)", "pmap_f"),
    ("jit(_threefry_seed)", "jit__threefry_seed"),
    ("step", "step"),
])
def test_names_are_what_a_device_trace_shows(fun_name, name):
    assert compiles.program_name(fun_name) == name


def test_the_module_jax_lowers_has_the_rows_name(ledger):
    fn = jax.jit(lambda x: x + 1)
    text = fn.lower(jnp.ones(3)).as_text()
    assert "module @jit__lambda" in text
    fn(jnp.ones(3))
    assert _named("jit__lambda_") or _named("jit__lambda")


# ------------------------------------------------- stages inside stages
def _play(events_):
    """Feed the listeners a hand-written sequence: ``(kind, event,
    fun_name, secs)``, kind ``start`` / ``end`` / ``event``."""
    for kind, event, fun_name, secs in events_:
        if kind == "start":
            compiles._on_start(event, 0.0, fun_name=fun_name)
        elif kind == "end":
            compiles._on_duration(event, secs, fun_name=fun_name)
        else:
            compiles._on_event(event)


T, L, B = compiles._TRACE, compiles._LOWER, compiles._BACKEND
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"


def test_nested_traces_add_nothing_to_the_totals(ledger):
    before = _counts()
    _play([("start", T, "step", 0), ("start", T, "matmul", 0),
           ("end", T, "matmul", 0.25), ("start", T, "tanh", 0),
           ("end", T, "tanh", 0.25), ("end", T, "step", 1.0),
           ("start", L, "jit(step)", 0), ("end", L, "jit(step)", 2.0),
           ("start", B, "jit(step)", 0), ("end", B, "jit(step)", 4.0)])
    (row,) = ledger.rows()
    assert (row.name, row.trace_s, row.lower_s, row.backend_s) == \
        ("jit_step", 1.0, 2.0, 4.0)
    after = _counts()
    assert after["compile.trace_s"] - before["compile.trace_s"] == 1.0
    assert after["compile.programs"] - before["compile.programs"] == 1


def test_the_real_nested_traces_of_jax_add_nothing(ledger):
    seen = []

    def listener(name, secs, fun_name="", **_kw):
        if name == T:
            seen.append((fun_name, secs))

    def outer_of_this_test(x):
        return jnp.tanh(x @ x)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        jax.jit(outer_of_this_test)(jnp.ones((4, 4)))
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    by_name = dict(seen)
    assert {"matmul", "tanh"} <= set(by_name)      # jax did nest some
    (row,) = _named("jit_outer_of_this_test")
    assert row.trace_s == pytest.approx(by_name["outer_of_this_test"])


def test_both_traces_of_a_retraced_function_are_its_trace_s(ledger):
    # the trainer's step with aux scalars: traced, _AuxKeys, traced again
    _play([("start", T, "step", 0), ("end", T, "step", 1.0),
           ("start", T, "step", 0), ("end", T, "step", 1.5),
           ("start", L, "jit(step)", 0), ("end", L, "jit(step)", 0.5),
           ("start", B, "jit(step)", 0), ("end", B, "jit(step)", 0.5)])
    (row,) = ledger.rows()
    assert row.trace_s == 2.5


def test_a_program_built_inside_a_trace_is_taken_off_that_trace(ledger):
    _play([("start", T, "step", 0),
           ("start", T, "convert", 0), ("end", T, "convert", 0.125),
           ("start", L, "jit(convert)", 0), ("end", L, "jit(convert)", 0.25),
           ("start", B, "jit(convert)", 0), ("end", B, "jit(convert)", 0.5),
           ("end", T, "step", 2.0),
           ("start", L, "jit(step)", 0), ("end", L, "jit(step)", 1.0),
           ("start", B, "jit(step)", 0), ("end", B, "jit(step)", 1.0)])
    inner, outer = ledger.rows()
    # the inner trace is the outer's time; its lowering and build are not
    assert (inner.name, inner.trace_s, inner.lower_s, inner.backend_s) == \
        ("jit_convert", 0.0, 0.25, 0.5)
    assert (outer.name, outer.trace_s) == ("jit_step", 2.0 - 0.25 - 0.5)
    assert sum(r.total_s for r in (inner, outer)) == 2.0 + 1.0 + 1.0


def test_a_stage_whose_start_nobody_heard_still_makes_a_row(ledger):
    _play([("end", T, "late", 0.5), ("end", L, "jit(late)", 0.25),
           ("end", B, "jit(late)", 1.0)])
    (row,) = ledger.rows()
    assert (row.name, row.trace_s, row.lower_s, row.backend_s) == \
        ("jit_late", 0.5, 0.25, 1.0)


def test_a_stage_left_open_is_dropped_by_the_stage_around_it(ledger):
    _play([("start", L, "jit(f)", 0), ("start", T, "kernel_body", 0),
           ("end", L, "jit(f)", 0.5),          # the body's end never came
           ("start", B, "jit(f)", 0), ("end", B, "jit(f)", 0.5),
           ("start", T, "g", 0), ("end", T, "g", 0.25)])
    assert [r.name for r in ledger.rows()] == ["jit_f"]
    assert compiles._thread.stack == []
    # g's trace found no stage open: it is an outermost one, and waits
    assert compiles._thread.traced["g"][0] == 0.25


def test_names_traced_and_never_lowered_are_bounded(ledger):
    _play([ev for i in range(300) for ev in (
        ("start", T, f"f{i}", 0), ("end", T, f"f{i}", 0.001))])
    assert len(compiles._thread.traced) == compiles._MAX_OPEN
    assert "f299" in compiles._thread.traced


# ------------------------------------------------- the persistent cache
@pytest.fixture
def persistent_cache(tmp_path):
    """jax's cache in ``tmp_path`` for one test; restored after."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prior = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs,
             jax.config.jax_persistent_cache_min_entry_size_bytes)
    cc.reset_cache()
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        yield
    finally:
        cc.reset_cache()
        jax.config.update("jax_compilation_cache_dir", prior[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prior[1])
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          prior[2])


def test_a_build_is_a_miss_and_the_load_after_it_a_hit(ledger,
                                                      persistent_cache):
    def cached_of_this_test(x):
        return jnp.cos(x) * 3 + x

    before = _counts()
    x = jnp.ones((16,))
    jax.jit(cached_of_this_test)(x)
    jax.clear_caches()
    jax.jit(cached_of_this_test)(x)
    built, loaded = _named("jit_cached_of_this_test")
    assert (built.outcome, built.retrieval_s) == ("miss", 0.0)
    assert loaded.outcome == "hit" and loaded.retrieval_s > 0
    assert loaded.backend_s >= loaded.retrieval_s
    assert ledger.first("jit_cached_of_this_test") is built
    after = _counts()
    mine = ledger.rows()
    assert after["compile.cache_hits"] - before["compile.cache_hits"] == \
        sum(r.outcome == "hit" for r in mine) >= 1
    assert after["compile.cache_misses"] - before["compile.cache_misses"] \
        == sum(r.outcome == "miss" for r in mine) >= 1


def test_two_threads_compiling_at_once_keep_their_outcomes_apart(ledger):
    # thread A's backend stage is open while B's hits: neither leaks
    a_open, b_done = threading.Event(), threading.Event()

    def thread_a():
        _play([("start", B, "jit(a)", 0), ("event", MISS, "", 0)])
        a_open.set()
        b_done.wait(10)
        _play([("end", B, "jit(a)", 2.0)])

    def thread_b():
        a_open.wait(10)
        _play([("start", B, "jit(b)", 0), ("event", HIT, "", 0)])
        compiles._on_duration(compiles._RETRIEVAL, 0.25)
        _play([("end", B, "jit(b)", 0.5)])
        b_done.set()

    threads = [threading.Thread(target=t) for t in (thread_a, thread_b)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(20)
    rows = {r.name: r for r in ledger.rows()}
    assert (rows["jit_a"].outcome, rows["jit_a"].retrieval_s) == \
        ("miss", 0.0)
    assert (rows["jit_b"].outcome, rows["jit_b"].retrieval_s) == \
        ("hit", 0.25)
    # a hit with no backend stage open on its thread belongs to nobody
    _play([("event", HIT, "", 0), ("start", B, "jit(c)", 0),
           ("end", B, "jit(c)", 0.5)])
    assert ledger.first("jit_c").outcome == "uncached"


def test_real_threads_each_get_a_row_of_their_own(ledger):
    def work(k):
        def fn(x):
            return x * (k + 2) + k
        fn.__name__ = f"threaded_of_this_test_{k}"
        jax.jit(fn)(jnp.ones((k + 3,)))

    threads = [threading.Thread(target=work, args=(k,)) for k in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    for k in range(3):
        (row,) = _named(f"jit_threaded_of_this_test_{k}")
        assert row.backend_s > 0 and row.trace_s > 0


# ------------------------------------------------- counters and the span
def test_the_counters_deltas_equal_the_rows(ledger):
    before = _counts()
    jax.jit(lambda x: x * 5 - 2)(jnp.ones((5,)))
    jax.jit(lambda x: jnp.sum(x) / 7)(jnp.ones((6,)))
    rows = ledger.rows()
    delta = {n: v - before[n] for n, v in _counts().items()}
    assert delta["compile.programs"] == len(rows) >= 2
    for stage in ("trace_s", "lower_s", "backend_s"):
        assert delta["compile." + stage] == pytest.approx(
            sum(getattr(r, stage) for r in rows))


def test_the_retroactive_span_carries_the_open_span_as_parent(
        ledger, tmp_path):
    path = str(tmp_path / "events.jsonl")
    config.set("observability.events_path", path)
    try:
        with spans.span("trainer", "first_step") as live:
            jax.jit(lambda x: x * 11 + 3)(jnp.ones((7,)))
        jax.jit(lambda x: x * 13 + 5)(jnp.ones((9,)))    # at the root
    finally:
        events.close()
        config.unset("observability.events_path")
    with open(path) as f:
        found = [json.loads(l) for l in f if l.strip()]
    made = [e for e in found if e["name"].startswith("compile:")]
    under = [e for e in made if e["parent_id"] == live.span_id]
    assert under and all(
        (e["parent"], e["depth"], e["type"]) == ("trainer:first_step", 1,
                                                 "span") for e in under)
    last = under[-1]
    (row,) = [r for r in ledger.rows() if r.parent == "trainer:first_step"
              and r.name == last["name"][len("compile:"):]][-1:]
    assert last["dur_s"] == pytest.approx(row.total_s, abs=1e-6)
    assert last["start"] == pytest.approx(row.start, abs=1e-5)
    assert last["attrs"] == {"trace_s": round(row.trace_s, 6),
                             "lower_s": round(row.lower_s, 6),
                             "outcome": row.outcome}
    (live_event,) = [e for e in found if e["name"] == "trainer:first_step"]
    assert live_event["start"] <= last["start"] and \
        last["start"] + last["dur_s"] <= \
        live_event["start"] + live_event["dur_s"] + 1e-3
    root = [e for e in made if e["parent_id"] is None]
    assert root and root[-1]["depth"] == 0 and root[-1]["parent"] == ""
    assert len({e["span_id"] for e in found if e["type"] == "span"}) == \
        len([e for e in found if e["type"] == "span"])


def test_the_flight_recorder_alone_holds_the_span(ledger):
    assert flightrec.active() and not events.events_enabled()
    flightrec.clear()
    jax.jit(lambda x: x * 17 - 1)(jnp.ones((11,)))
    names = [e["name"] for e in flightrec.snapshot()
             if e["type"] == "span"]
    assert any(n.startswith("compile:jit_") for n in names)


def test_with_every_sink_off_a_row_and_no_event(ledger):
    config.set("observability.flight_recorder_size", 0)
    try:
        ids = spans.next_span_id()
        jax.jit(lambda x: x * 19 - 4)(jnp.ones((13,)))
        assert spans.next_span_id() == ids + 1           # none minted
        assert ledger.rows()
    finally:
        config.unset("observability.flight_recorder_size")


# ------------------------------------------------- bounds and install
def test_the_deque_is_bounded(ledger):
    for i in range(compiles.MAX_ROWS + 10):
        _play([("end", B, f"jit(f{i})", 0.001)])
    rows = ledger.rows()
    assert len(rows) == compiles.MAX_ROWS
    assert rows[-1].name == f"jit_f{compiles.MAX_ROWS + 9}"
    assert ledger.first("jit_f0") is None                # the oldest went


def test_install_twice_registers_once():
    compiles.install()
    compiles.install()
    for listeners, mine in (
            (jax_monitoring.get_scalar_listeners(), compiles._on_start),
            (jax_monitoring.get_event_duration_listeners(),
             compiles._on_duration),
            (jax_monitoring.get_event_listeners(), compiles._on_event)):
        assert listeners.count(mine) == 1


def test_enabling_the_cache_and_building_a_trainer_both_install(
        monkeypatch):
    import optax
    from mmlspark_tpu import compile_cache
    from mmlspark_tpu.parallel.trainer import DistributedTrainer
    calls = []
    monkeypatch.setattr(compiles, "install", lambda: calls.append(1))
    compile_cache.enable()                 # no directory named: still
    DistributedTrainer(lambda p, b, r: 0.0, optax.sgd(0.1))
    assert len(calls) == 2
