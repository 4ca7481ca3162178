"""The readers of what a start is made of (``program_metric``,
``program_compiles``, ``step_memory``), on the CPU rehearsal at toy size:
a traced run gives all nine metrics (and the two routed gauges in a routed
cell) with one ``# startup`` and one ``# step_memory`` note; against a
program without ``observability/compiles.py`` and without
``scopes.memory`` (hidden here, as the parent commit lacks them) every one
of them reads nothing and the run still ends with its result; and the
boundary arithmetic on rows written by hand."""
import json
import sys
import types

import pytest

from benchmark.harness import spec
from benchmark.readers import program_compiles, program_metric, step_memory
from benchmark.tests import toy, toy_lm_lfm2

NINE = {"trainer.init_s", "trainer.first_step_s", "compile.step_trace_s",
        "compile.step_lower_s", "compile.step_backend_s",
        "compile.step_cache_hit", "compile.misses_to_first_step",
        "compile.backend_s_to_first_step", "trainer.step_hbm_gb"}
ROUTED = {"moe.rows_moved", "moe.overflow_layers"}
US = 1000


def _note(out, tag):
    lines = [l for l in out.splitlines() if l.startswith(f"# {tag} ")]
    return [json.loads(l[len(tag) + 3:]) for l in lines]


@pytest.fixture
def traced(monkeypatch):
    """A traced run as ``harness/main.main`` sets it up, on a CPU: the
    program's spans are on, and the trace is a window with one step."""
    from benchmark.harness import trace
    from mmlspark_tpu.observability import compiles, scopes
    from mmlspark_tpu.utils import config
    events = {"devices": {"0": {"ops": [["%fusion.1 = x", 10 * US, US]],
                                "modules": [["jit_step(1)", 10 * US,
                                             US]]}},
              "host": [["bench:window", 0, 20 * US, "python3"]]}
    monkeypatch.setattr(trace.Tracer, "events", lambda self: events)
    for name in ("start", "open", "stop"):
        monkeypatch.setattr(trace.Tracer, name, lambda self: None)
    scopes.clear()
    compiles.install()
    compiles.clear()
    config.set("observability.annotate", True)
    try:
        yield
    finally:
        config.unset("observability.annotate")
        scopes.clear()
        compiles.clear()


@pytest.mark.parametrize("cell, more", [(toy.train_cell, set()),
                                        (toy_lm_lfm2.cell, ROUTED)])
def test_a_traced_run_gives_the_nine_metrics(traced, tmp_path, capsys,
                                             cell, more):
    from mmlspark_tpu.observability import compiles, scopes
    parts = toy.run(cell(), tmp_path, traced=True)
    out = capsys.readouterr().out
    assert parts["correct"] is True, out
    m = {k: v["value"] for k, v in parts["metrics"].items()}
    assert NINE | more <= set(m), sorted((NINE | more) - set(m))
    if not more:
        assert not ROUTED & set(m)              # a cell is on their list

    row = compiles.first("jit_step")
    assert (m["compile.step_trace_s"], m["compile.step_lower_s"],
            m["compile.step_backend_s"]) == \
        (row.trace_s, row.lower_s, row.backend_s)
    assert row.parent == "trainer:first_step"
    # no persistent cache in a rehearsal: built, and counted as no miss
    assert m["compile.step_cache_hit"] == 0 == \
        m["compile.misses_to_first_step"]
    # the stages lie inside the span that waited for them
    assert 0 < row.total_s <= m["trainer.first_step_s"]
    assert m["compile.step_backend_s"] <= \
        m["compile.backend_s_to_first_step"]
    assert m["trainer.init_s"] > 0
    memory = scopes.memory("jit_step")
    assert m["trainer.step_hbm_gb"] * 1e9 == pytest.approx(
        memory["argument"] + memory["output"] - memory["alias"]
        + memory["temp"] + memory["generated_code"])
    assert m["trainer.step_hbm_gb"] > 0

    (startup,) = _note(out, "startup")          # one note, not one a key
    (step_note,) = _note(out, "step_memory")
    rows = startup["rows_name_parent_trace_lower_backend_ms_outcome_at_s"]
    assert len(rows) == startup["to_first_step"]["programs"] + \
        startup["after"]["programs"] == len(compiles.rows())
    assert [r[0] for r in rows].count("jit_step") >= 1
    totals = [r[2] + r[3] + r[4] for r in rows]
    assert all(a >= b - 0.2 for a, b in zip(totals, totals[1:]))  # by s
    assert ["jit_full_init", "trainer:init"] in [r[:2] for r in rows]
    at = {r[0]: r[6] for r in reversed(rows)}       # a name's longest row
    assert 0 <= at["jit_full_init"] < at["jit_step"]    # the order they ran
    assert startup["step"]["parent"] == "trainer:first_step"
    assert startup["gauges_s"]["trainer.first_step_s"] == pytest.approx(
        m["trainer.first_step_s"], abs=1e-3)
    assert startup["to_first_step"]["backend_s"] == pytest.approx(
        m["compile.backend_s_to_first_step"], abs=1e-3)
    assert step_note["gb"] == pytest.approx(m["trainer.step_hbm_gb"],
                                            abs=1e-3)
    assert set(step_note["parts_gb"]) == {
        "argument", "output", "alias", "temp", "generated_code",
        "peak_memory"}
    # what the accepted metrics read from outside stays as it is
    assert {"compile.programs", "compile.window_compiles",
            "trainer.step_ms"} <= set(m)


def test_a_program_without_the_ledger_reads_nothing_and_the_run_ends(
        traced, tmp_path, capsys, monkeypatch):
    """The parent commit under these benchmark files: no
    ``observability/compiles.py``, no ``scopes.memory``, neither gauge."""
    from mmlspark_tpu.observability import metrics, scopes
    import mmlspark_tpu.observability as obs
    monkeypatch.setitem(sys.modules,
                        "mmlspark_tpu.observability.compiles", None)
    monkeypatch.delattr(obs, "compiles", raising=False)
    monkeypatch.delattr(scopes, "memory")
    real = metrics.MetricsRegistry.to_dict

    def without_the_gauges(self):
        return {k: v for k, v in real(self).items()
                if k not in ("trainer.init_s", "trainer.first_step_s")}

    monkeypatch.setattr(metrics.MetricsRegistry, "to_dict",
                        without_the_gauges)
    parts = toy.run(toy.train_cell(), tmp_path, traced=True)
    out = capsys.readouterr().out
    assert parts["correct"] is True, out
    assert not (NINE | ROUTED) & set(parts["metrics"])
    assert {"compile.programs", "trainer.step_ms", "step.forward_ms"} <= \
        set(parts["metrics"])                   # the accepted ones, as ever
    assert not _note(out, "startup") and not _note(out, "step_memory")


def test_the_metric_files_and_entries():
    with open(spec.ROOT + "/BENCHMARK.json") as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    first = min(names.index(n) for n in NINE | ROUTED)
    assert set(names[first:first + 11]) == NINE | ROUTED   # appended, and
    assert names.index("diffattn.bwd_roofline") == first - 1  # at PR 48's end
    for name in NINE:
        assert entries[name]["workloads"] == cells
        assert entries[name]["moves"] == (
            "items_s_chip" if name == "trainer.step_hbm_gb" else "setup_s")
    routed = [c for c in cells if "-ep" in c]
    for name in ROUTED:
        assert entries[name]["workloads"] == routed and len(routed) == 5
    layers = {entries[n]["layer"] for n in NINE}
    assert layers == {"trainer", "compile cache", "device"}
    for cell in cells:
        mine = {m["name"]: m for m in spec.load_cell(cell).per_layer}
        assert NINE <= set(mine)
        assert (ROUTED <= set(mine)) == (cell in routed)
        for name in NINE | ROUTED & set(mine):
            assert mine[name]["reader"] in (
                "program_metric", "program_compiles", "step_memory")


# ------------------------------------------------ rows written by hand
def _row(name, trace=0.0, lower=0.0, backend=0.0, outcome="hit",
         parent=""):
    return types.SimpleNamespace(
        name=name, start=100.0, trace_s=trace, lower_s=lower,
        backend_s=backend, outcome=outcome, retrieval_s=0.0, parent=parent,
        total_s=trace + lower + backend)


def test_the_boundary_is_the_first_row_of_the_step():
    rows = [_row("jit_full_init", 0.5, 0.25, 2.0, "miss", "trainer:init"),
            _row("jit_norms", 0.0, 0.125, 0.5, "hit"),
            _row("jit_step", 3.0, 1.0, 40.0, "miss", "trainer:first_step"),
            _row("jit_walk", 0.5, 0.5, 9.0, "miss"),
            _row("jit_step", 0.0, 1.0, 4.0, "hit")]     # the table's thunk
    found = program_compiles.startup(rows)
    assert (found["step_trace_s"], found["step_lower_s"],
            found["step_backend_s"], found["step_cache_hit"]) == \
        (3.0, 1.0, 40.0, 0.0)
    assert found["misses_to_first_step"] == 2
    assert found["backend_s_to_first_step"] == 42.5
    assert found["to_first_step"] == {
        "programs": 3, "trace_s": 3.5, "lower_s": 1.375, "backend_s": 42.5,
        "hit": 1, "miss": 2, "uncached": 0}
    assert found["after"]["programs"] == 2 and found["after"]["hit"] == 1
    rows[2] = _row("jit_step", 3.0, 1.0, 4.0, "hit")
    assert program_compiles.startup(rows)["step_cache_hit"] == 1.0
    assert program_compiles.startup(rows[:2]) is None   # no step built
    assert program_compiles.startup([]) is None


def test_one_note_a_run_and_none_where_nothing_is_read(monkeypatch,
                                                       capsys):
    rows = [_row("jit_step", 1.0, 1.0, 1.0, "uncached")]
    monkeypatch.setattr(program_compiles, "_rows", lambda: rows)
    rin = types.SimpleNamespace()
    assert program_compiles.read(rin, "step_lower_s") == 1.0
    assert program_compiles.read(rin, "step_cache_hit") == 0.0
    assert len(_note(capsys.readouterr().out, "startup")) == 1
    monkeypatch.setattr(program_compiles, "_rows", lambda: None)
    rin = types.SimpleNamespace()
    assert program_compiles.read(rin, "step_lower_s") is None
    assert program_compiles.read(rin, "misses_to_first_step") is None
    assert not capsys.readouterr().out


def test_program_metric_never_creates_what_it_looks_for():
    from mmlspark_tpu.observability import metrics
    name = "test.startup_readers.nobody_made_this"
    assert program_metric.read(None, name) is None
    assert name not in metrics.get_registry().to_dict()
    metrics.gauge(name).set(2.5)
    try:
        assert program_metric.read(None, name) == 2.5
        hist = name + ".histogram"
        metrics.histogram(hist).observe(1.0)
        assert program_metric.read(None, hist) is None  # no one value
    finally:
        metrics.get_registry()._metrics.pop(name, None)
        metrics.get_registry()._metrics.pop(name + ".histogram", None)


def test_step_memory_is_the_buffers_and_the_code(monkeypatch, capsys):
    parts = {"argument": 4_000_000_000, "output": 3_000_000_000,
             "alias": 3_000_000_000, "temp": 9_000_000_000,
             "generated_code": 250_000_000, "peak_memory": 12_500_000_000}
    monkeypatch.setattr(step_memory, "_memory", lambda program: parts)
    rin = types.SimpleNamespace()
    assert step_memory.read(rin) == 13.25 == step_memory.read(rin)
    (said,) = _note(capsys.readouterr().out, "step_memory")
    assert said["gb"] == 13.25 and said["parts_gb"]["temp"] == 9.0
    monkeypatch.setattr(step_memory, "_memory", lambda program: None)
    assert step_memory.read(types.SimpleNamespace()) is None
