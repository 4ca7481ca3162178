"""``readers/scope_ms_per_step.py``: on synthetic events and a synthetic
table whose answers are known by hand (a ``while`` with children counted
once, phases summing to the step programs' busy time, parts partitioning
it, a name the table lacks counted in the note, a program without a table
reading ``None``), and on the toy ``train_lm_dense`` cell at CPU size with
the table of the real step program."""
import json
import types

import pytest

from benchmark.harness import spec
from benchmark.readers import scope_ms_per_step as reader
from benchmark.tests import toy, toy_lm_dense

US = 1000
FWD = "jit(step)/loss_and_grad/jvp(M)/"
BWD = "jit(step)/loss_and_grad/transpose(jvp(M))/"
REMAT = BWD + "block0/loss_and_grad/jvp(M)/block0/checkpoint/" \
    "rematted_computation/"
TABLE = {
    "fusion.1": (FWD + "block0/attn/mla_attention/attn_query_a/dot_general",
                 ("loss_and_grad",)),
    "fusion.2": (FWD + "block0/ffn/moe_combine/shared/ffn/mlp_up/"
                 "dot_general", ("loss_and_grad",)),
    "while.3": (FWD + "block1/attn/mamba2_mixer/ssd_scan/while",
                ("loss_and_grad",)),
    "fusion.4": (FWD + "block1/attn/mamba2_mixer/ssd_scan/while/body/mul",
                 ("loss_and_grad",)),
    "copy.5": ("", ()),
    "fusion.6": (REMAT + "block0/ffn/mlp_up/dot_general",
                 ("loss_and_grad",)),
    "fusion.7": (BWD + "block0/ffn/mlp_up/dot_general;" + FWD + "x/add",
                 ("loss_and_grad", "optimizer_update")),
    "fusion.8": ("jit(step)/optimizer_update/add", ("optimizer_update",)),
    "fusion.9": ("jit(step)/metrics_ring/scatter", ("metrics_ring",)),
    "fusion.10": (BWD + "lm_loss/while/body/dot_general",
                  ("loss_and_grad",)),
    "fusion.11": (FWD + "block0/norm1/mul", ("loss_and_grad",)),
}


def _op(name, start, dur):
    return [f"%{name} = f32[8,4]{{1,0}} fusion(%p.{start})", start * US,
            dur * US]


def _events(extra=()):
    """One chip, a window of [0, 2000) us, two step programs of 1000 us.
    In each: fusion.1 100, fusion.2 100, while.3 300 that covers two
    runs of fusion.4 (100 each, 50 between them and at the ends), copy.5
    50, fusion.6 100, fusion.7 100, fusion.8 50, fusion.9 10, fusion.10
    100, fusion.11 40; 50 idle. An eval program after the window's steps
    is nobody's."""
    ops = []
    for base in (0, 1000):
        t = base
        for name, dur in (("fusion.1", 100), ("fusion.2", 100)):
            ops.append(_op(name, t, dur))
            t += dur
        ops.append(_op("while.3", t, 300))
        ops.append(_op("fusion.4", t + 25, 100))
        ops.append(_op("fusion.4", t + 175, 100))
        t += 300
        for name, dur in (("copy.5", 50), ("fusion.6", 100),
                          ("fusion.7", 100), ("fusion.8", 50),
                          ("fusion.9", 10), ("fusion.10", 100),
                          ("fusion.11", 40)) + tuple(extra):
            ops.append(_op(name, t, dur))
            t += dur
    return {"devices": {"0": {"ops": ops, "modules": [
        ["jit_step(7)", 0, 1000 * US], ["jit_step(7)", 1000 * US, 1000 * US]
    ]}}, "host": [["bench:window", 0, 2000 * US, "python3"]]}


def test_a_while_with_children_is_counted_once():
    found = reader.split(_events(), TABLE)
    assert found["steps"] == 2
    # the walk's children 200 us a step, the while itself keeps 100
    assert found["detail"]["ssm"] == {"ssd_scan": pytest.approx(0.3)}
    assert found["cells"]["forward", "ssm"] == pytest.approx(0.3)
    assert sum(found["cells"].values()) == pytest.approx(0.95)


def test_self_times_give_every_instant_to_the_latest_started():
    ops = [("outer", 0, 100), ("a", 10, 30), ("b", 30, 60),
           ("late", 90, 120), ("alone", 200, 210)]
    got = {}
    segments, inside = reader.self_times(ops)
    for name, ns in segments:
        got[name] = got.get(name, 0) + ns
    assert got == {"outer": 100 - 20 - 30 - 10, "a": 20, "b": 30,
                   "late": 30, "alone": 10}
    assert sum(got.values()) == 120 + 10            # the union, once
    # `late` outlasts `outer`: it is not inside it
    assert inside == {"a": "outer", "b": "outer"}


@pytest.mark.parametrize("path", ["", "ragged-dot-none"])
def test_a_child_under_none_of_the_steps_scopes_is_its_containers(path):
    """A grouped product inside the routed layer's ``cond`` (XLA gives
    what it makes itself no op_name, or a bare one: ``ragged-dot-none``)
    and one at the top level."""
    events = _events()
    ops = events["devices"]["0"]["ops"]
    ops.append(["%conditional.1 = f32[8]{0} conditional(%p)", 955 * US,
                40 * US])
    for start in (960, 1960):               # the second: top level
        ops.append(["%ragged-dot-none.2 = f32[8]{0} custom-call(%x)",
                    start * US, 20 * US])
    table = dict(TABLE, **{
        "conditional.1": (BWD + "block1/ffn/moe_experts/cond",
                          ("loss_and_grad",)),
        "ragged-dot-none.2": (path, ())})
    found = reader.split(events, table)
    assert found["cells"]["backward", "moe_experts"] == pytest.approx(0.02)
    assert found["inherited_ms"] == pytest.approx(0.01)
    assert found["detail"]["moe_experts"] == {
        "ragged-dot-none": pytest.approx(0.01)}
    assert found["cells"]["unscoped", "other"] == pytest.approx(0.05 + 0.01)
    assert found["unscoped_top"][1] == ["ragged-dot-none f32[8]{0}", 0.01]


def test_phases_sum_to_the_busy_time_and_parts_partition_it():
    found = reader.split(_events(), TABLE)
    by_phase, by_part = {}, {}
    for (phase, part), v in found["cells"].items():
        by_phase[phase] = by_phase.get(phase, 0) + v
        by_part[part] = by_part.get(part, 0) + v
    assert by_phase == pytest.approx({
        "forward": 0.1 + 0.1 + 0.3 + 0.04, "recompute": 0.1,
        "backward": 0.1 + 0.1, "optimizer": 0.05, "ring": 0.01,
        "unscoped": 0.05})
    assert by_part == pytest.approx({
        "attention": 0.1, "ffn": 0.1 + 0.1 + 0.1, "ssm": 0.3, "loss": 0.1,
        "other": 0.05 + 0.05 + 0.01 + 0.04})
    assert sum(by_phase.values()) == pytest.approx(sum(by_part.values()))
    assert found["mixed_ms"] == pytest.approx(0.1)          # fusion.7
    assert found["missing"] == {"count": 0, "ms": 0}
    assert found["unscoped_top"] == [["copy f32[8,4]{1,0}", 0.05]]


def test_the_innermost_known_name_wins_and_a_name_is_a_whole_component():
    assert reader.part_of(TABLE["fusion.2"][0]) == ("ffn", "mlp_up")
    assert reader.part_of(FWD + "block0/ffn/moe_combine/add") == (
        "moe_combine", "-")
    assert reader.part_of(FWD + "block0/mlp_up/dot_general") == (
        "other", "-")
    assert reader.part_of(BWD + "block3/mlp/ffn/mlp_up/dot_general") == (
        "ffn", "mlp_up")
    assert reader.part_of("jit(step)/loss_and_grad/jvp(lm_loss)/while/"
                          "body/dot_general")[0] == "loss"
    assert reader.part_of("") == ("other", "-")


@pytest.mark.parametrize("path, phase", [
    (FWD + "x/add", "forward"), (BWD + "x/add", "backward"),
    (REMAT + "x/add", "recompute"),
    ("jit(step)/optimizer_update/add", "optimizer"),
    ("jit(step)/metrics_ring/scatter", "ring"), ("", "unscoped"),
    ("jit(step)/jit(fold_in)/add", "unscoped"),
    (BWD + "x/mul;jit(step)/optimizer_update/add", "backward"),
])
def test_phase_of(path, phase):
    assert reader.phase_of(path) == phase


def _rin(events):
    return types.SimpleNamespace(events=events)


def _args(metric):
    with open(f"{spec.BENCH_DIR}/metrics/{metric}.json") as f:
        body = json.load(f)
    assert body["reader"] == "scope_ms_per_step"
    return body["args"]


def test_the_metric_files_select_their_cells(monkeypatch, capsys):
    monkeypatch.setattr(reader, "_table", lambda program: TABLE)
    monkeypatch.setattr(reader, "_hbm_gb", lambda: 0.0)
    rin = _rin(_events())
    want = {"step.forward_ms": 0.54, "step.recompute_ms": 0.1,
            "step.backward_ms": 0.2, "step.optimizer_ms": 0.05,
            "step.unscoped_ms": 0.05, "model.attention_ms": 0.1,
            "model.ffn_ms": 0.3, "model.other_ms": 0.15,
            "linattn.layer_ms": 0, "ssm.layer_ms": 0.3,
            "moe.routed_path_ms": 0, "moe.dispatch_combine_ms": 0,
            "loss.head_ms": 0.1}
    for name, value in want.items():
        got = reader.read(rin, **_args(name))
        # a table that matches nothing reads 0, not None
        assert got is not None and got == pytest.approx(value), name
    out = capsys.readouterr().out
    notes = [l for l in out.splitlines() if l.startswith("# scope_split ")]
    assert len(notes) == 1                          # the first call's
    body = json.loads(notes[0][len("# scope_split "):])
    assert body["ms_per_step"]["forward"]["ssm"] == pytest.approx(0.3)
    assert body["phase_ms"]["ring"] == pytest.approx(0.01)
    assert sum(body["phase_ms"].values()) == pytest.approx(0.95)
    assert sum(body["part_ms"].values()) == pytest.approx(0.95)
    assert body["names_the_table_lacks"] == {"count": 0, "ms": 0}
    assert body["mixed_fusion_ms"] == pytest.approx(0.1)


def test_a_name_the_table_lacks_is_counted_in_the_note(monkeypatch,
                                                       capsys):
    monkeypatch.setattr(reader, "_table", lambda program: TABLE)
    monkeypatch.setattr(reader, "_hbm_gb", lambda: 0.0)
    rin = _rin(_events(extra=(("fusion.99", 30),)))
    assert reader.read(rin, phases=["unscoped"]) == pytest.approx(0.08)
    body = json.loads(capsys.readouterr().out.split("# scope_split ")[1])
    assert body["names_the_table_lacks"] == {
        "count": 2, "ms": pytest.approx(0.03)}


def test_a_program_without_a_table_reads_none(monkeypatch, capsys):
    monkeypatch.setattr(reader, "_table", lambda program: None)
    monkeypatch.setattr(reader, "_hbm_gb", lambda: 0.0)
    rin = _rin(_events())
    assert reader.read(rin, phases=["forward"]) is None
    assert reader.read(rin, parts=["ffn"]) is None
    assert "scope_split" not in capsys.readouterr().out
    assert reader.read(_rin(None), parts=["ffn"]) is None   # untraced


def test_a_collective_is_no_parts():
    events = _events()
    ops = events["devices"]["0"]["ops"]
    ops.append(["%all-reduce.1 = f32[8]{0} all-reduce(%fusion.1)",
                960 * US, 20 * US])
    table = dict(TABLE, **{"all-reduce.1": (
        BWD + "block0/attn/key/reduce_sum", ("loss_and_grad",))})
    found = reader.split(events, table)
    assert found["cells"]["backward", "other"] == pytest.approx(0.01)
    assert ("backward", "attention") not in found["cells"]
    assert found["detail"]["other"]["collective"] == pytest.approx(0.01)


def test_operations_outside_the_step_programs_are_not_the_steps():
    events = _events()
    dev = events["devices"]["0"]
    dev["modules"].pop()            # the second program is another's now
    dev["modules"].append(["jit_eval(9)", 1000 * US, 1000 * US])
    found = reader.split(events, TABLE)
    assert found["steps"] == 1
    assert sum(found["cells"].values()) == pytest.approx(0.95)
    assert found["outside_ms"] == pytest.approx(0.95)


def test_the_thirteen_metrics_and_their_cells():
    bench = json.load(open(f"{spec.ROOT}/BENCHMARK.json"))
    mine = [m for m in bench["per_layer"]
            if json.load(open(f"{spec.BENCH_DIR}/metrics/{m['name']}.json"))
            ["reader"] == "scope_ms_per_step"]
    assert bench["per_layer"][-13:] == mine
    cells = [w["name"] for w in bench["workloads"]]
    lm, moe = cells[2:], cells[2:4]
    assert {m["name"]: m["workloads"] for m in mine} == {
        **{n: cells for n in (
            "step.forward_ms", "step.recompute_ms", "step.backward_ms",
            "step.optimizer_ms", "step.unscoped_ms", "model.attention_ms",
            "model.ffn_ms", "model.other_ms")},
        "linattn.layer_ms": ["qwen3-next-train-ep16share"],
        "ssm.layer_ms": ["granite-4.0-h-micro-train-8k"],
        "moe.routed_path_ms": moe, "moe.dispatch_combine_ms": moe,
        "loss.head_ms": lm}
    for m in mine:
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == ("ms", "lower", "device_trace", "model step",
                                "items_s_chip")


def test_the_toy_runner_end_to_end_with_the_real_programs_table(
        tmp_path, monkeypatch, capsys):
    """The toy granite cell, traced, with ``observability.annotate`` as
    ``harness/main.main`` sets it: the trainer publishes its step, the
    reader asks for the table after the window, and synthetic events made
    of that table's own names (the CPU's profiler has no device plane) are
    split by it."""
    from benchmark.harness import trace
    from mmlspark_tpu.observability import scopes
    from mmlspark_tpu.utils import config

    counted = []

    def events(self):
        table = scopes.table("jit_step")
        assert table, "the trainer published nothing"
        names = sorted(n for n, s in table.items()
                       if s.path and "fusion" in n)[:400]
        ops = [[f"%{n} = f32[2]{{0}} fusion(%p)", (10 + i) * US, US]
               for i, n in enumerate(names)]
        counted.append(len(names))
        end = (10 + len(names)) * US
        return {"devices": {"0": {"ops": ops, "modules": [
            ["jit_step(1)", 10 * US, end - 10 * US]]}},
            "host": [["bench:window", 0, end + US, "python3"]]}

    monkeypatch.setattr(trace.Tracer, "events", events)
    monkeypatch.setattr(trace.Tracer, "start", lambda self: None)
    monkeypatch.setattr(trace.Tracer, "open", lambda self: None)
    monkeypatch.setattr(trace.Tracer, "stop", lambda self: None)
    scopes.clear()
    config.set("observability.annotate", True)
    try:
        parts = toy.run(toy_lm_dense.cell(), tmp_path, traced=True)
    finally:
        config.unset("observability.annotate")
        scopes.clear()
    out = capsys.readouterr().out
    assert parts["correct"] is True, out
    m = {k: v["value"] for k, v in parts["metrics"].items()}
    mine = {"step.forward_ms", "step.recompute_ms", "step.backward_ms",
            "step.optimizer_ms", "step.unscoped_ms", "model.attention_ms",
            "model.ffn_ms", "model.other_ms", "ssm.layer_ms",
            "loss.head_ms"}
    assert mine <= set(m) and not [k for k in m if k.startswith(
        ("moe.", "linattn."))]
    body = json.loads(next(l for l in out.splitlines() if l.startswith(
        "# scope_split "))[len("# scope_split "):])
    assert body["names_the_table_lacks"] == {"count": 0, "ms": 0}
    assert counted[0] > 100 and body["steps"] == 1
    assert sum(body["phase_ms"].values()) == pytest.approx(
        counted[0] * 1e-3, rel=1e-3)        # a microsecond a name
    phases = [m[f"step.{p}_ms"] for p in (
        "forward", "recompute", "backward", "optimizer", "unscoped")]
    assert sum(phases) + body["phase_ms"]["ring"] == pytest.approx(
        m["model.attention_ms"] + m["model.ffn_ms"] + m["model.other_ms"]
        + m["ssm.layer_ms"] + m["loss.head_ms"])
    assert m["ssm.layer_ms"] > 0 and m["model.ffn_ms"] > 0
    assert m["step.unscoped_ms"] == 0
