"""CPU rehearsal of the ``lfm2-24b-a2b-train-ep8share-8k`` cell at toy size
(its own configuration, traffic and metric files through
``train_lm_dense``): the result's key set, the numbers it compares, the
three ``shortconv.*`` metrics the cell brought (on a table known by hand,
and on the real step program's table), and what a program without the part
reads."""
import json
import types

import pytest

from benchmark.harness import report, spec
from benchmark.readers import scope_named_ms_per_step as reader
from benchmark.tests import toy, toy_lm_family, toy_lm_lfm2
from benchmark.tests.test_rehearsal_lm_dense import _checks, _note

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
US = 1000
NEW = {"shortconv.layer_ms", "shortconv.gate_conv_ms",
       "shortconv.gate_conv_roofline"}
FWD = "jit(step)/loss_and_grad/jvp(Lfm2Moe)/"
BWD = "jit(step)/loss_and_grad/transpose(jvp(Lfm2Moe))/"
REMAT = BWD + "loss_and_grad/jvp(Lfm2Moe)/checkpoint/rematted_computation/"
CONV = "block0/attn/short_conv/"
TABLE = {
    "fusion.1": (FWD + CONV + "attn_in/dot_general", ("loss_and_grad",)),
    "fusion.2": (FWD + CONV + "gate_conv/mul", ("loss_and_grad",)),
    "fusion.3": (REMAT + CONV + "gate_conv/mul", ("loss_and_grad",)),
    "fusion.4": (BWD + CONV + "gate_conv/reduce_sum;" + FWD + "x/add",
                 ("loss_and_grad",)),
    "call.5": (BWD + CONV + "gate_conv/mul", ("loss_and_grad",)),
    "copy.6": ("", ()),                 # inside call.5: its container's
    "fusion.7": (FWD + "block0/ffn/ffn/mlp_up/dot_general",
                 ("loss_and_grad",)),
    "fusion.8": (FWD + "block1/attn/grouped_attention/qk_norm/mul",
                 ("loss_and_grad",)),
    "copy.9": ("", ()),                 # at the top level: nobody's
    "fusion.10": (FWD + "gate_conv_like/short_conv_v2/mul",
                  ("loss_and_grad",)),  # a name is a whole component
}


def _op(name, start, dur):
    return [f"%{name} = bf16[8,4]{{1,0}} fusion(%p.{start})", start * US,
            dur * US]


def _events():
    """One chip, a window of [0, 2000) us, two step programs of 1000 us.
    In each: fusion.1 100, fusion.2 50, fusion.3 50, fusion.4 80, call.5
    100 that covers copy.6 (30), fusion.7 200, fusion.8 40, copy.9 20,
    fusion.10 60. Under short_conv: 100 + 50 + 50 + 80 + 100 = 380; under
    gate_conv: 280."""
    ops = []
    for base in (0, 1000):
        t = base
        for name, dur in (("fusion.1", 100), ("fusion.2", 50),
                          ("fusion.3", 50), ("fusion.4", 80)):
            ops.append(_op(name, t, dur))
            t += dur
        ops.append(_op("call.5", t, 100))
        ops.append(_op("copy.6", t + 40, 30))
        t += 100
        for name, dur in (("fusion.7", 200), ("fusion.8", 40),
                          ("copy.9", 20), ("fusion.10", 60)):
            ops.append(_op(name, t, dur))
            t += dur
    return {"devices": {"0": {"ops": ops, "modules": [
        ["jit_step(1)", 0, 1000 * US], ["jit_step(1)", 1000 * US, 1000 * US],
        ["jit_eval(2)", 2000 * US, 100 * US]]}},
        "host": [["bench:window", 0, 2000 * US, "python3"]]}


def _rin(call=None):
    return types.SimpleNamespace(
        events=_events(), peaks={"bf16_tflops": 197.0, "hbm_gbps": 819.0},
        work={"kernel_calls": {"short_conv": call} if call else {}})


def _metric(name):
    cell = spec.load_cell(toy_lm_lfm2.CELL)
    return next(m for m in cell.per_layer if m["name"] == name)


def test_the_three_metrics_on_a_table_known_by_hand(monkeypatch):
    monkeypatch.setattr(reader.rules, "_table", lambda program: TABLE)
    call = {"rows": 1, "len": 1000, "dim": 64, "taps": 3, "layers": 1}
    rin = _rin(call)
    got = {name: reader.read(rin, **_metric(name)["args"]) for name in NEW}
    assert got["shortconv.layer_ms"] == pytest.approx(0.380)
    assert got["shortconv.gate_conv_ms"] == pytest.approx(0.280)
    # 1000 tokens x 15 rows x 64 channels x 2 B at 819 GB/s, over 280 us
    need = 1000 * 15 * 64 * 2 / 819e9
    assert got["shortconv.gate_conv_roofline"] == pytest.approx(
        100 * need / 280e-6)
    # one pass over the trace serves all three
    assert rin.scope_paths[1] == 2
    assert sum(rin.scope_paths[0].values()) == 2 * 700 * US
    # any other scope reads by the same rules; a part of a name is no name
    assert reader.read(rin, scope="qk_norm") == pytest.approx(0.040)
    assert reader.read(rin, scope="conv") is None
    assert reader.read(rin, scope="short_conv_v2") == pytest.approx(0.060)
    # without the work's shape the share says nothing; the times still do
    bare = _rin()
    assert reader.read(
        bare, **_metric("shortconv.gate_conv_roofline")["args"]) is None
    assert reader.read(bare, scope="gate_conv") == pytest.approx(0.280)


def test_a_program_without_the_part_reports_nothing(monkeypatch):
    """A table with no such scope (every other family's step), a program
    that publishes none (the parent of PR 35), a run that was not traced:
    the reader returns None and does not raise."""
    args = [_metric(name)["args"] for name in sorted(NEW)]
    other = {k: v for k, v in TABLE.items() if "short_conv/" not in v[0]}
    for table in (other, {}, None):
        monkeypatch.setattr(reader.rules, "_table", lambda program: table)
        rin = _rin({"rows": 1, "len": 8, "dim": 8, "taps": 3, "layers": 1})
        assert [reader.read(rin, **a) for a in args] == [None] * 3
    untraced = types.SimpleNamespace(events=None, peaks={}, work={})
    assert [reader.read(untraced, **a) for a in args] == [None] * 3
    # no accepted cell gains one of the three
    qwen = spec.load_cell(toy_lm_family.CELL)
    assert not [m for m in qwen.per_layer if m["name"] in NEW]


def test_the_cell_at_toy_size(tmp_path, capsys):
    parts = toy.run(toy_lm_lfm2.cell(), tmp_path)
    line = json.loads(report.result_line(**parts))
    out = capsys.readouterr().out
    assert set(line) == KEYS, out
    assert line["correct"] is True and line["failed"] == 0, out
    assert set(line["metrics"]) == {"items_s_chip", "setup_s"}
    held = {f"{k}_step{s}_rel_gap" for k in ("loss", "loss_main")
            for s in range(3)} | {
        "first_grad_norm_worst_leaf_gap", "param_change_norm_worst_leaf_gap",
        "first_grad_rel_diff", "routing_flip_share", "routing_flip_margin",
        "window_compiles", "nonfinite_losses", "state_step_count_gap",
        "attention.flash_fallbacks", "linear_attention.fallbacks"}
    assert set(_checks(out)) == held
    # four routed layers, each printed beside the two that are held
    assert set(_note(out, "compared_not_held")) == {
        f"routing_flip_{k}_layer{i}" for k in ("share", "margin")
        for i in range(4)}
    ring = _note(out, "ring")
    assert set(ring) == {"steps", "loss.main", "moe.slots_here",
                         "moe.load_max_over_mean", "moe.overflow_layers"}
    # 2 rows x 32 tokens x 2 choices x 4 layers, half of them held here
    # by an even router; no layer ran at full size
    assert all(150 < v < 360 for v in ring["moe.slots_here"])
    assert not any(ring["moe.overflow_layers"])
    counters = _note(out, "program_counters")
    assert counters["moe.grouped_calls.ragged_dot"] >= 12
    assert counters["linear_attention.rule_calls.delta"] == 0 \
        or counters["linear_attention.calls.chunked"] >= 0
    assert _note(out, "setup")["routing_s"] > 0         # the routing pass


def test_traced_run_reports_the_three_new_metrics_beside_the_inherited(
        tmp_path, monkeypatch, capsys):
    """The toy cell, traced, with ``observability.annotate`` as
    ``harness/main.main`` sets it: the trainer publishes its step, and
    synthetic events made of that table's own names (a microsecond each;
    the CPU's profiler has no device plane), a flash call and a grouped
    product are read by the cell's own metric files."""
    from benchmark.harness import trace
    from benchmark.readers import scope_ms_per_step
    from mmlspark_tpu.observability import scopes
    from mmlspark_tpu.utils import config

    want = {}

    def events(self):
        table = scopes.table("jit_step")
        assert table, "the trainer published nothing"
        names = sorted(n for n, s in table.items()
                       if s.path and "fusion" in n)
        for scope in ("short_conv", "gate_conv"):
            want[scope] = sum(scope in scope_ms_per_step._components(
                table[n].path) for n in names)
        ops = [[f"%{n} = f32[2]{{0}} fusion(%p)", (10 + i) * US, US]
               for i, n in enumerate(names)]
        end = (10 + len(names)) * US
        ops += [["%_flash_forward.1 = bf16[2,4,32,16]{3,2,1,0} custom-call("
                 "%a, %b, %c), custom_call_target=\"tpu_custom_call\"",
                 end, 30 * US],
                ["%ragged-dot-none.7 = bf16[512,32]{1,0} custom-call(%a, %b),"
                 " custom_call_target=\"tpu_custom_call\"", end + 30 * US,
                 20 * US]]
        end += 50 * US
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
        parts = toy.run(toy_lm_lfm2.cell(), tmp_path, traced=True)
    finally:
        config.unset("observability.annotate")
        scopes.clear()
    out = capsys.readouterr().out
    assert parts["correct"] is True, out
    m = {k: v["value"] for k, v in parts["metrics"].items()}
    assert NEW <= set(m)
    assert want["short_conv"] > want["gate_conv"] > 20
    assert m["shortconv.layer_ms"] == pytest.approx(
        want["short_conv"] * 1e-3)
    assert m["shortconv.gate_conv_ms"] == pytest.approx(
        want["gate_conv"] * 1e-3)
    assert m["shortconv.gate_conv_roofline"] > 0
    # the accepted table's parts still partition the step: a block's
    # mixer is the flax module ``attn``, one of PARTS' names for attention,
    # so the short convolution's time lies in model.attention_ms
    assert m["model.attention_ms"] >= m["shortconv.layer_ms"]
    assert m["kernel.flash_attention_ms"] == pytest.approx(0.030)
    assert m["moe.expert_matmul_ms"] == pytest.approx(0.020)
    assert 0 < m["moe.expert_matmul_roofline"]
    assert {"moe.routed_path_ms", "moe.dispatch_combine_ms",
            "moe.load_max_over_mean", "model.attention_ms", "model.ffn_ms",
            "loss.head_ms", "step.forward_ms", "trainer.step_ms",
            "model.mfu", "compile.window_compiles"} <= set(m)
    assert m["moe.routed_path_ms"] > m["moe.dispatch_combine_ms"] > 0
    assert not [k for k in m if k.startswith(("linattn.", "ssm.", "mesh."))]


def test_the_cell_is_what_the_issue_named():
    cell = spec.load_cell(toy_lm_lfm2.CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "lfm2-24b-a2b", "train-lm-8k-x4", 1)
    assert cell.config["runner"] == "train_lm_dense"
    assert cell.config["reference"] == "lfm2_moe"
    assert {k: cell.traffic[k] for k in (
        "batch_per_chip", "tokens_per_row", "resident_batches",
        "segment_steps", "check_steps", "reference_block_rows",
        "trace_seconds")} == {
        "batch_per_chip": 4, "tokens_per_row": 8192, "resident_batches": 4,
        "segment_steps": 4, "check_steps": 3, "reference_block_rows": 1,
        "trace_seconds": 4}
    assert cell.config["program"]["zoo_args"] == {"gate_grad": False}
    assert set(cell.config["limits"]) == {
        "loss_rel_gap", "grad_norm_gap", "grad_rel_diff", "delta_norm_gap",
        "routing_flip_share", "routing_flip_margin"}
    assert {m["name"] for m in cell.end_to_end} == {"items_s_chip",
                                                    "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert names >= NEW | {
        "trainer.step_ms", "trainer.syncs_per_step", "trainer.dispatch_ms",
        "trainer.steps_in_flight", "model.mfu", "model.copy_ms",
        "device.idle_share.train", "device.hbm_peak_gb.train",
        "kernel.flash_attention_ms", "kernel.flash_fwd_roofline",
        "kernel.flash_bwd_ms", "model.attention_ms", "model.ffn_ms",
        "model.other_ms", "loss.head_ms", "step.forward_ms",
        "step.recompute_ms", "step.backward_ms", "step.optimizer_ms",
        "step.unscoped_ms", "moe.expert_matmul_ms",
        "moe.expert_matmul_roofline", "moe.load_max_over_mean",
        "moe.routed_path_ms", "moe.dispatch_combine_ms"}
    assert not [n for n in names if n.startswith(("linattn.", "ssm.",
                                                  "mesh."))]
    for m in cell.per_layer:
        if m["name"] in NEW:
            assert (m["reader"], m["moves"], m["source"]) == (
                "scope_named_ms_per_step", "items_s_chip", "device_trace")
