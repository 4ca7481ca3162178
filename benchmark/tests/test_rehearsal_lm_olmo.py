"""CPU rehearsal of the ``olmo-hybrid-7b-train-8k`` cell at toy size (its
own configuration, traffic and metric files through ``train_lm_dense``):
the result's key set, the numbers it compares, the three ``linattn.*``
metrics the cell brought on synthetic events, and the three kinds of walk
(this 96 x 192 state, qwen's 128 x 128, granite's state-space one) held
apart by their patterns."""
import json
import re

import pytest

from benchmark.harness import report, spec
from benchmark.tests import toy, toy_lm_dense, toy_lm_family, toy_lm_olmo
from benchmark.tests.test_rehearsal_lm_dense import LOSS as GRANITE_LOSS
from benchmark.tests.test_rehearsal_lm_dense import WALKS as GRANITE_WALKS
from benchmark.tests.test_rehearsal_lm_dense import _checks, _note
from benchmark.tests.test_rehearsal_lm_family import LOSS as QWEN_LOSS
from benchmark.tests.test_rehearsal_lm_family import WALKS as QWEN_WALKS

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
MS = 1_000_000
# as the compiled step for a described v5e names them (PR 38): the three
# kinds of walk (forward, recomputed forward, backward) and the chunked
# loss's two
WALKS = (
    "%while.63 = (s32[]{:T(128)}, f32[1,30,96,192]{3,2,1,0:T(8,128)S(1)}, "
    "f32[128,1,30,96,192]{4,3,2,1,0:T(8,128)}, f32[128,1,30,64,192]{4,3,2,1,"
    "0:T(8,128)}, bf16[128,1,30,64,96]{4,3,2,1,0:T(8,128)(2,1)}",
    "%while.66 = (s32[]{:T(128)}, f32[1,30,96,192]{3,2,1,0:T(8,128)S(1)}, "
    "f32[128,1,30,96,192]{4,3,2,1,0:T(8,128)}, f32[128,1,30,64,192]{4,3,2,1,"
    "0:T(8,128)}, f32[128,1,30,1,1]{2,4,3,1,0:T(1,128)}",
    "%while.69 = (s32[]{:T(128)}, f32[1,30,96,192]{2,3,1,0:T(8,128)S(1)}, "
    "bf16[128,1,30,64,96]{4,3,2,1,0:T(8,128)(2,1)}, f32[128,1,30,64,192]")
LOSS = (
    "%while.62 = (s32[]{:T(128)}, f32[]{:T(128)}, bf16[4,2048,3840]{2,1,0:"
    "T(8,128)(2,1)}, s32[4,2048]{1,0:T(4,128)}, f32[4,2048]{1,0:T(4,128)}",
    "%while.72 = (s32[]{:T(128)}, bf16[3840,12544]{1,0:T(8,128)(2,1)}, "
    "f32[4,2048,3840]{2,1,0:T(8,128)}, bf16[4,2048,3840]{2,1,0}")
CALLS = (
    "%delta_chunk_fwd.3 = (bf16[128,1,30,64,96]{4,3,2,1,0}, f32[128,1,30,64,"
    "192]{4,3,2,1,0}) custom-call(%a), custom_call_target=\"tpu_custom_call\"",
    "%delta_chunk_bwd.3 = (f32[1,8192,2880]{2,1,0}, f32[1,8192,2880]{2,1,0})"
    " custom-call(%a), custom_call_target=\"tpu_custom_call\"",
    "%delta_chunk_out.6 = f32[1,8192,5760]{2,1,0:T(8,128)} custom-call(%a), "
    "custom_call_target=\"tpu_custom_call\"",
    "%delta_chunk_out_bwd.3 = (bf16[128,1,30,64,96]{4,3,2,1,0}, bf16[64,1,30,"
    "128,128]{4,3,2,1,0}) custom-call(%a), custom_call_target="
    "\"tpu_custom_call\"")
NEW = {"linattn.state_walk_ms", "linattn.state_walk_roofline",
       "linattn.chunk_kernel_roofline"}


def _events(self=None):
    ops, t = [], 10 * MS
    rows = [(w, 2 * MS) for w in WALKS] + [(l, 20 * MS) for l in LOSS] \
        + [(c, 4 * MS) for c in CALLS] + [
        # a consumer carries a walk's name as an operand only
        ("%get-tuple-element.9 = f32[128,1,30,96,192]{4,3,2,1,0} "
         "get-tuple-element(%while.63), index=2", 3 * MS),
        ("%_flash_forward.1 = bf16[1,30,32,272,128]{4,3,2,1,0} custom-call("
         "%a, %b, %c), custom_call_target=\"tpu_custom_call\"", 30 * MS),
        ("%long_attention_bwd.1 = (bf16[1,30,8192,128]{3,2,1,0}, bf16[1,30,"
         "8192,128]{3,2,1,0}) custom-call(%a), custom_call_target="
         "\"tpu_custom_call\"", 5 * MS),
        ("%copy.5 = bf16[8192,3840]{1,0} copy(%p)", MS),
        ("%fusion.1 = bf16[8192,3840]{1,0} fusion(%p)", 50 * MS)]
    for name, dur in rows:
        ops.append([name, t, dur])
        t += dur
    return {"devices": {"0": {"ops": ops, "modules": [
        ["jit_step(123)", 10 * MS, t - 10 * MS]]}},
        "host": [["bench:window", 5 * MS, t, "python3"],
                 ["trainer:dispatch", 6 * MS, MS, "python3"]]}


def test_the_cell_at_toy_size(tmp_path, capsys):
    parts = toy.run(toy_lm_olmo.cell(), tmp_path)
    line = json.loads(report.result_line(**parts))
    out = capsys.readouterr().out
    assert set(line) == KEYS, out
    assert line["correct"] is True and line["failed"] == 0, out
    assert set(line["metrics"]) == {"items_s_chip", "setup_s"}
    held = {f"{k}_step{s}_rel_gap" for k in ("loss", "loss_main")
            for s in range(3)} | {
        "first_grad_norm_worst_leaf_gap", "param_change_norm_worst_leaf_gap",
        "first_grad_rel_diff", "window_compiles", "nonfinite_losses",
        "state_step_count_gap", "attention.flash_fallbacks",
        "linear_attention.fallbacks"}
    assert set(_checks(out)) == held
    assert _note(out, "compared_not_held") == {}
    assert set(_note(out, "ring")) == {"steps", "loss.main"}
    counters = _note(out, "program_counters")
    # three Gated DeltaNet layers, the step traced twice (aux keys); the
    # counter is the process's, so earlier tests of a whole run add to it
    assert counters["linear_attention.rule_calls.delta"] >= 6
    assert counters["linear_attention.calls.recurrent"] == 0
    assert counters["linear_attention.fallbacks"] == 0
    assert _note(out, "setup")["routing_s"] < 0.01      # no routing pass


def test_traced_run_reports_the_three_new_metrics_beside_the_inherited(
        tmp_path, monkeypatch):
    from benchmark.harness import trace
    monkeypatch.setattr(trace.Tracer, "events", _events)
    monkeypatch.setattr(trace.Tracer, "start", lambda self: None)
    monkeypatch.setattr(trace.Tracer, "open", lambda self: None)
    monkeypatch.setattr(trace.Tracer, "stop", lambda self: None)
    parts = toy.run(toy_lm_olmo.cell(), tmp_path, traced=True)
    m = {k: v["value"] for k, v in parts["metrics"].items()}
    assert m["linattn.state_walk_ms"] == pytest.approx(6.0)   # not the 40
    assert m["linattn.chunk_kernel_ms"] == pytest.approx(16.0)
    assert 0 < m["linattn.state_walk_roofline"] < 100
    assert 0 < m["linattn.chunk_kernel_roofline"] < 100
    assert m["kernel.flash_attention_ms"] == pytest.approx(30.0)
    assert m["kernel.flash_bwd_ms"] == pytest.approx(5.0)
    assert 0 < m["kernel.flash_fwd_roofline"] < 100
    assert m["model.copy_ms"] == pytest.approx(1.0)
    assert {"trainer.step_ms", "trainer.syncs_per_step", "model.mfu",
            "compile.window_compiles", "device.idle_share.train",
            "trainer.dispatch_ms"} <= set(m)     # no peak on the CPU
    assert not [k for k in m if k.startswith(("moe.", "ssm.", "mesh."))]
    assert "linattn.delta_rule_ms" not in m
    assert "linattn.delta_rule_roofline" not in m


def test_the_three_walks_are_held_apart():
    from benchmark.harness.trace import _short
    cell = spec.load_cell(toy_lm_olmo.CELL)
    new = {m["name"]: m for m in cell.per_layer if m["name"] in NEW}
    assert set(new) == NEW
    walk = re.compile(new["linattn.state_walk_ms"]["args"]["pattern"])
    assert new["linattn.state_walk_roofline"]["args"]["pattern"] \
        == walk.pattern
    assert all(walk.search(_short(w)) for w in WALKS)
    assert not any(walk.search(_short(x)) for x in (
        LOSS + QWEN_LOSS + GRANITE_LOSS + QWEN_WALKS + GRANITE_WALKS + CALLS))
    assert not walk.search(
        "%get-tuple-element.9 = f32[128,1,30,96,192] get-tuple-element("
        "%while.63), index=2")
    for name in ("linattn.state_walk_roofline",
                 "linattn.chunk_kernel_roofline"):
        assert new[name]["args"]["per"] == "step"
        assert new[name]["args"]["reference"] == "olmo_hybrid"
    # the accepted walks' patterns do not find this state, nor the calls
    qwen = spec.load_cell(toy_lm_family.CELL)
    old = re.compile(next(m["args"]["pattern"] for m in qwen.per_layer
                          if m["name"] == "linattn.delta_rule_ms"))
    granite = spec.load_cell(toy_lm_dense.CELL)
    ssm = re.compile(next(m["args"]["pattern"] for m in granite.per_layer
                          if m["name"] == "ssm.state_walk_ms"))
    for rx in (old, ssm):
        assert not any(rx.search(_short(x)) for x in WALKS + LOSS + CALLS)
    assert all(old.search(_short(w)) for w in QWEN_WALKS)
    assert all(ssm.search(_short(w)) for w in GRANITE_WALKS)
    # the chunk kernels' share reads the four calls linattn.chunk_kernel_ms
    # reads, and nothing else
    calls = re.compile(new["linattn.chunk_kernel_roofline"]["args"][
        "pattern"])
    assert calls.pattern == next(
        m["args"]["pattern"] for m in cell.per_layer
        if m["name"] == "linattn.chunk_kernel_ms")
    assert all(calls.search(_short(c)) for c in CALLS)
    assert not any(calls.search(_short(x)) for x in WALKS + LOSS)
    # neither accepted cell gains one of the three
    for other in (qwen, granite):
        assert not [m for m in other.per_layer if m["name"] in NEW]


def test_a_program_without_the_layer_reports_nothing_for_it():
    """The parent of this PR under these files (it fails before a window:
    its zoo has no such entry), and any cell whose state has another
    shape: the readers return None and do not raise."""
    from benchmark.harness.main import ReaderInput

    class Ctx:
        device = {"peaks": {"bf16_tflops": 197.0, "hbm_gbps": 819.0}}
    events = {"devices": {"0": {"ops": [
        ["%fusion.1 = f32[] fusion()", 10, 5], [LOSS[0], 20, 50],
        [QWEN_WALKS[0], 70, 20]], "modules": [["jit_step(1)", 0, 100]]}},
        "host": [["bench:window", 0, 100, "python3"]]}
    rin = ReaderInput({"counters": {}, "work": {}}, events, Ctx(), {})
    cell = spec.load_cell(toy_lm_olmo.CELL)
    for m in cell.per_layer:
        if m["name"] in NEW:
            reader = spec.load_plugin("readers", m["reader"])
            assert reader.read(rin, **m.get("args", {})) is None, m["name"]


def test_the_cell_is_what_the_issue_named():
    cell = spec.load_cell(toy_lm_olmo.CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "olmo-hybrid-7b", "train-lm-8k", 1)
    assert cell.config["runner"] == "train_lm_dense"
    assert {m["name"] for m in cell.end_to_end} == {"items_s_chip",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} >= NEW | {
        "trainer.step_ms", "trainer.syncs_per_step", "trainer.dispatch_ms",
        "trainer.steps_in_flight", "model.mfu", "model.copy_ms",
        "device.idle_share.train", "device.hbm_peak_gb.train",
        "kernel.flash_attention_ms", "kernel.flash_fwd_roofline",
        "kernel.flash_bwd_ms", "linattn.chunk_kernel_ms", "linattn.layer_ms",
        "model.attention_ms", "model.ffn_ms", "model.other_ms",
        "loss.head_ms", "step.forward_ms", "step.unscoped_ms"}
    assert not [m for m in cell.per_layer
                if m["name"].startswith(("moe.", "ssm.", "mesh."))]
