"""CPU rehearsal of the ``train_lm_dense`` runner at toy size (the
``granite-4.0-h-micro-train-8k`` cell's own files): the result's key set,
the numbers it compares, what it hands the readers, the two ``ssm.*``
metrics' pattern on synthetic events; and the routed qwen toy through this
runner, number for number what ``train_lm_family`` gives."""
import json
import re

import pytest

from benchmark.harness import report, spec
from benchmark.tests import toy, toy_lm_dense, toy_lm_family
from benchmark.tests.test_rehearsal_lm_family import LOSS as QWEN_LOSS
from benchmark.tests.test_rehearsal_lm_family import WALKS as QWEN_WALKS

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
MS = 1_000_000
# as the compiled step for a described v5e names them: the three kinds of
# walk (forward, recomputed forward, backward) and the chunked loss's two
WALKS = (
    "%while.153 = (s32[]{:T(128)}, f32[1,1,4096,128]{3,2,1,0:T(8,128)S(1)}, "
    "bf16[32,1,1,4096,128]{4,3,2,1,0:T(8,128)(2,1)S(1)}, f32[32,1,1,4096,128]",
    "%while.162 = (s32[]{:T(128)}, f32[1,1,4096,128]{3,2,1,0:T(8,128)S(1)}, "
    "f32[32,1,1,4096,128]{4,3,2,1,0:T(8,128)}, f32[32,1,1,4096,128]{4,3,2,1",
    "%while.175 = (s32[], f32[1,1,4096,128]{3,2,1,0}, "
    "bf16[32,1,1,4096,128]{4,3,2,1,0}, f32[32,1,1,4096,1]{3,4,2,1,0}")
LOSS = (
    "%while.152 = (s32[]{:T(128)}, f32[]{:T(128)}, bf16[4,2048,2048]{2,1,0:"
    "T(8,128)(2,1)}, s32[4,2048]{1,0:T(4,128)}, f32[4,2048]{1,0:T(4,128)}",
    "%while.180 = (s32[]{:T(128)}, bf16[2048,12544]{0,1:T(8,128)(2,1)}, "
    "f32[4,2048,2048]{2,1,0:T(8,128)S(1)}, bf16[4,2048,2048]{2,1,0}")


def _events(self=None):
    ops, t = [], 10 * MS
    rows = [(w, 2 * MS) for w in WALKS] + [(l, 20 * MS) for l in LOSS] + [
        # a consumer carries a walk's name as an operand only
        ("%get-tuple-element.9 = bf16[32,1,1,4096,128]{4,3,2,1,0} "
         "get-tuple-element(%while.153), index=2", 3 * MS),
        ("%_flash_forward.1 = bf16[1,32,32,272,64]{4,3,2,1,0} custom-call("
         "%a, %b, %c), custom_call_target=\"tpu_custom_call\"", 30 * MS),
        ("%long_attention_bwd.1 = (bf16[1,32,8192,64]{3,2,1,0}, bf16[1,32,"
         "8192,64]{3,2,1,0}) custom-call(%a), custom_call_target="
         "\"tpu_custom_call\"", 5 * MS),
        ("%copy.5 = bf16[8192,2048]{1,0} copy(%p)", MS),
        ("%fusion.1 = bf16[8192,2048]{1,0} fusion(%p)", 50 * MS)]
    for name, dur in rows:
        ops.append([name, t, dur])
        t += dur
    return {"devices": {"0": {"ops": ops, "modules": [
        ["jit_step(123)", 10 * MS, t - 10 * MS]]}},
        "host": [["bench:window", 5 * MS, t, "python3"],
                 ["trainer:dispatch", 6 * MS, MS, "python3"]]}


def _checks(out):
    rows = {}
    for line in out.splitlines():
        if line.startswith("# check "):
            row = json.loads(line[len("# check "):])
            rows[row["name"]] = row
    return rows


def _note(out, name):
    return json.loads(next(l for l in out.splitlines() if l.startswith(
        f"# {name} "))[len(name) + 3:])


def test_train_lm_dense_runner_at_toy_size(tmp_path, capsys):
    parts = toy.run(toy_lm_dense.cell(), tmp_path)
    line = json.loads(report.result_line(**parts))
    out = capsys.readouterr().out
    assert set(line) == KEYS, out
    assert line["correct"] is True and line["failed"] == 0, out
    assert set(line["metrics"]) == {"items_s_chip", "setup_s"}
    # no MTP head, no routed layer: nothing of either is compared
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
    # three Mamba-2 layers, the step traced twice (aux keys); the counter
    # is the process's, so earlier tests of a whole run add to it
    assert counters["linear_attention.rule_calls.ssd"] >= 6
    assert counters["linear_attention.calls.recurrent"] == 0
    assert counters["linear_attention.fallbacks"] == 0
    assert "# step_high_water " in out
    assert _note(out, "setup")["routing_s"] < 0.01      # no routing pass


def test_traced_run_reports_the_walk_beside_what_the_cell_inherits(
        tmp_path, monkeypatch):
    from benchmark.harness import trace
    monkeypatch.setattr(trace.Tracer, "events", _events)
    monkeypatch.setattr(trace.Tracer, "start", lambda self: None)
    monkeypatch.setattr(trace.Tracer, "open", lambda self: None)
    monkeypatch.setattr(trace.Tracer, "stop", lambda self: None)
    parts = toy.run(toy_lm_dense.cell(), tmp_path, traced=True)
    m = {k: v["value"] for k, v in parts["metrics"].items()}
    assert m["ssm.state_walk_ms"] == pytest.approx(6.0)     # not the 40
    assert 0 < m["ssm.state_walk_roofline"] < 100
    assert m["kernel.flash_attention_ms"] == pytest.approx(30.0)
    assert m["kernel.flash_bwd_ms"] == pytest.approx(5.0)
    assert 0 < m["kernel.flash_fwd_roofline"] < 100
    assert m["model.copy_ms"] == pytest.approx(1.0)
    assert {"trainer.step_ms", "trainer.syncs_per_step", "model.mfu",
            "compile.window_compiles", "device.idle_share.train",
            "trainer.dispatch_ms"} <= set(m)     # no peak on the CPU
    assert not [k for k in m if k.startswith(("moe.", "linattn.", "mesh."))]
    assert "kernel.normalize_roofline" not in m


def test_the_pattern_tells_the_walk_from_the_loss_and_the_delta_rule():
    from benchmark.harness.trace import _short
    cell = spec.load_cell(toy_lm_dense.CELL)
    new = {m["name"]: m for m in cell.per_layer
           if m["name"].startswith("ssm.")}
    assert set(new) == {"ssm.state_walk_ms", "ssm.state_walk_roofline"}
    patterns = {m["args"]["pattern"] for m in new.values()}
    assert len(patterns) == 1
    rx = re.compile(patterns.pop())
    assert all(rx.search(_short(w)) for w in WALKS)
    assert not any(rx.search(_short(l)) for l in LOSS + QWEN_LOSS)
    assert not any(rx.search(_short(w)) for w in QWEN_WALKS)
    assert not rx.search("%get-tuple-element.9 = bf16[32,1,1,4096,128] "
                         "get-tuple-element(%while.153), index=2")
    assert new["ssm.state_walk_roofline"]["args"]["per"] == "step"
    # and the delta rule's pattern does not find this state
    qwen = spec.load_cell(toy_lm_family.CELL)
    old = re.compile(next(m["args"]["pattern"] for m in qwen.per_layer
                          if m["name"] == "linattn.delta_rule_ms"))
    assert not any(old.search(_short(w)) for w in WALKS + LOSS)
    assert all(old.search(_short(w)) for w in QWEN_WALKS)
    assert not [m for m in qwen.per_layer if m["name"].startswith("ssm.")]
    assert not [m for m in cell.per_layer
                if m["name"].startswith(("linattn.", "moe."))]


def test_a_program_without_the_layer_reports_nothing_for_it():
    """The parent of this PR under these files (it fails before a window:
    its zoo has no such entry), and any cell without the layer: no such
    operation in the trace, no such shape from the runner; the readers
    return None and do not raise."""
    from benchmark.harness.main import ReaderInput

    class Ctx:
        device = {"peaks": {"bf16_tflops": 197.0, "hbm_gbps": 819.0}}
    events = {"devices": {"0": {"ops": [
        ["%fusion.1 = f32[] fusion()", 10, 5], [LOSS[0], 20, 50],
        [QWEN_WALKS[0], 70, 20]], "modules": [["jit_step(1)", 0, 100]]}},
        "host": [["bench:window", 0, 100, "python3"]]}
    rin = ReaderInput({"counters": {}, "work": {}}, events, Ctx(), {})
    cell = spec.load_cell(toy_lm_dense.CELL)
    for m in cell.per_layer:
        if m["name"].startswith("ssm."):
            reader = spec.load_plugin("readers", m["reader"])
            assert reader.read(rin, **m.get("args", {})) is None, m["name"]


def test_the_cell_is_what_the_issue_named():
    cell = spec.load_cell(toy_lm_dense.CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "granite-4.0-h-micro", "train-lm-8k", 1)
    assert cell.config["runner"] == "train_lm_dense"
    assert {m["name"] for m in cell.end_to_end} == {"items_s_chip",
                                                    "setup_s"}
    t = cell.traffic
    assert (t["batch_per_chip"], t["tokens_per_row"], t["resident_batches"],
            t["segment_steps"], t["check_steps"], t["reference_block_rows"],
            t["trace_seconds"]) == (1, 8192, 4, 4, 3, 1, 4)
    assert {m["name"] for m in cell.per_layer} >= {
        "trainer.step_ms", "trainer.syncs_per_step", "trainer.dispatch_ms",
        "trainer.steps_in_flight", "model.mfu", "model.copy_ms",
        "device.idle_share.train", "device.hbm_peak_gb.train",
        "kernel.flash_attention_ms", "kernel.flash_fwd_roofline",
        "kernel.flash_bwd_ms", "ssm.state_walk_ms",
        "ssm.state_walk_roofline"}


def test_the_routed_toy_through_this_runner_reads_what_its_own_gives(
        tmp_path, capsys):
    """``toy_lm_family`` (the qwen toy: four routed layers, an untied
    head) through ``train_lm_dense`` and through ``train_lm_family``, one
    seed: the same checks with the same values, the same ring and the same
    counters for the readers; which is what lets a ``benchmark`` issue
    point the routed configurations at this runner."""
    runs = {}
    for runner in ("train_lm_family", "train_lm_dense"):
        cell = toy_lm_family.cell()
        cell.config["runner"] = runner
        parts = toy.run(cell, tmp_path / runner, seed=77, seconds=0.5)
        out = capsys.readouterr().out
        assert parts["correct"] is True, out
        runs[runner] = (_checks(out), _note(out, "ring"),
                        _note(out, "compared_not_held"))
    own, new = runs["train_lm_family"], runs["train_lm_dense"]
    assert set(own[0]) == set(new[0]) and "routing_flip_share" in new[0]
    for name, row in own[0].items():
        assert new[0][name]["value"] == pytest.approx(
            row["value"], rel=1e-6, abs=1e-12), name
        assert new[0][name]["limit"] == row["limit"], name
    assert set(new[1]) == {"steps", "loss.main", "moe.slots_here",
                           "moe.load_max_over_mean"}
    for key in ("loss.main", "moe.slots_here", "moe.load_max_over_mean"):
        assert new[1][key][:4] == pytest.approx(own[1][key][:4], rel=1e-6)
    assert new[2] == pytest.approx(own[2], rel=1e-6, abs=1e-12)
