"""CPU rehearsal of the ``train_lm_family`` runner at toy size (the
``qwen3-next-train-ep16share`` cell's own files): the result's key set,
the numbers it compares, what it hands the readers, and the two
``linattn.*`` metrics' pattern on synthetic events."""
import json

import pytest

from benchmark.harness import report, spec
from benchmark.tests import toy, toy_lm_family

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
MS = 1_000_000
# as the compiled step for a described v5e names them: the three kinds of
# walk (forward, recomputed forward, backward) and the chunked loss's two
WALKS = (
    "%while.65 = (s32[]{:T(128)}, f32[2,32,128,128]{3,2,1,0:T(8,128)S(1)}, "
    "bf16[64,2,32,128,128]{4,3,2,1,0:T(8,128)(2,1)}, bf16[64,2,32,64,128]",
    "%while.68 = (s32[]{:T(128)}, f32[2,32,128,128]{3,2,1,0:T(8,128)S(1)}, "
    "f32[64,2,32,128,128]{4,3,2,1,0:T(8,128)}, bf16[64,2,32,64,128]",
    "%while.71 = (s32[], f32[2,32,128,128]{3,2,1,0}, "
    "bf16[64,2,32,64,128]{4,3,2,1,0}, bf16[64,2,32,64,128]{4,3,2,1,0}")
LOSS = (
    "%while.64 = (s32[]{:T(128)}, f32[]{:T(128)}, bf16[4,2048,2048]{2,1,0:"
    "T(8,128)(2,1)}, s32[4,2048]{1,0:T(4,128)}, f32[4,2048]{1,0:T(4,128)}",
    "%while.74 = (s32[]{:T(128)}, bf16[2048,18992]{0,1:T(8,128)(2,1)}, "
    "f32[4,2048,2048]{2,1,0:T(8,128)S(1)}, bf16[4,2048,2048]{2,1,0}")


def _events(self=None):
    ops, t = [], 10 * MS
    rows = [(w, 8 * MS) for w in WALKS] + [(l, 20 * MS) for l in LOSS] + [
        # a consumer carries a walk's name as an operand only
        ("%get-tuple-element.9 = bf16[64,2,32,64,128]{4,3,2,1,0} "
         "get-tuple-element(%while.65), index=3", 3 * MS),
        ("%_flash_forward.1 = bf16[2,16,16,272,256]{4,3,2,1,0} custom-call("
         "%a, %b, %c), custom_call_target=\"tpu_custom_call\"", 3 * MS),
        ("%long_attention_bwd.1 = (bf16[2,16,4096,256]{3,2,1,0}, bf16[2,16,"
         "4096,256]{3,2,1,0}) custom-call(%a), custom_call_target="
         "\"tpu_custom_call\"", 5 * MS),
        ("%ragged-dot-none.7 = f32[81920,512]{1,0} custom-call(%x), "
         "custom_call_target=\"tpu_custom_call\"", 2 * MS),
        ("%copy.5 = bf16[8192,2048]{1,0} copy(%p)", MS),
        ("%fusion.1 = bf16[8192,2048]{1,0} fusion(%p)", 50 * MS)]
    for name, dur in rows:
        ops.append([name, t, dur])
        t += dur
    return {"devices": {"0": {"ops": ops, "modules": [
        ["jit_step(123)", 10 * MS, t - 10 * MS]]}},
        "host": [["bench:window", 5 * MS, t, "python3"],
                 ["trainer:dispatch", 6 * MS, MS, "python3"]]}


def test_train_lm_family_runner_at_toy_size(tmp_path, capsys):
    parts = toy.run(toy_lm_family.cell(), tmp_path)
    line = json.loads(report.result_line(**parts))
    out = capsys.readouterr().out
    assert set(line) == KEYS, out
    assert line["correct"] is True and line["failed"] == 0, out
    assert set(line["metrics"]) == {"items_s_chip", "setup_s"}
    checks = {}
    for l in out.splitlines():
        if l.startswith("# check "):
            row = json.loads(l[len("# check "):])
            checks[row["name"]] = row
    # no MTP head in this family: nothing of it is compared
    held = {f"{k}_step{s}_rel_gap" for k in ("loss", "loss_main")
            for s in range(3)} | {
        "first_grad_norm_worst_leaf_gap", "param_change_norm_worst_leaf_gap",
        "first_grad_rel_diff", "routing_flip_share", "routing_flip_margin",
        "window_compiles", "nonfinite_losses", "state_step_count_gap",
        "attention.flash_fallbacks", "linear_attention.fallbacks"}
    assert set(checks) == held
    ring = json.loads(next(l for l in out.splitlines()
                           if l.startswith("# ring "))[7:])
    assert set(ring) == {"steps", "loss.main", "moe.slots_here",
                         "moe.load_max_over_mean"}
    counters = json.loads(next(l for l in out.splitlines() if l.startswith(
        "# program_counters"))[len("# program_counters "):])
    # three Gated DeltaNet layers, the step traced twice (aux keys); the
    # counter is the process's, so earlier tests of a whole run add to it
    assert counters["linear_attention.calls.chunked"] >= 6
    assert counters["linear_attention.calls.recurrent"] == 0
    assert counters["moe.grouped_calls.ragged_dot"] >= 24
    not_held = json.loads(next(l for l in out.splitlines() if l.startswith(
        "# compared_not_held"))[len("# compared_not_held "):])
    assert {f"routing_flip_share_layer{i}" for i in range(4)} <= set(
        not_held)                       # all four layers are routed
    assert "# step_high_water " in out


def test_traced_run_reports_the_delta_rule_beside_what_the_cell_inherits(
        tmp_path, monkeypatch):
    from benchmark.harness import trace
    monkeypatch.setattr(trace.Tracer, "events", _events)
    monkeypatch.setattr(trace.Tracer, "start", lambda self: None)
    monkeypatch.setattr(trace.Tracer, "open", lambda self: None)
    monkeypatch.setattr(trace.Tracer, "stop", lambda self: None)
    parts = toy.run(toy_lm_family.cell(), tmp_path, traced=True)
    m = {k: v["value"] for k, v in parts["metrics"].items()}
    assert m["linattn.delta_rule_ms"] == pytest.approx(24.0)  # not the 40
    assert 0 < m["linattn.delta_rule_roofline"] < 100
    assert m["kernel.flash_attention_ms"] == pytest.approx(3.0)
    assert m["kernel.flash_bwd_ms"] == pytest.approx(5.0)
    assert m["moe.expert_matmul_ms"] == pytest.approx(2.0)
    assert 0 < m["kernel.flash_fwd_roofline"] < 100
    assert 0 < m["moe.expert_matmul_roofline"] < 100
    assert m["moe.load_max_over_mean"] >= 1.0
    assert m["model.copy_ms"] == pytest.approx(1.0)
    assert {"trainer.step_ms", "trainer.syncs_per_step", "model.mfu",
            "compile.window_compiles", "device.idle_share.train"} <= set(m)
    assert "mesh.collective_exposed_ms" not in m
    assert "kernel.normalize_roofline" not in m


def test_the_pattern_tells_the_walk_from_the_chunked_loss():
    import re
    cell = spec.load_cell(toy_lm_family.CELL)
    new = {m["name"]: m for m in cell.per_layer
           if m["name"].startswith("linattn.")}
    assert set(new) == {"linattn.delta_rule_ms",
                        "linattn.delta_rule_roofline"}
    patterns = {m["args"]["pattern"] for m in new.values()}
    assert len(patterns) == 1
    rx = re.compile(patterns.pop())
    from benchmark.harness.trace import _short
    assert all(rx.search(_short(w)) for w in WALKS)
    assert not any(rx.search(_short(l)) for l in LOSS)
    assert not rx.search("%get-tuple-element.9 = bf16[64,2,32,64,128] "
                         "get-tuple-element(%while.65), index=3")
    assert new["linattn.delta_rule_roofline"]["args"]["per"] == "step"


def test_a_program_without_the_recurrence_reports_nothing_for_it():
    """The parent of this PR under these files (it fails before a window:
    its zoo has no such entry), and any cell without the layer: no such
    operation in the trace, no such shape from the runner; the readers
    return None and do not raise."""
    from benchmark.harness.main import ReaderInput

    class Ctx:
        device = {"peaks": {"bf16_tflops": 197.0, "hbm_gbps": 819.0}}
    events = {"devices": {"0": {"ops": [
        ["%fusion.1 = f32[] fusion()", 10, 5],
        [LOSS[0], 20, 50]], "modules": [["jit_step(1)", 0, 100]]}},
        "host": [["bench:window", 0, 100, "python3"]]}
    rin = ReaderInput({"counters": {}, "work": {}}, events, Ctx(), {})
    cell = spec.load_cell(toy_lm_family.CELL)
    for m in cell.per_layer:
        if m["name"].startswith("linattn."):
            reader = spec.load_plugin("readers", m["reader"])
            assert reader.read(rin, **m.get("args", {})) is None, m["name"]
    # and the accepted language-model cell does not list them
    other = spec.load_cell("glm-4.7-flash-train-ep8share")
    assert not [m for m in other.per_layer
                if m["name"].startswith("linattn.")]


def test_the_cell_is_what_the_issue_named():
    cell = spec.load_cell(toy_lm_family.CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "qwen3-next-80b-a3b", "train-lm-4k", 1)
    assert cell.config["runner"] == "train_lm_family"
    assert {m["name"] for m in cell.end_to_end} == {"items_s_chip",
                                                    "setup_s"}
    ref = spec.load_plugin("references", cell.config["reference"])
    calls = ref.kernel_calls(cell.config, 2, 4096, 5120.0)
    assert calls["delta_rule"] == {"rows": 2, "len": 4096, "heads": 32,
                                   "key_dim": 128, "value_dim": 128,
                                   "chunk": 64, "layers": 3}
