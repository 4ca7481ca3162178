"""CPU rehearsal of the ``train_lm`` runner at toy size: the result's key
set, the numbers it compares, and what it hands the readers."""
import json

import pytest

from benchmark.harness import report, spec
from benchmark.tests import toy, toy_lm

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_train_lm_runner_at_toy_size(tmp_path, capsys):
    parts = toy.run(toy_lm.train_lm_cell(), tmp_path)
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
    held = {f"{k}_step{s}_rel_gap" for k in ("loss", "loss_main", "loss_mtp")
            for s in range(3)} | {
        "first_grad_norm_worst_leaf_gap", "param_change_norm_worst_leaf_gap",
        "first_grad_rel_diff", "routing_flip_share", "routing_flip_margin",
        "window_compiles", "nonfinite_losses", "state_step_count_gap",
        "attention.flash_fallbacks"}
    assert set(checks) == held
    segments = json.loads(next(l for l in out.splitlines()
                               if l.startswith("# segments"))[11:])
    assert segments["items_per_step"] == 2 and len(
        segments["items_s_chip"]) >= 2
    value = line["metrics"]["items_s_chip"]["value"]
    assert value == pytest.approx(segments["total_over_window"], abs=1e-3)
    # the four aux scalars ride the ring, fetched once after the window
    ring = json.loads(next(l for l in out.splitlines()
                           if l.startswith("# ring "))[7:])
    assert set(ring) == {"steps", "loss.main", "loss.mtp", "moe.slots_here",
                         "moe.load_max_over_mean"}
    counters = json.loads(next(l for l in out.splitlines() if l.startswith(
        "# program_counters"))[len("# program_counters "):])
    assert counters["gauges"]["moe.load_max_over_mean"] >= 1.0
    assert counters["moe.grouped_calls.ragged_dot"] >= 9


@pytest.mark.parametrize("spans, first", [
    ([2.4] * 10, 0),                       # every segment took its time
    ([0.006] + [2.4] * 10, 1),             # the host stalled before stamp 0
    ([0.006, 0.004] + [2.4] * 9, 2),
    ([2.4, 2.41, 0.9, 2.4, 2.39], 0),      # a short one later is not cut
    ([2.4, 2.0, 2.6, 2.4], 0),             # nor is scatter
])
def test_the_window_opens_past_segments_that_were_complete_already(
        spans, first):
    import numpy as np
    runner = spec.load_plugin("runners", "train_lm")
    stamps = list(100.0 + np.concatenate([[0.0], np.cumsum(spans)]))
    assert runner.opening(stamps) == first


def test_traced_run_reports_the_new_per_layer_metrics(tmp_path, monkeypatch):
    """No device plane on a CPU: the reduced trace is a synthetic one with
    the operations the new readers look for."""
    from benchmark.harness import trace
    ms = 1_000_000

    def events(self):
        ops, t = [], 10 * ms
        for name, dur in (
                ("%_flash_forward.3 = bf16[2,20,4096,256]{3,2,1,0} "
                 "custom-call(%a, %b, %c), custom_call_target="
                 "\"tpu_custom_call\"", 30 * ms),
                ("%ragged-dot-none.7 = f32[32768,1536]{1,0} custom-call("
                 "%x), custom_call_target=\"tpu_custom_call\"", 2 * ms),
                ("%ragged-dot-metadata.7 = (s32[9]{0}) custom-call(%g), "
                 "custom_call_target=\"tpu_custom_call\"", ms // 100),
                ("%copy.5 = bf16[8192,2048]{1,0} copy(%p)", ms),
                ("%fusion.1 = bf16[8192,2048]{1,0} fusion(%p)", 50 * ms)):
            ops.append([name, t, dur])
            t += dur
        return {"devices": {"0": {"ops": ops, "modules": [
            ["jit_step(123)", 10 * ms, t - 10 * ms]]}},
            "host": [["bench:window", 5 * ms, t, "python3"],
                     ["trainer:dispatch", 6 * ms, ms, "python3"]]}
    monkeypatch.setattr(trace.Tracer, "events", events)
    monkeypatch.setattr(trace.Tracer, "start", lambda self: None)
    monkeypatch.setattr(trace.Tracer, "open", lambda self: None)
    monkeypatch.setattr(trace.Tracer, "stop", lambda self: None)
    parts = toy.run(toy_lm.train_lm_cell(), tmp_path, traced=True)
    m = {k: v["value"] for k, v in parts["metrics"].items()}
    assert m["kernel.flash_attention_ms"] == pytest.approx(30.0)
    assert m["moe.expert_matmul_ms"] == pytest.approx(2.01)
    assert 0 < m["kernel.flash_fwd_roofline"] < 100
    assert 0 < m["moe.expert_matmul_roofline"] < 100
    assert m["moe.load_max_over_mean"] >= 1.0
    assert m["model.copy_ms"] == pytest.approx(1.0)
    assert {"trainer.step_ms", "trainer.syncs_per_step", "model.mfu",
            "compile.window_compiles", "device.idle_share.train"} <= set(m)


def test_a_program_without_the_kernels_reports_nothing_for_them():
    """The parent of this PR under these files: no such operation in its
    trace, no such counter from its runner; the readers return None."""
    from benchmark.harness.main import ReaderInput

    class Ctx:
        device = {"peaks": {"bf16_tflops": 197.0, "hbm_gbps": 819.0}}
    events = {"devices": {"0": {"ops": [["%fusion.1 = f32[] fusion()", 10,
                                         5]], "modules": []}},
              "host": [["bench:window", 0, 100, "python3"]]}
    rin = ReaderInput({"counters": {}, "work": {}}, events, Ctx(), {})
    cell = spec.load_cell("glm-4.7-flash-train-ep8share")
    new = [m for m in cell.per_layer if m["name"].startswith(
        ("kernel.flash", "moe."))]
    assert len(new) == 5
    for m in new:
        reader = spec.load_plugin("readers", m["reader"])
        assert reader.read(rin, **m.get("args", {})) is None, m["name"]
