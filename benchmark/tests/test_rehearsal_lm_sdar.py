"""CPU rehearsal of the ``sdar-30b-a3b-train-ep8share-4k`` cell at toy size
(its own configuration, traffic and metric files through
``train_lm_diffusion``): the result's key set, the numbers it compares, the
four ``diffattn.*`` metrics the cell brought (on a trace known by hand, and
beside the real step program's table), what a program without a masked call
reads, the masked call's cost against a count by loops, and the noise the
runner and the reference share."""
import json
import types

import numpy as np
import pytest

from benchmark.harness import costs, report, spec
from benchmark.references import sdar_moe
from benchmark.runners import train_lm_diffusion
from benchmark.tests import toy, toy_lm_lfm2, toy_lm_sdar
from benchmark.tests.test_rehearsal_lm_dense import _checks, _note

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
US = 1000
NEW = {"diffattn.fwd_ms", "diffattn.fwd_roofline", "diffattn.bwd_ms",
       "diffattn.bwd_roofline"}
PEAKS = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}
CALL = {"rows": 4, "len": 4096, "block": 4, "heads": 32, "head_dim": 128}
CUSTOM = ' custom-call(%a, %b, %c), custom_call_target="tpu_custom_call"'
DURS = {"fusion.1": 100, "block_diffusion_attention_fwd.2": 40,
        "block_diffusion_attention_fwd.3": 44,
        "block_diffusion_attention_bwd.4": 90, "_flash_forward.5": 300,
        "long_attention_bwd.7": 200}


def _op(name, start, dur):
    kind = CUSTOM if "attention" in name or "flash" in name \
        else " fusion(%p)"
    shape = "(bf16[4,32,8192,128]{3,2,1,0}, bf16[2])" if "bwd" in name \
        else "bf16[4,32,16,544,128]{4,3,2,1,0}"
    return [f"%{name} = {shape}{kind}", start * US, dur * US]


def _events(names=tuple(DURS)):
    """One chip, a window of [0, 2000) us, two step programs of 1000 us.
    In each: a fusion 100, two masked forward calls 40 and 44, a masked
    backward call 90, a causal forward 300 and backward 200."""
    ops = []
    for base in (0, 1000):
        t = base
        for name in names:
            ops.append(_op(name, t, DURS[name]))
            t += DURS[name]
    return {"devices": {"0": {"ops": ops, "modules": [
        ["jit_step(1)", 0, 1000 * US], ["jit_step(1)", 1000 * US, 1000 * US],
        ["jit_eval(2)", 2000 * US, 100 * US]]}},
        "host": [["bench:window", 0, 2000 * US, "python3"]]}


def _rin(events, calls=True):
    return types.SimpleNamespace(
        events=events, peaks=PEAKS, work={"kernel_calls": {
            "block_diffusion_fwd": CALL, "block_diffusion_bwd": CALL,
            "flash_fwd": {"rows": 4, "len": 8192, "heads": 32,
                          "head_dim": 128}} if calls else {}})


def _metric(name, cell=toy_lm_sdar.CELL):
    return next(m for m in spec.load_cell(cell).per_layer
                if m["name"] == name)


def _read(rin, name, cell=toy_lm_sdar.CELL):
    m = _metric(name, cell)
    return spec.load_plugin("readers", m["reader"]).read(rin, **m["args"])


def test_the_four_metrics_on_a_trace_known_by_hand():
    rin = _rin(_events())
    got = {name: _read(rin, name) for name in NEW}
    assert got["diffattn.fwd_ms"] == pytest.approx(0.084)
    assert got["diffattn.bwd_ms"] == pytest.approx(0.090)
    fwd = costs.min_seconds(sdar_moe.block_diffusion_fwd_cost(CALL), PEAKS)
    # per call: two forward calls took 84 us together
    assert got["diffattn.fwd_roofline"] == pytest.approx(
        100 * 2 * fwd / 84e-6)
    assert got["diffattn.bwd_roofline"] == pytest.approx(
        100 * 2.5 * fwd / 90e-6)
    # the accepted readers of the causal calls (another cell's: this one
    # lists none of them) read those alone, not a masked call
    lfm2 = toy_lm_lfm2.CELL
    assert _read(rin, "kernel.flash_attention_ms", lfm2) \
        == pytest.approx(0.300)
    assert _read(rin, "kernel.flash_bwd_ms", lfm2) == pytest.approx(0.200)
    # without the work's shape the shares say nothing; the times still do
    bare = _rin(_events(), calls=False)
    assert _read(bare, "diffattn.fwd_roofline") is None
    assert _read(bare, "diffattn.fwd_ms") == pytest.approx(0.084)


def test_a_program_without_a_masked_call_reports_nothing():
    """A step whose calls are causal alone (every other family's, and the
    parent's), a run that was not traced: each reader returns None and
    does not raise."""
    causal = tuple(n for n in DURS if "block_diffusion" not in n)
    rin = _rin(_events(causal))
    assert [_read(rin, name) for name in sorted(NEW)] == [None] * 4
    untraced = types.SimpleNamespace(events=None, peaks={}, work={})
    assert [_read(untraced, name) for name in sorted(NEW)] == [None] * 4
    # no accepted cell gains one of the four, and this cell reads none of
    # the causal calls' three
    lfm2 = spec.load_cell(toy_lm_lfm2.CELL)
    assert not [m for m in lfm2.per_layer if m["name"] in NEW]
    mine = {m["name"] for m in spec.load_cell(toy_lm_sdar.CELL).per_layer}
    assert not mine & {"kernel.flash_attention_ms", "kernel.flash_bwd_ms",
                       "kernel.flash_fwd_roofline"}


@pytest.mark.parametrize("length,block", [(16, 4), (16, 1), (8, 8), (12, 3),
                                          (64, 16)])
def test_the_masked_calls_cost_is_a_count_by_loops(length, block):
    """``block_diffusion_fwd_cost``'s pairs against the (query, key) pairs
    a loop counts from the three cases, 4 FLOPs a pair and head channel
    (two products, a multiply and an add each); the backward's five
    products are 2.5 times that; bytes are 4 and 7 arrays of ``2 L``
    positions in bfloat16."""
    pairs = 0
    for i in range(2 * length):
        for j in range(2 * length):
            bi, bj = (i % length) // block, (j % length) // block
            if i < length:
                pairs += (bj == bi) if j < length else (bj < bi)
            else:
                pairs += j >= length and bj <= bi
    assert pairs == length * length + length * block \
        == sdar_moe.live_pairs(length, block)
    call = {"rows": 3, "len": length, "block": block, "heads": 5,
            "head_dim": 16}
    flops, nbytes = sdar_moe.block_diffusion_fwd_cost(call)
    assert flops == 3 * 4 * pairs * 5 * 16
    assert nbytes == 3 * 4 * 2 * length * 5 * 16 * 2
    back, back_bytes = sdar_moe.block_diffusion_bwd_cost(call)
    assert back == 2.5 * flops and back_bytes == nbytes * 7 / 4


def test_the_cells_call_is_half_a_causal_call_and_compute_bound():
    fwd, nbytes = sdar_moe.block_diffusion_fwd_cost(CALL)
    causal, _ = spec.load_plugin("references", "glm47_flash").flash_fwd_cost(
        {"rows": 4, "len": 8192, "heads": 32, "head_dim": 128})
    assert 0.5 < fwd / causal < 0.501
    assert fwd / 197e12 > nbytes / 819e9
    # and the step's required work counts the live pairs, not the square
    cfg = spec.load_cell(toy_lm_sdar.CELL).config
    parts = sdar_moe._fwd_flops_per_item(cfg, 4096)
    assert parts["core"] == 4 * 32 * 128 * sdar_moe.live_pairs(4096, 4)
    assert 0.40 < parts["core"] / parts["layer"] < 0.42
    assert sdar_moe.train_flops_per_item(cfg, 4096) == pytest.approx(
        3 * (3 * parts["layer"] + parts["last_layer"] + parts["head"]))
    assert parts["last_layer"] < 0.55 * parts["layer"]


def test_noise_is_reproducible_and_the_same_for_runner_and_reference():
    cell = toy_lm_sdar.cell()
    seed = 2 ** 31 + 77
    a, b = (train_lm_diffusion._rows(cell, seed) for _ in range(2))
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert set(a) == {"tokens", "noised", "weight"}
    mask = sdar_moe.mask_token(cell.config)
    assert mask == cell.config["vocab_size"] - 1
    assert a["tokens"].max() < mask             # clean ids below the mask
    noised, weight = sdar_moe.noise(seed, a["tokens"], 4, 0.001, mask)
    assert np.array_equal(noised, a["noised"])
    assert np.array_equal(weight, a["weight"])
    assert not np.array_equal(
        train_lm_diffusion._rows(cell, seed + 1)["noised"], a["noised"])
    # the reference is handed these very arrays
    seen = {}
    real = sdar_moe.train_reference

    def spy(cfg, seed_, tokens, noised, weight, **kw):
        seen.update(tokens=tokens, noised=noised, weight=weight)
        raise StopIteration
    sdar_moe.train_reference = spy
    try:
        with pytest.raises(StopIteration):
            train_lm_diffusion._reference(cell, seed, a)
    finally:
        sdar_moe.train_reference = real
    assert seen["noised"].shape == (3, 2, 32)
    assert np.array_equal(seen["noised"].reshape(6, 32), a["noised"][:6])
    assert np.array_equal(seen["weight"].reshape(6, 32), a["weight"][:6])
    # a traffic file that disagrees with the configuration is refused
    cell.traffic["block_length"] = 8
    with pytest.raises(ValueError, match="block_length"):
        train_lm_diffusion._rows(cell, seed)


def test_the_cell_at_toy_size(tmp_path, capsys):
    parts = toy.run(toy_lm_sdar.cell(), tmp_path)
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
        "attention.flash_fallbacks"}
    assert set(_checks(out)) == held
    assert set(_note(out, "compared_not_held")) == {
        f"routing_flip_{k}_layer{i}" for k in ("share", "margin")
        for i in range(4)}
    ring = _note(out, "ring")
    assert set(ring) == {"steps", "loss.main", "diffusion.masked_share",
                         "moe.slots_here", "moe.load_max_over_mean",
                         "moe.overflow_layers"}
    # 2 rows x 64 positions x 2 choices x 4 layers, half of them held here
    # by an even router; no layer ran at full size
    assert all(300 < v < 720 for v in ring["moe.slots_here"])
    assert not any(ring["moe.overflow_layers"])
    assert all(0.25 < v < 0.75 for v in ring["diffusion.masked_share"])
    note = _note(out, "diffusion")
    assert (note["block_length"], note["noise_eps"]) == (4, 0.001)
    assert 0.25 < note["masked_share"] < 0.75
    counters = _note(out, "program_counters")
    assert counters["moe.grouped_calls.ragged_dot"] >= 12
    assert counters["attention.flash_fallbacks"] == 0
    # on the CPU the masked reference path runs; on the chip the counter
    # that must read 4 a traced step is .block_diffusion
    assert counters["attention.fused_calls.reference"] >= 4
    assert counters["attention.fused_calls.flash"] == 0
    assert _note(out, "setup")["routing_s"] > 0         # the routing pass


def test_traced_run_reports_the_four_new_metrics_beside_the_inherited(
        tmp_path, monkeypatch, capsys):
    """The toy cell, traced, with ``observability.annotate`` as
    ``harness/main.main`` sets it: the trainer publishes its step, and
    synthetic events made of that table's own names (a microsecond each;
    the CPU's profiler has no device plane) beside one call of each masked
    kernel and a grouped product are read by the cell's own metric
    files."""
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
        for scope in ("block_diffusion_attention", "grouped_attention",
                      "lm_loss"):
            want[scope] = sum(scope in scope_ms_per_step._components(
                table[n].path) for n in names)
        ops = [[f"%{n} = f32[2]{{0}} fusion(%p)", (10 + i) * US, US]
               for i, n in enumerate(names)]
        end = (10 + len(names)) * US
        for name, dur in (("block_diffusion_attention_fwd.2", 5),
                          ("block_diffusion_attention_bwd.4", 12)):
            ops.append(_op(name, end // US, dur))
            end += dur * US
        ops.append(["%ragged-dot-none.7 = bf16[512,32]{1,0} custom-call(%a, "
                    "%b), custom_call_target=\"tpu_custom_call\"", end,
                    20 * US])
        end += 20 * US
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
        parts = toy.run(toy_lm_sdar.cell(), tmp_path, traced=True)
    finally:
        config.unset("observability.annotate")
        scopes.clear()
    out = capsys.readouterr().out
    assert parts["correct"] is True, out
    m = {k: v["value"] for k, v in parts["metrics"].items()}
    assert NEW <= set(m)
    # the masked call's scope lies inside the mixer's, which is one of
    # PARTS' names for attention: model.attention_ms sees it with no edit
    assert want["grouped_attention"] > want["block_diffusion_attention"] > 0
    assert want["lm_loss"] > 0
    assert m["diffattn.fwd_ms"] == pytest.approx(0.005)
    assert m["diffattn.bwd_ms"] == pytest.approx(0.012)
    assert 0 < m["diffattn.fwd_roofline"] and 0 < m["diffattn.bwd_roofline"]
    assert m["model.attention_ms"] > 0 and m["loss.head_ms"] > 0
    assert m["moe.expert_matmul_ms"] == pytest.approx(0.020)
    assert {"moe.routed_path_ms", "moe.dispatch_combine_ms",
            "moe.load_max_over_mean", "moe.expert_matmul_roofline",
            "model.attention_ms", "model.ffn_ms", "model.other_ms",
            "loss.head_ms", "step.forward_ms", "trainer.step_ms",
            "model.mfu", "compile.window_compiles"} <= set(m)
    assert not [k for k in m if k.startswith((
        "linattn.", "ssm.", "mesh.", "shortconv.", "attn.window",
        "kernel.flash"))]


def test_the_cell_is_what_the_issue_named():
    cell = spec.load_cell(toy_lm_sdar.CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "sdar-30b-a3b", "train-diffusion-4k-x4", 1)
    assert cell.config["runner"] == "train_lm_diffusion"
    assert cell.config["reference"] == "sdar_moe"
    lfm2 = spec.load_cell(toy_lm_lfm2.CELL).traffic
    assert {k: v for k, v in cell.traffic.items()
            if k not in ("name", "kind", "why")} == {
        **{k: v for k, v in lfm2.items()
           if k not in ("name", "kind", "why")},
        "tokens_per_row": 4096, "block_length": 4, "noise_eps": 0.001}
    assert cell.config["program"]["zoo_args"] == {"gate_grad": False,
                                                  "block_length": 4}
    # the losses are printed and not held (``limits_not_set`` says why)
    assert set(cell.config["limits"]) == {
        "grad_norm_gap", "grad_rel_diff", "delta_norm_gap",
        "routing_flip_share", "routing_flip_margin"}
    assert set(cell.config["limits_not_set"]) == {"loss_rel_gap"}
    assert cell.config["optimizer"]["learning_rate"] == 1e-5
    assert {m["name"] for m in cell.end_to_end} == {"items_s_chip",
                                                    "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert names >= NEW | {
        "trainer.step_ms", "trainer.syncs_per_step", "trainer.dispatch_ms",
        "trainer.steps_in_flight", "model.mfu", "model.copy_ms",
        "device.idle_share.train", "device.hbm_peak_gb.train",
        "model.attention_ms", "model.ffn_ms", "model.other_ms",
        "loss.head_ms", "step.forward_ms", "step.recompute_ms",
        "step.backward_ms", "step.optimizer_ms", "step.unscoped_ms",
        "moe.expert_matmul_ms", "moe.expert_matmul_roofline",
        "moe.load_max_over_mean", "moe.routed_path_ms",
        "moe.dispatch_combine_ms"}
    assert not [n for n in names if n.startswith((
        "linattn.", "ssm.", "mesh.", "shortconv.", "attn.window",
        "kernel."))]
    for m in cell.per_layer:
        if m["name"] in NEW:
            assert (m["moves"], m["source"], m["layer"]) == (
                "items_s_chip", "device_trace", "kernels")
