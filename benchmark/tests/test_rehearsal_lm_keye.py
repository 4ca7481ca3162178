"""CPU rehearsal of the ``keye-vl2-30b-a3b-train-ep8share-16k`` cell at toy
size (its own configuration, traffic and metric files through
``train_lm_sparse``): the result's key set, the numbers it compares (the
loss by part, the selection's flips), the ten ``sparseattn.*`` metrics the
cell brought (on a trace known by hand, and beside the real step program's
table), what a program without the new calls reads (the parent's), and the
selection the runner hands the reference."""
import json
import types

import numpy as np
import pytest

from benchmark.harness import costs, report, spec
from benchmark.references import keye_vl2
from benchmark.runners import train_lm_sparse
from benchmark.tests import toy, toy_lm_keye, toy_lm_sdar
from benchmark.tests.test_rehearsal_lm_dense import _checks, _note

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
US = 1000
KERNELS = {"sparseattn.select_ms", "sparseattn.select_roofline",
           "sparseattn.core_fwd_ms", "sparseattn.core_fwd_roofline",
           "sparseattn.core_bwd_ms", "sparseattn.core_bwd_roofline"}
SCOPES = {"sparseattn.layer_ms", "sparseattn.indexer_ms",
          "sparseattn.indexer_loss_ms"}
NEW = KERNELS | SCOPES | {"sparseattn.selected_share"}
PEAKS = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}
CALL = {"rows": 1, "len": 16384, "heads": 32, "kv_heads": 4,
        "head_dim": 128, "top_k": 2048}
CUSTOM = ' custom-call(%a, %b, %c), custom_call_target="tpu_custom_call"'
DURS = {"fusion.1": 100, "topk_mask.2": 30, "selected_attention_fwd.3": 40,
        "selected_attention_fwd.4": 44, "selected_attention_bwd.5": 90,
        "_flash_forward.6": 300, "long_attention_bwd.7": 200}


def _op(name, start, dur):
    kind = CUSTOM if any(w in name for w in ("attention", "flash", "topk")) \
        else " fusion(%p)"
    shape = "(bf16[1,32,16384,128]{3,2,1,0}, bf16[2])" if "bwd" in name \
        else "s8[1,32,16384,512]{3,2,1,0}" if "topk" in name \
        else "bf16[1,32,32,536,128]{4,3,2,1,0}"
    return [f"%{name} = {shape}{kind}", start * US, dur * US]


def _events(names=tuple(DURS)):
    """One chip, a window of [0, 2000) us, two step programs of 1000 us.
    In each: a fusion 100, a choice 30, two selected forward calls 40 and
    44, a selected backward call 90, a causal forward 300 and backward
    200."""
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


def _rin(events, calls=True, counters=None):
    return types.SimpleNamespace(
        events=events, peaks=PEAKS, counters=counters or {},
        work={"kernel_calls": {
            "selected_fwd": CALL, "selected_bwd": CALL,
            "topk_mask": {"rows": 1, "len": 16384}} if calls else {}})


def _metric(name, cell=toy_lm_keye.CELL):
    return next(m for m in spec.load_cell(cell).per_layer
                if m["name"] == name)


def _read(rin, name, cell=toy_lm_keye.CELL):
    m = _metric(name, cell)
    return spec.load_plugin("readers", m["reader"]).read(rin, **m["args"])


def test_the_kernels_metrics_on_a_trace_known_by_hand():
    rin = _rin(_events())
    got = {name: _read(rin, name) for name in KERNELS}
    assert got["sparseattn.select_ms"] == pytest.approx(0.030)
    assert got["sparseattn.core_fwd_ms"] == pytest.approx(0.084)
    assert got["sparseattn.core_bwd_ms"] == pytest.approx(0.090)
    fwd = costs.min_seconds(keye_vl2.selected_fwd_cost(CALL), PEAKS)
    # per call: two forward calls took 84 us together
    assert got["sparseattn.core_fwd_roofline"] == pytest.approx(
        100 * 2 * fwd / 84e-6)
    assert got["sparseattn.core_bwd_roofline"] == pytest.approx(
        100 * 2.5 * fwd / 90e-6)
    read = 16384 * 16385 / 2 * 4 / 819e9
    assert got["sparseattn.select_roofline"] == pytest.approx(
        100 * read / 30e-6)
    # the accepted readers of the causal calls (another cell's: this one
    # lists none of them) read those alone, not a selected call
    kimi = "kimi-linear-48b-a3b-train-ep32share-16k"
    assert _read(rin, "kernel.flash_attention_ms", kimi) \
        == pytest.approx(0.300)
    assert _read(rin, "kernel.flash_bwd_ms", kimi) == pytest.approx(0.200)
    sdar = toy_lm_sdar.CELL
    assert _read(rin, "diffattn.fwd_ms", sdar) is None
    # without the work's shape the shares say nothing; the times still do
    bare = _rin(_events(), calls=False)
    assert _read(bare, "sparseattn.core_fwd_roofline") is None
    assert _read(bare, "sparseattn.core_fwd_ms") == pytest.approx(0.084)
    # the share the ring counted
    assert _read(_rin(None, counters={
        "sparse_attention.selected_share": 0.2344}),
        "sparseattn.selected_share") == pytest.approx(0.2344)


def test_a_program_without_the_new_calls_reports_nothing():
    """A step whose calls are causal alone (every other family's, and the
    parent's), a run that was not traced: each reader returns None and
    does not raise."""
    causal = tuple(n for n in DURS if "selected" not in n and "topk" not in n)
    rin = _rin(_events(causal))
    assert [_read(rin, name) for name in sorted(NEW)] == [None] * 10
    untraced = types.SimpleNamespace(events=None, peaks={}, work={},
                                     counters={})
    assert [_read(untraced, name) for name in sorted(NEW)] == [None] * 10
    # no accepted cell gains one of the ten, and this cell reads none of
    # the causal calls' three nor the masked rows' four
    sdar = spec.load_cell(toy_lm_sdar.CELL)
    assert not [m for m in sdar.per_layer if m["name"] in NEW]
    mine = {m["name"] for m in spec.load_cell(toy_lm_keye.CELL).per_layer}
    assert not mine & {"kernel.flash_attention_ms", "kernel.flash_bwd_ms",
                       "kernel.flash_fwd_roofline", "diffattn.fwd_ms"}


def test_the_cells_calls_count_the_chosen_pairs_and_are_compute_bound():
    fwd, nbytes = keye_vl2.selected_fwd_cost(CALL)
    causal, _ = spec.load_plugin("references", "glm47_flash").flash_fwd_cost(
        {"rows": 1, "len": 16384, "heads": 32, "head_dim": 128})
    assert 0.23 < fwd / causal < 0.24           # 23.4% of the causal pairs
    assert fwd / 197e12 > nbytes / 819e9
    cfg = spec.load_cell(toy_lm_keye.CELL).config
    parts = keye_vl2._fwd_flops_per_item(cfg, 16384)
    assert parts["core"] == fwd
    calls = keye_vl2.kernel_calls(cfg, 1, 16384, 8192.0)
    assert calls["selected_fwd"] == calls["selected_bwd"] == CALL
    assert calls["topk_mask"] == {"rows": 1, "len": 16384}
    assert "flash_fwd" not in calls and calls["expert_matmul"]["held"] == 16


def test_the_reference_is_handed_the_programs_selection():
    """``_compared`` takes the selection out of what the run gathered and
    hands it on as ``observe``; the flips the reference reads of it are
    what ``compare`` reports."""
    cell = toy_lm_keye.cell()
    seen = {}
    real = keye_vl2.train_reference

    def spy(cfg, seed_, tokens, **kw):
        seen.update(kw, tokens=tokens)
        raise StopIteration
    keye_vl2.train_reference = spy
    try:
        with pytest.raises(StopIteration):
            train_lm_sparse._compared(cell, 5, train_lm_sparse._all_tokens(
                cell, 5), {"selection": "the program's"})
    finally:
        keye_vl2.train_reference = real
    assert seen["observe"] == "the program's" and seen["steps"] == 3
    assert seen["tokens"].shape == (3, 2, 32)
    flips = [(0.01, 0.002), (0.03, 0.004)]
    got = {"losses": [1.0], "main": [0.5], "mtp": [], "indexer": [0.5],
           "grad_norms": [1.0], "delta_norms": [1.0],
           "first_grad": [np.ones(2, np.float32)], "choices": []}
    want = dict(got, indexer=[0.4], routing=[], selection_flips=flips,
                selection_pairs=[(100, 100), (90, 100)])
    out = train_lm_sparse.compare(got, want)
    assert out["selection_flip_share"] == pytest.approx(0.02)
    assert out["selection_pairs_gap"] == pytest.approx(0.1)
    assert train_lm_sparse.limit_of("selection_pairs_gap") \
        == "selection_pairs_gap"
    assert out["selection_flip_margin"] == 0.004
    assert out["selection_flip_share_layer1"] == 0.03
    assert out["loss_indexer_step0_rel_gap"] == pytest.approx(0.25)
    assert train_lm_sparse.limit_of("selection_flip_share") \
        == "selection_flip_share"
    assert train_lm_sparse.limit_of("selection_flip_share_layer0") is None
    assert train_lm_sparse.limit_of("loss_indexer_step2_rel_gap") \
        == train_lm_sparse.limit_of("loss_step0_rel_gap") == "loss_rel_gap"
    assert train_lm_sparse.limit_of("loss_main_step1_rel_gap") \
        == "loss_main_rel_gap"
    assert train_lm_sparse.limit_of("first_grad_rel_diff_indexer") \
        == "grad_rel_diff_indexer"
    assert train_lm_sparse.limit_of(
        "first_grad_norm_worst_leaf_gap_indexer") == "grad_norm_gap_indexer"


def test_the_cell_at_toy_size(tmp_path, capsys):
    parts = toy.run(toy_lm_keye.cell(), tmp_path)
    line = json.loads(report.result_line(**parts))
    out = capsys.readouterr().out
    assert set(line) == KEYS, out
    assert line["correct"] is True and line["failed"] == 0, out
    assert set(line["metrics"]) == {"items_s_chip", "setup_s"}
    held = {f"{k}_step{s}_rel_gap" for k in ("loss", "loss_main",
                                             "loss_indexer")
            for s in range(3)} | {
        "first_grad_norm_worst_leaf_gap", "param_change_norm_worst_leaf_gap",
        "first_grad_rel_diff", "routing_flip_share", "routing_flip_margin",
        "first_grad_rel_diff_indexer", "selection_flip_share",
        "selection_flip_margin", "selection_pairs_gap",
        "window_compiles", "nonfinite_losses", "state_step_count_gap",
        "attention.flash_fallbacks", "sparse_attention.fallbacks"}
    assert set(_checks(out)) == held
    classes = ("attention", "experts", "indexer", "norms", "tables")
    assert set(_note(out, "compared_not_held")) == {
        f"{kind}_flip_{k}_layer{i}" for kind in ("routing", "selection")
        for k in ("share", "margin") for i in range(4)} | {
        f"first_grad_{k}_{c}" for k in ("rel_diff", "norm_worst_leaf_gap")
        for c in classes} - {"first_grad_rel_diff_indexer"}
    worst = _note(out, "worst_leaves")
    assert set(worst) == {"grad_norms", "delta_norms"}
    assert all(name in keye_vl2.leaf_names(toy_lm_keye.cell().config)
               for name in worst.values())
    ring = _note(out, "ring")
    assert set(ring) == {"steps", *keye_vl2.AUX}
    # 2 rows x 32 positions: 8 keys a query of the 24 that have more past,
    # t + 1 of the first 8; four layers
    pairs = 4 * 2 * keye_vl2.selected_pairs(32, 8)
    assert set(ring["sparse_attention.selected_pairs"]) == {float(pairs)}
    assert set(ring["sparse_attention.causal_pairs"]) == {
        float(4 * 2 * keye_vl2.causal_pairs(32))}
    assert all(v > 0 for v in ring["loss.indexer"])
    assert not any(ring["moe.overflow_layers"])
    note = _note(out, "selection")
    assert note["selected_share"] == pytest.approx(
        keye_vl2.selected_pairs(32, 8) / keye_vl2.causal_pairs(32), abs=1e-6)
    counters = _note(out, "program_counters")
    assert counters["attention.flash_fallbacks"] == 0
    assert counters["sparse_attention.fallbacks"] == 0
    # on the CPU XLA's forms run; on the chip the counters that must read
    # 8 after a run's two traces are the two .pallas
    assert counters["sparse_attention.core_calls.xla"] >= 8
    assert counters["sparse_attention.select_calls.xla"] >= 8
    assert counters["sparse_attention.core_calls.pallas"] == 0
    assert _note(out, "setup")["routing_s"] > 0     # the selections' pass


def test_traced_run_reports_the_new_metrics_beside_the_inherited(
        tmp_path, monkeypatch, capsys):
    """The toy cell, traced, with ``observability.annotate`` as
    ``harness/main.main`` sets it: the trainer publishes its step, and
    synthetic events made of that table's own names (a microsecond each;
    the CPU's profiler has no device plane) beside one call of each new
    kernel and a grouped product are read by the cell's own metric
    files."""
    from benchmark.harness import trace
    from benchmark.readers import scope_ms_per_step
    from mmlspark_tpu.observability import scopes
    from mmlspark_tpu.utils import config

    want, anywhere = {}, {}

    def events(self):
        table = scopes.table("jit_step")
        assert table, "the trainer published nothing"
        names = sorted(n for n, s in table.items()
                       if s.path and "fusion" in n)
        for scope in ("sparse_attention_layer", "grouped_attention",
                      "indexer", "indexer_scores", "indexer_loss",
                      "indexer_target", "lm_loss"):
            want[scope] = sum(scope in scope_ms_per_step._components(
                table[n].path) for n in names)
            anywhere[scope] = sum(
                scope in scope_ms_per_step._components(s.path)
                for s in table.values() if s.path)
        ops = [[f"%{n} = f32[2]{{0}} fusion(%p)", (10 + i) * US, US]
               for i, n in enumerate(names)]
        end = (10 + len(names)) * US
        for name, dur in (("topk_mask.2", 3),
                          ("selected_attention_fwd.3", 5),
                          ("selected_attention_bwd.4", 12)):
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
        parts = toy.run(toy_lm_keye.cell(), tmp_path, traced=True)
    finally:
        config.unset("observability.annotate")
        scopes.clear()
    out = capsys.readouterr().out
    assert parts["correct"] is True, out
    m = {k: v["value"] for k, v in parts["metrics"].items()}
    assert NEW <= set(m)
    # the scopes nest: the mixer holds the layer, the layer the indexer and
    # the loss, each of those its inner scope
    assert want["grouped_attention"] >= want["sparse_attention_layer"] \
        > want["indexer"] >= want["indexer_scores"] > 0
    assert want["sparse_attention_layer"] > want["indexer_loss"] > 0
    assert anywhere["indexer_loss"] > anywhere["indexer_target"] > 0
    assert want["lm_loss"] > 0
    assert m["sparseattn.layer_ms"] == pytest.approx(
        want["sparse_attention_layer"] * 1e-3)
    assert m["sparseattn.indexer_ms"] == pytest.approx(
        want["indexer"] * 1e-3)
    assert m["sparseattn.indexer_loss_ms"] == pytest.approx(
        want["indexer_loss"] * 1e-3)
    assert m["sparseattn.select_ms"] == pytest.approx(0.003)
    assert m["sparseattn.core_fwd_ms"] == pytest.approx(0.005)
    assert m["sparseattn.core_bwd_ms"] == pytest.approx(0.012)
    assert 0 < m["sparseattn.core_fwd_roofline"]
    assert 0 < m["sparseattn.core_bwd_roofline"]
    assert 0 < m["sparseattn.select_roofline"]
    assert m["sparseattn.selected_share"] == pytest.approx(
        keye_vl2.selected_pairs(32, 8) / keye_vl2.causal_pairs(32), abs=1e-6)
    assert m["model.attention_ms"] > 0 and m["loss.head_ms"] > 0
    assert m["moe.expert_matmul_ms"] == pytest.approx(0.020)
    assert {"moe.routed_path_ms", "moe.dispatch_combine_ms",
            "moe.load_max_over_mean", "moe.expert_matmul_roofline",
            "model.attention_ms", "model.ffn_ms", "model.other_ms",
            "loss.head_ms", "step.forward_ms", "trainer.step_ms",
            "model.mfu", "compile.window_compiles"} <= set(m)
    assert not [k for k in m if k.startswith((
        "linattn.", "ssm.", "mesh.", "shortconv.", "attn.window",
        "kernel.flash", "diffattn.", "kda.", "mla."))]


def test_the_cell_is_what_the_issue_named():
    cell = spec.load_cell(toy_lm_keye.CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "keye-vl-2.0-30b-a3b", "train-lm-16k", 1)
    assert cell.config["runner"] == "train_lm_sparse"
    assert cell.config["reference"] == "keye_vl2"
    kimi = spec.load_cell("kimi-linear-48b-a3b-train-ep32share-16k")
    assert cell.traffic == kimi.traffic           # the same file
    assert cell.config["program"]["zoo_args"] == {"gate_grad": False}
    assert cell.config["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert set(cell.config["limits"]) >= {
        "grad_norm_gap", "grad_rel_diff", "delta_norm_gap",
        "routing_flip_share", "routing_flip_margin",
        "selection_flip_share", "selection_flip_margin",
        "selection_pairs_gap", "loss_rel_gap", "loss_main_rel_gap",
        "grad_rel_diff_indexer", "grad_norm_gap_indexer"}
    assert cell.config["limits"]["selection_pairs_gap"] == 0
    assert "limits_not_set" not in cell.config
    assert cell.config["optimizer"]["learning_rate"] == 1e-5
    assert {m["name"] for m in cell.end_to_end} == {"items_s_chip",
                                                    "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert names >= NEW | {
        "trainer.step_ms", "trainer.syncs_per_step", "trainer.dispatch_ms",
        "trainer.steps_in_flight", "model.mfu", "model.copy_ms",
        "device.idle_share.train", "device.hbm_peak_gb.train",
        "trainer.step_hbm_gb", "model.attention_ms", "model.ffn_ms",
        "model.other_ms", "loss.head_ms", "attn.norm_turn_ms",
        "step.forward_ms", "step.recompute_ms", "step.backward_ms",
        "step.optimizer_ms", "step.unscoped_ms", "moe.expert_matmul_ms",
        "moe.expert_matmul_roofline", "moe.load_max_over_mean",
        "moe.routed_path_ms", "moe.dispatch_combine_ms"}
    assert not [n for n in names if n.startswith((
        "linattn.", "ssm.", "mesh.", "shortconv.", "attn.window",
        "kernel.", "diffattn.", "kda.", "mla."))]
    for m in cell.per_layer:
        if m["name"] in NEW:
            assert (m["moves"], m["workloads"]) == (
                "items_s_chip", [toy_lm_keye.CELL])
            assert m["layer"] == ("kernels" if m["name"] in KERNELS
                                  else "model step")
