"""CPU rehearsal of the ``laguna-xs.2-train-ep8share-8k`` cell at toy size
(its own configuration, traffic and metric files through
``train_lm_dense``): the result's key set, the numbers it compares, the
five ``attn.window_*`` metrics the cell brought (on a trace known by hand,
and on the real step program's table), what a program without a windowed
layer reads, and the band's cost against a count by loops."""
import json
import types

import pytest

from benchmark.harness import costs, report, spec
from benchmark.readers import scope_named_ms_per_step as scoped
from benchmark.references import laguna
from benchmark.tests import toy, toy_lm_laguna, toy_lm_lfm2
from benchmark.tests.test_rehearsal_lm_dense import _checks, _note

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
US = 1000
NEW = {"attn.window_fwd_ms", "attn.window_fwd_roofline",
       "attn.window_bwd_ms", "attn.window_bwd_roofline",
       "attn.window_layer_ms"}
PEAKS = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}
CALL = {"rows": 2, "len": 8192, "heads": 64, "head_dim": 128, "window": 512}
FWD = "jit(step)/loss_and_grad/jvp(Decoder)/"
BWD = "jit(step)/loss_and_grad/transpose(jvp(Decoder))/"
BAND = "block1/attn/grouped_attention/window_attention_layer/"
TABLE = {
    "fusion.1": (FWD + BAND + "attn_query/dot_general", ("loss_and_grad",)),
    "window_attention_fwd.2": (FWD + BAND + "window_attention_fwd",
                               ("loss_and_grad",)),
    "fusion.3": (FWD + BAND + "head_gate/mul", ("loss_and_grad",)),
    "window_attention_bwd.4": (BWD + BAND + "window_attention_bwd",
                               ("loss_and_grad",)),
    "_flash_forward.5": (FWD + "block0/attn/grouped_attention/pallas_call",
                         ("loss_and_grad",)),
    "fusion.6": (FWD + "block0/attn/grouped_attention/head_gate/mul",
                 ("loss_and_grad",)),
    "long_attention_bwd.7": (
        BWD + "block0/attn/grouped_attention/long_attention_bwd",
        ("loss_and_grad",)),
}
CUSTOM = ' custom-call(%a, %b, %c), custom_call_target="tpu_custom_call"'


def _op(name, start, dur):
    kind = CUSTOM if "attention" in name or "flash" in name \
        else " fusion(%p)"
    shape = "(bf16[2,64,8192,128]{3,2,1,0}, bf16[2])" if "bwd" in name \
        else "bf16[2,64,32,256,128]{4,3,2,1,0}"
    return [f"%{name} = {shape}{kind}", start * US, dur * US]


def _events(names=tuple(TABLE)):
    """One chip, a window of [0, 2000) us, two step programs of 1000 us.
    In each: fusion.1 100, the band's forward call 40, fusion.3 10, its
    backward call 90, the causal forward 300, fusion.6 10, the causal
    backward 200."""
    durs = dict(zip(TABLE, (100, 40, 10, 90, 300, 10, 200)))
    ops = []
    for base in (0, 1000):
        t = base
        for name in names:
            ops.append(_op(name, t, durs[name]))
            t += durs[name]
    return {"devices": {"0": {"ops": ops, "modules": [
        ["jit_step(1)", 0, 1000 * US], ["jit_step(1)", 1000 * US, 1000 * US],
        ["jit_eval(2)", 2000 * US, 100 * US]]}},
        "host": [["bench:window", 0, 2000 * US, "python3"]]}


def _rin(events, calls=True):
    return types.SimpleNamespace(
        events=events, peaks=PEAKS, work={"kernel_calls": {
            "window_fwd": CALL, "window_bwd": CALL, "flash_fwd": {
                "rows": 2, "len": 8192, "heads": 48, "head_dim": 128}}
            if calls else {}})


def _metric(name, cell=toy_lm_laguna.CELL):
    return next(m for m in spec.load_cell(cell).per_layer
                if m["name"] == name)


def _read(rin, name):
    m = _metric(name)
    return spec.load_plugin("readers", m["reader"]).read(rin, **m["args"])


def test_the_five_metrics_on_a_trace_known_by_hand(monkeypatch):
    monkeypatch.setattr(scoped.rules, "_table", lambda program: TABLE)
    rin = _rin(_events())
    got = {name: _read(rin, name) for name in NEW}
    assert got["attn.window_fwd_ms"] == pytest.approx(0.040)
    assert got["attn.window_bwd_ms"] == pytest.approx(0.090)
    # the whole mixer of the sliding layer, every phase: 100 + 40 + 10 + 90
    assert got["attn.window_layer_ms"] == pytest.approx(0.240)
    fwd = costs.min_seconds(laguna.window_fwd_cost(CALL), PEAKS)
    assert got["attn.window_fwd_roofline"] == pytest.approx(
        100 * fwd / 40e-6)
    assert got["attn.window_bwd_roofline"] == pytest.approx(
        100 * 2.5 * fwd / 90e-6)
    # the accepted readers of the causal calls go on reading those alone:
    # one shape of call, the full layers'
    assert _read(rin, "kernel.flash_attention_ms") == pytest.approx(0.300)
    assert _read(rin, "kernel.flash_bwd_ms") == pytest.approx(0.200)
    causal = costs.min_seconds(
        spec.load_plugin("references", "glm47_flash").flash_fwd_cost(
            rin.work["kernel_calls"]["flash_fwd"]), PEAKS)
    assert _read(rin, "kernel.flash_fwd_roofline") == pytest.approx(
        100 * causal / 300e-6)
    # without the work's shape the shares say nothing; the times still do
    bare = _rin(_events(), calls=False)
    assert _read(bare, "attn.window_fwd_roofline") is None
    assert _read(bare, "attn.window_fwd_ms") == pytest.approx(0.040)


def test_a_program_without_a_windowed_layer_reports_nothing(monkeypatch):
    """A step whose calls are causal alone (every other family's, and the
    parent's), a program that publishes no table, a run that was not
    traced: each reader returns None and does not raise."""
    causal = tuple(n for n in TABLE if "window" not in n
                   and n not in ("fusion.1", "fusion.3"))
    table = {k: v for k, v in TABLE.items() if k in causal}
    for tab in (table, {}, None):
        monkeypatch.setattr(scoped.rules, "_table", lambda program: tab)
        rin = _rin(_events(causal))
        assert [_read(rin, name) for name in sorted(NEW)] == [None] * 5
    untraced = types.SimpleNamespace(events=None, peaks={}, work={})
    assert [_read(untraced, name) for name in sorted(NEW)] == [None] * 5
    # no accepted cell gains one of the five
    lfm2 = spec.load_cell(toy_lm_lfm2.CELL)
    assert not [m for m in lfm2.per_layer if m["name"] in NEW]


@pytest.mark.parametrize("length,window", [(16, 4), (16, 1), (8, 8),
                                           (8, 20), (64, 17)])
def test_the_bands_cost_is_a_count_by_loops(length, window):
    """``window_fwd_cost``'s pairs against the (query, key) pairs a loop
    counts, 4 FLOPs a pair and head channel (two products, a multiply and
    an add each); the backward's five products are 2.5 times that; bytes
    are 4 and 7 arrays of the call's shape in bfloat16."""
    pairs = sum(1 for i in range(length) for j in range(length)
                if 0 <= i - j < window)
    call = {"rows": 3, "len": length, "heads": 5, "head_dim": 16,
            "window": window}
    flops, nbytes = laguna.window_fwd_cost(call)
    assert flops == 3 * 4 * pairs * 5 * 16
    assert nbytes == 3 * 4 * length * 5 * 16 * 2
    back, back_bytes = laguna.window_bwd_cost(call)
    assert back == 2.5 * flops and back_bytes == nbytes * 7 / 4
    if window >= length:        # the causal half, as glm47_flash counts it
        assert pairs == length * (length + 1) // 2


def test_the_cells_band_is_an_eighth_of_a_causal_half():
    fwd, _ = laguna.window_fwd_cost(CALL)
    causal, _ = spec.load_plugin("references", "glm47_flash").flash_fwd_cost(
        {**CALL})
    assert 0.118 < fwd / causal < 0.125
    # compute-bound at the chip's peaks: 0.26 TFLOP against 0.55 GB
    flops, nbytes = laguna.window_fwd_cost(CALL)
    assert flops / 197e12 > nbytes / 819e9


def test_the_cell_at_toy_size(tmp_path, capsys):
    parts = toy.run(toy_lm_laguna.cell(), tmp_path)
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
    assert counters["attention.flash_fallbacks"] == 0
    assert _note(out, "setup")["routing_s"] > 0         # the routing pass


def test_traced_run_reports_the_layers_time_beside_the_inherited(
        tmp_path, monkeypatch, capsys):
    """The toy cell, traced, with ``observability.annotate`` as
    ``harness/main.main`` sets it: the trainer publishes its step, and
    synthetic events made of that table's own names (a microsecond each;
    the CPU's profiler has no device plane) beside one call of each of the
    four attention kernels and a grouped product are read by the cell's
    own metric files."""
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
        for scope in ("window_attention_layer", "head_gate",
                      "grouped_attention"):
            want[scope] = sum(scope in scope_ms_per_step._components(
                table[n].path) for n in names)
        ops = [[f"%{n} = f32[2]{{0}} fusion(%p)", (10 + i) * US, US]
               for i, n in enumerate(names)]
        end = (10 + len(names)) * US
        for name, dur in (("_flash_forward.1", 30),
                          ("window_attention_fwd.2", 5),
                          ("long_attention_bwd.3", 60),
                          ("window_attention_bwd.4", 12)):
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
        parts = toy.run(toy_lm_laguna.cell(), tmp_path, traced=True)
    finally:
        config.unset("observability.annotate")
        scopes.clear()
    out = capsys.readouterr().out
    assert parts["correct"] is True, out
    m = {k: v["value"] for k, v in parts["metrics"].items()}
    assert NEW <= set(m)
    # three sliding layers of five: most of the attention scopes' fusions,
    # and a gate in every layer
    assert want["grouped_attention"] > want["window_attention_layer"] > 20
    assert want["head_gate"] >= 5
    assert m["attn.window_layer_ms"] == pytest.approx(
        want["window_attention_layer"] * 1e-3)
    # the outer scope is one of PARTS' names for attention: the sliding
    # layers' time lies in model.attention_ms with no edit to that reader
    assert m["model.attention_ms"] >= m["attn.window_layer_ms"]
    assert m["attn.window_fwd_ms"] == pytest.approx(0.005)
    assert m["attn.window_bwd_ms"] == pytest.approx(0.012)
    assert m["kernel.flash_attention_ms"] == pytest.approx(0.030)
    assert m["kernel.flash_bwd_ms"] == pytest.approx(0.060)
    assert 0 < m["attn.window_fwd_roofline"] \
        and 0 < m["attn.window_bwd_roofline"]
    assert m["moe.expert_matmul_ms"] == pytest.approx(0.020)
    assert {"moe.routed_path_ms", "moe.dispatch_combine_ms",
            "moe.load_max_over_mean", "model.attention_ms", "model.ffn_ms",
            "loss.head_ms", "step.forward_ms", "trainer.step_ms",
            "model.mfu", "compile.window_compiles",
            "kernel.flash_fwd_roofline"} <= set(m)
    assert not [k for k in m if k.startswith((
        "linattn.", "ssm.", "mesh.", "shortconv."))]


def test_the_cell_is_what_the_issue_named():
    cell = spec.load_cell(toy_lm_laguna.CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "laguna-xs.2", "train-lm-8k-x2", 1)
    assert cell.config["runner"] == "train_lm_dense"
    assert cell.config["reference"] == "laguna"
    assert {k: cell.traffic[k] for k in (
        "batch_per_chip", "tokens_per_row", "resident_batches",
        "segment_steps", "check_steps", "reference_block_rows",
        "trace_seconds")} == {
        "batch_per_chip": 2, "tokens_per_row": 8192, "resident_batches": 4,
        "segment_steps": 4, "check_steps": 3, "reference_block_rows": 1,
        "trace_seconds": 4}
    assert cell.config["program"]["zoo_args"]["gate_grad"] is False
    assert set(cell.config["limits"]) == {
        "loss_rel_gap", "grad_norm_gap", "grad_rel_diff", "delta_norm_gap",
        "routing_flip_share", "routing_flip_margin"}
    # every published width, head count, the window and both rotary rules
    cfg = cell.config
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["sliding_window"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["num_key_value_heads"]) == (
        2048, 128, 512, 8192, 512, 512, 8, 8)
    assert cfg["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert cfg["layer_types"] == ["full_attention"] + [
        "sliding_attention"] * 3 + ["full_attention"]
    assert cfg["rope_parameters"]["full_attention"]["factor"] == 64
    assert cfg["rope_parameters"]["sliding_attention"]["rope_theta"] == 10000
    assert cfg["deployment"]["chips_sharing_each_layer"] == 8
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
    assert not [n for n in names if n.startswith((
        "linattn.", "ssm.", "mesh.", "shortconv."))]
    for m in cell.per_layer:
        if m["name"] in NEW:
            assert (m["moves"], m["source"]) == ("items_s_chip",
                                                 "device_trace")
