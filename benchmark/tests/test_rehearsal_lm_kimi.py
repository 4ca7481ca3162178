"""CPU rehearsal of the ``kimi-linear-48b-a3b-train-ep32share-16k`` cell at
toy size (its own configuration, traffic and metric files through
``train_lm_family``): the result's key set, the numbers it compares, the
six metrics the cell brought (on a trace known by hand, and on the real
step program's table), what a program without the layer reads, and the
kernels' costs against counts by hand."""
import json
import re
import types

import pytest

from benchmark.harness import costs, report, spec
from benchmark.readers import scope_named_ms_per_step as scoped
from benchmark.references import glm47_flash, kimi_linear, qwen3_next
from benchmark.tests import toy, toy_lm_family, toy_lm_kimi
from benchmark.tests.test_rehearsal_lm_dense import _checks, _note

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
US = 1000
NEW = {"kda.layer_ms", "kda.chunk_kernel_ms", "kda.chunk_kernel_roofline",
       "kda.state_walk_ms", "kda.state_walk_roofline",
       "mla.flash_fwd_roofline"}
PEAKS = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}
RULE = {"rows": 1, "len": 16384, "heads": 32, "key_dim": 128,
        "value_dim": 128, "chunk": 64, "layers": 4}
FLASH = {"rows": 1, "len": 16384, "heads": 32, "key_dim": 192,
         "value_dim": 128}
FWD = "jit(step)/loss_and_grad/jvp(Decoder)/"
BWD = "jit(step)/loss_and_grad/transpose(jvp(Decoder))/"
KDA = "block1/block1.mix/attn/kimi_delta_attention/"
# as the step compiled for a described v5e names them
WALK = ("%while.7 = (s32[]{:T(128)}, f32[1,32,128,128]{3,2,1,0:T(8,128)"
        "S(1)}, f32[256,1,32,128,128]{4,3,2,1,0:T(8,128)}, f32[256,1,32,64,"
        "128]{4,3,2,1,0:T(8,128)}, bf16[256,1,32,64,128]")
CUSTOM = ' custom-call(%a, %b), custom_call_target="tpu_custom_call"'
OPS = {     # name -> (text, us, the table's path)
    "fusion.1": ("%fusion.1 = bf16[16384,4096]{1,0} fusion(%p)", 100,
                 FWD + KDA + "attn_query/dot_general"),
    "kda_chunk_fwd.2": ("%kda_chunk_fwd.2 = (bf16[256,1,32,64,128]{4,3,2,1,"
                        "0}, f32[256,1,32,64,128])" + CUSTOM, 40,
                        FWD + KDA + "kda_chunk_fwd"),
    "while.7": (WALK, 30, FWD + KDA + "kda_state_walk/while"),
    "kda_chunk_out.3": ("%kda_chunk_out.3 = f32[1,16384,4096]{2,1,0}"
                        + CUSTOM, 20, FWD + KDA + "kda_chunk_out"),
    "kda_chunk_out_bwd.4": ("%kda_chunk_out_bwd.4 = (bf16[256,1,32,64,128])"
                            + CUSTOM, 25, BWD + KDA + "kda_chunk_out_bwd"),
    "kda_chunk_bwd.5": ("%kda_chunk_bwd.5 = (f32[1,16384,4096]{2,1,0})"
                        + CUSTOM, 80, BWD + KDA + "kda_chunk_bwd"),
    "_flash_forward.6": ("%_flash_forward.6 = bf16[1,32,16,1048,128]{4,3,2,"
                         "1,0}" + CUSTOM, 300,
                         FWD + "block3/block3.mix/attn/mla_attention/"
                         "pallas_call"),
    "fusion.8": ("%fusion.8 = bf16[16384,2304]{1,0} fusion(%p)", 50,
                 FWD + "block3/block3.mix/attn/mla_attention/attn_out/"
                 "dot_general"),
}
TABLE = {name: (path, ("loss_and_grad",)) for name, (_, _, path)
         in OPS.items()}


def _events(names=tuple(OPS)):
    """One chip, a window of [0, 2000) us, two step programs of 1000 us."""
    ops = []
    for base in (0, 1000):
        t = base
        for name in names:
            text, dur, _ = OPS[name]
            ops.append([text, t * US, dur * US])
            t += dur
    return {"devices": {"0": {"ops": ops, "modules": [
        ["jit_step(1)", 0, 1000 * US], ["jit_step(1)", 1000 * US, 1000 * US],
        ["jit_eval(2)", 2000 * US, 100 * US]]}},
        "host": [["bench:window", 0, 2000 * US, "python3"]]}


def _rin(events, calls=True):
    return types.SimpleNamespace(
        events=events, peaks=PEAKS, work={"kernel_calls": {
            "kda_chunk": RULE, "kda_walk": RULE, "flash_fwd": FLASH}
            if calls else {}})


def _metric(name, cell=toy_lm_kimi.CELL):
    return next(m for m in spec.load_cell(cell).per_layer
                if m["name"] == name)


def _read(rin, name, cell=toy_lm_kimi.CELL):
    m = _metric(name, cell)
    return spec.load_plugin("readers", m["reader"]).read(rin, **m["args"])


def test_the_six_metrics_on_a_trace_known_by_hand(monkeypatch):
    monkeypatch.setattr(scoped.rules, "_table", lambda program: TABLE)
    rin = _rin(_events())
    got = {name: _read(rin, name) for name in NEW}
    # the whole mixer, every phase: 100 + 40 + 30 + 20 + 25 + 80
    assert got["kda.layer_ms"] == pytest.approx(0.295)
    assert got["kda.chunk_kernel_ms"] == pytest.approx(0.165)
    assert got["kda.state_walk_ms"] == pytest.approx(0.030)
    chunk = costs.min_seconds(kimi_linear.kda_chunk_cost(RULE), PEAKS)
    walk = costs.min_seconds(kimi_linear.kda_walk_cost(RULE), PEAKS)
    assert got["kda.chunk_kernel_roofline"] == pytest.approx(
        100 * chunk / 165e-6)
    assert got["kda.state_walk_roofline"] == pytest.approx(
        100 * walk / 30e-6)
    flash = costs.min_seconds(kimi_linear.flash_fwd_cost(FLASH), PEAKS)
    assert got["mla.flash_fwd_roofline"] == pytest.approx(
        100 * flash / 300e-6)
    # the accepted reader of the forward call's time finds it as it is
    assert _read(rin, "kernel.flash_attention_ms") == pytest.approx(0.300)
    # without the work's shapes the shares say nothing; the times still do
    bare = _rin(_events(), calls=False)
    assert _read(bare, "kda.chunk_kernel_roofline") is None
    assert _read(bare, "mla.flash_fwd_roofline") is None
    assert _read(bare, "kda.chunk_kernel_ms") == pytest.approx(0.165)


def test_the_scalar_rules_readers_do_not_read_the_new_calls():
    """``linattn.chunk_kernel_ms`` finds no ``kda_chunk_*`` call and this
    cell's pattern no ``delta_chunk_*`` one; the walk's pattern IS
    ``linattn.delta_rule_ms``'s (a state of the same shape first), which is
    why each metric lists its own cells."""
    theirs = re.compile(_metric("linattn.chunk_kernel_ms",
                                toy_lm_family.CELL)["args"]["pattern"])
    mine = re.compile(_metric("kda.chunk_kernel_ms")["args"]["pattern"])
    from benchmark.harness.trace import _short
    calls = [OPS[n][0] for n in OPS if n.startswith("kda_chunk")]
    assert len(calls) == 4 and all(mine.search(_short(c)) for c in calls)
    assert not any(theirs.search(_short(c)) for c in calls)
    assert not mine.search("%delta_chunk_fwd.1 = (bf16[2])" + CUSTOM)
    assert _metric("kda.state_walk_ms")["args"]["pattern"] == _metric(
        "linattn.delta_rule_ms", toy_lm_family.CELL)["args"]["pattern"]
    assert re.search(_metric("kda.state_walk_ms")["args"]["pattern"],
                     _short(WALK))
    names = {m["name"] for m in spec.load_cell(toy_lm_kimi.CELL).per_layer}
    assert not [n for n in names if n.startswith(("linattn.", "ssm."))]
    assert "kernel.flash_fwd_roofline" not in names
    other = {m["name"] for m in spec.load_cell(toy_lm_family.CELL).per_layer}
    assert not other & NEW


def test_a_program_without_the_layer_reports_nothing(monkeypatch):
    """A step with no KDA layer (every other family's, and the parent's,
    which has no such zoo entry and fails before a window), a program that
    publishes no table, a run that was not traced: each reader returns
    None and does not raise."""
    plain = ("fusion.8",)
    for tab in ({k: TABLE[k] for k in plain}, {}, None):
        monkeypatch.setattr(scoped.rules, "_table", lambda program: tab)
        rin = _rin(_events(plain))
        assert [_read(rin, name) for name in sorted(NEW)] == [None] * 6
    untraced = types.SimpleNamespace(events=None, peaks={}, work={})
    assert [_read(untraced, name) for name in sorted(NEW)] == [None] * 6


def test_the_costs_are_counts_by_hand():
    """The chunk calls' FLOPs are ``olmo_hybrid``'s count at 128 x 128
    (a product split by level counts once), the walk's
    ``qwen3_next.delta_rule_cost``'s, each with the decay's bytes a vector;
    the flash call's two products at their own widths."""
    chunks = 4 * 32 * 16384 / 64
    flops, nbytes = kimi_linear.kda_chunk_cost(RULE)
    inside = 2.0 * 64 * 64 * (3 * 128 + 2 * 128 + 64) + 2.0 * 64 * 128 * 128
    assert flops == chunks * 3 * inside
    scalar = spec.load_plugin("references", "olmo_hybrid").delta_chunk_cost(
        RULE)
    assert flops == scalar[0]
    # G is (64, 128) float32 a chunk where g was 64 numbers
    assert nbytes - scalar[1] == chunks * 3 * (64 * 128 * 4 - 64 * 4)
    wf, wb = kimi_linear.kda_walk_cost(RULE)
    sf, sb = qwen3_next.delta_rule_cost(RULE)
    assert wf == sf == chunks * 3 * 4 * 64 * 128 * 128
    # the chunk's decay of 128 float32: read forward, read again backward,
    # its gradient written
    assert wb - sb == chunks * 3 * 128 * 4
    ff, fb = kimi_linear.flash_fwd_cost(FLASH)
    assert ff == 2.0 * 16384 * 16384 / 2 * 32 * (192 + 128)
    assert fb == 2.0 * 16384 * 32 * (192 + 128) * 2
    # one width for all: the sibling's count at 160 is the same FLOPs
    assert ff == glm47_flash.flash_fwd_cost(
        {"rows": 1, "len": 16384, "heads": 32, "head_dim": 160})[0]
    # compute-bound at the chip's peaks
    assert ff / 197e12 > fb / 819e9


def test_the_cell_at_toy_size(tmp_path, capsys):
    parts = toy.run(toy_lm_kimi.cell(), tmp_path)
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
    # four KDA layers, the step traced twice (aux keys); the counter is
    # the process's, so earlier tests of a whole run add to it
    assert counters["linear_attention.calls.chunked"] >= 8
    assert counters["linear_attention.fallbacks"] == 0
    assert counters["attention.flash_fallbacks"] == 0
    assert "# step_high_water " in out


def test_traced_run_reports_the_layers_time_beside_the_inherited(
        tmp_path, monkeypatch, capsys):
    """The toy cell, traced, with ``observability.annotate`` as
    ``harness/main.main`` sets it: the trainer publishes its step, and
    synthetic events made of that table's own names (a microsecond each;
    the CPU's profiler has no device plane) beside one call of each kernel
    and a walk are read by the cell's own metric files."""
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
        for scope in ("kimi_delta_attention", "kda_state_walk",
                      "mla_attention"):
            want[scope] = sum(scope in scope_ms_per_step._components(
                table[n].path) for n in names)
        ops = [[f"%{n} = f32[2]{{0}} fusion(%p)", (10 + i) * US, US]
               for i, n in enumerate(names)]
        end = (10 + len(names)) * US
        for name in ("kda_chunk_fwd.2", "while.7", "kda_chunk_out.3",
                     "kda_chunk_out_bwd.4", "kda_chunk_bwd.5",
                     "_flash_forward.6"):
            text, dur, _ = OPS[name]
            ops.append([text, end, dur * US])
            end += dur * US
        ops.append(["%long_attention_bwd.9 = (bf16[1,32,16384,192]{3,2,1,0},"
                    " bf16[2])" + CUSTOM, end, 60 * US])
        end += 60 * US
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
        parts = toy.run(toy_lm_kimi.cell(), tmp_path, traced=True)
    finally:
        config.unset("observability.annotate")
        scopes.clear()
    out = capsys.readouterr().out
    assert parts["correct"] is True, out
    m = {k: v["value"] for k, v in parts["metrics"].items()}
    assert NEW <= set(m)
    # four KDA layers of five: most of the mixers' fusions, the walk's
    # products among them
    assert want["kimi_delta_attention"] > want["mla_attention"] > 5
    assert want["kda_state_walk"] >= 4
    assert m["kda.layer_ms"] == pytest.approx(
        want["kimi_delta_attention"] * 1e-3)
    # the scope is none of PARTS' names and lies inside the flax module
    # ``attn``, which PARTS counts to attention: the mixers' time lies in
    # model.attention_ms beside the latent layer's
    assert m["model.attention_ms"] >= m["kda.layer_ms"] \
        + want["mla_attention"] * 1e-3
    assert m["kda.chunk_kernel_ms"] == pytest.approx(0.165)
    assert m["kda.state_walk_ms"] == pytest.approx(0.030)
    assert m["kernel.flash_attention_ms"] == pytest.approx(0.300)
    assert m["kernel.flash_bwd_ms"] == pytest.approx(0.060)
    assert 0 < m["kda.chunk_kernel_roofline"] \
        and 0 < m["kda.state_walk_roofline"] \
        and 0 < m["mla.flash_fwd_roofline"]
    assert m["moe.expert_matmul_ms"] == pytest.approx(0.020)
    assert {"moe.routed_path_ms", "moe.dispatch_combine_ms",
            "moe.load_max_over_mean", "model.attention_ms", "model.ffn_ms",
            "loss.head_ms", "step.forward_ms", "trainer.step_ms",
            "model.mfu", "compile.window_compiles"} <= set(m)
    assert not [k for k in m if k.startswith((
        "linattn.", "ssm.", "mesh.", "shortconv.", "attn.window"))]
    assert "kernel.flash_fwd_roofline" not in m


def test_the_cell_is_what_the_issue_named():
    cell = spec.load_cell(toy_lm_kimi.CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "kimi-linear-48b-a3b", "train-lm-16k", 1)
    assert cell.config["runner"] == "train_lm_family"
    assert cell.config["reference"] == "kimi_linear"
    assert {k: cell.traffic[k] for k in (
        "batch_per_chip", "tokens_per_row", "resident_batches",
        "segment_steps", "check_steps", "reference_block_rows",
        "trace_seconds")} == {
        "batch_per_chip": 1, "tokens_per_row": 16384, "resident_batches": 4,
        "segment_steps": 4, "check_steps": 3, "reference_block_rows": 1,
        "trace_seconds": 4}
    assert cell.config["program"]["zoo_args"]["gate_grad"] is False
    # every published width
    cfg = cell.config
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["num_experts_per_token"],
            cfg["num_attention_heads"], cfg["q_lora_rank"]) == (
        2304, 9216, 1024, 512, 128, 64, 128, 8, 32, None)
    assert cfg["linear_attn_config"] == {
        "full_attn_layers": [4], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5], "num_heads": 32,
        "short_conv_kernel_size": 4}
    assert cfg["reduced"] == ["num_hidden_layers", "linear_attn_config",
                              "num_experts", "vocab_size"]
    assert set(cfg["reduced_from"]) == set(cfg["reduced"])
    assert cfg["deployment"]["chips_sharing_each_layer"] == 32
    assert (cfg["num_experts"], cfg["num_hidden_layers"],
            cfg["vocab_size"]) == (8, 5, 20480)
    assert {m["name"] for m in cell.end_to_end} == {"items_s_chip",
                                                    "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert names >= NEW | {
        "trainer.step_ms", "trainer.syncs_per_step", "trainer.dispatch_ms",
        "trainer.steps_in_flight", "model.mfu", "model.copy_ms",
        "device.idle_share.train", "device.hbm_peak_gb.train",
        "kernel.flash_attention_ms", "kernel.flash_bwd_ms",
        "model.attention_ms", "model.ffn_ms", "model.other_ms",
        "loss.head_ms", "step.forward_ms", "step.recompute_ms",
        "step.backward_ms", "step.optimizer_ms", "step.unscoped_ms",
        "moe.expert_matmul_ms", "moe.expert_matmul_roofline",
        "moe.load_max_over_mean", "moe.routed_path_ms",
        "moe.dispatch_combine_ms", "trainer.step_hbm_gb"}
    for m in cell.per_layer:
        if m["name"] in NEW:
            assert (m["moves"], m["source"], m["workloads"]) == (
                "items_s_chip", "device_trace", [toy_lm_kimi.CELL])
    calls = kimi_linear.kernel_calls(cfg, 1, 16384, 2048.0)
    assert calls["kda_chunk"] == calls["kda_walk"] == RULE
    assert calls["flash_fwd"] == FLASH
