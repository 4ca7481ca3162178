"""The bench methodology itself (bench.py helpers + output contract).

The driver consumes exactly one JSON line from ``python bench.py`` and the
judge reads the ratios; the helpers that produce them (within-round medians,
short-region extrapolation, two-length slope cancellation, round-robin
scheduling, budget trimming) are judged infrastructure and get the same unit
coverage as product code. All tests run the helpers on synthetic timings —
no accelerator, no timed regions.
"""
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import bench  # noqa: E402


@pytest.fixture(autouse=True)
def _cache_placed_from_outside(monkeypatch, tmp_path):
    """bench.main() turns the compile cache on; left alone it would bind
    this test process to <checkout>/.jax_cache. Place it from outside the
    way the driver can — main() then updates no jax cache setting and the
    checkout stays clean (asserted below)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def test_med_ratio_is_within_round_median():
    rounds = [[2.0, 4.0], [1.0, 3.0], [2.0, 2.0]]
    # ratios num/den per round: 2.0, 3.0, 1.0 -> median 2.0
    assert bench._med_ratio(rounds, 1, 0) == 2.0
    assert bench._best(rounds, 0) == 1.0
    assert bench._best(rounds, 1) == 2.0


def test_scaled_ratio_extrapolates_by_iteration_count():
    rounds = [[1.0, 0.5]]  # framework 8 iters in 1.0s, baseline 2 in 0.5s
    # per-iter baseline cost scales to 8 iters: 0.5 * (8/2) / 1.0 = 2.0
    assert bench._scaled_ratio(rounds, 1, 0, 8, 2) == 2.0


def test_med_slope_ratio_cancels_fixed_sync_cost():
    # baseline region: fixed 1.0s sync + 0.1s/iter, timed at 5 and 1 iters;
    # framework: 0.05s/iter over 10 iters.
    rounds = [[0.5, 1.5, 1.1]]
    got = bench._med_slope_ratio(rounds, 1, 2, 5, 1, 0, 10)
    # slope = (1.5-1.1)/(5-1) = 0.1s/iter; fw = 0.5/10 = 0.05 -> ratio 2.0
    assert got == 2.0
    # plain scaling would have overstated the baseline: (1.5/5)/0.05 = 6.0
    # degraded-data fallback (all slopes non-positive) = exactly that scaling
    rounds_noise = [[0.5, 1.0, 1.2]]
    assert bench._med_slope_ratio(rounds_noise, 1, 2, 5, 1, 0, 10) == \
        pytest.approx((1.0 / 5) / 0.05)


def test_robin_rounds_interleaves_and_varies_order():
    calls = []

    def make(i):
        def run():
            calls.append(i)
        return run

    rounds = bench._robin_rounds(make(0), make(1), make(2), trials=4,
                                 deadline_s=1e9)
    assert len(rounds) == 4 and all(len(t) == 3 for t in rounds)
    assert all(t[i] >= 0 for t in rounds for i in range(3))
    per_round = [tuple(calls[r * 3:(r + 1) * 3]) for r in range(4)]
    # every round runs each region exactly once (round-robin, no repeats)
    assert all(sorted(o) == [0, 1, 2] for o in per_round)
    # rotation + odd-round reversal: the order must actually vary
    assert len(set(per_round)) >= 2
    # round 0 is the identity rotation
    assert per_round[0] == (0, 1, 2)


def test_robin_rounds_respects_deadline_with_min_two_rounds():
    def slow():
        time.sleep(0.05)

    rounds = bench._robin_rounds(slow, slow, trials=50, deadline_s=0.01)
    assert 2 <= len(rounds) < 50


def test_mfu_is_null_on_cpu_but_tflops_reported():
    # a CPU run is never a device measurement: utilization must be None
    # rather than a fabricated number, while achieved TFLOP/s (a
    # backend-independent arithmetic fact) is still reported
    tflops, mfu = bench._mfu(1000.0, 1e9, 32)
    assert tflops == pytest.approx(1000.0 / 32 * 1e9 / 1e12, abs=1e-4)
    assert mfu is None


def test_mfu_divides_by_the_device_kinds_table_row(monkeypatch):
    import jax
    from mmlspark_tpu.observability import peaks

    class _Dev:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    tflops, mfu = bench._mfu(1000.0, 1e12, 10)     # 100 TFLOP/s achieved
    assert tflops == pytest.approx(100.0)
    assert mfu == pytest.approx(
        100.0 / peaks.DEVICE_PEAKS["TPU v5 lite"].bf16_tflops, abs=1e-6)
    # an accelerator nobody sourced a peak for is an error, not a v5e
    _Dev.device_kind = "TPU v99"
    with pytest.raises(KeyError, match="TPU v99"):
        bench._mfu(1000.0, 1e12, 10)


def test_step_flops_raises_instead_of_returning_zero():
    class _NoCost:
        def lower(self, *a):
            raise RuntimeError("backend offers no cost model")

    with pytest.raises(RuntimeError):
        bench._step_flops(_NoCost())


def _fake_config(value=123.0):
    def cfg():
        return {"value": value, "unit": "images/sec/chip",
                "vs_baseline": 1.5, "vs_resident_baseline": 1.01,
                "step_ms": 1.0, "mfu": None}
    return cfg


def test_main_prints_exactly_one_json_line(monkeypatch, capsys):
    import jax
    monkeypatch.setattr(bench, "CONFIGS", {"train": _fake_config()})
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    cache_before = jax.config.jax_compilation_cache_dir
    assert bench.main() == 0          # 2 = regression-gate red, 3 = killed
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1, out
    line = json.loads(out[0])
    # the line says which device it was taken on — here, not a chip
    assert (line["platform"], line["device_kind"],
            line["device_count"]) == ("cpu", "cpu", 8)
    # the cache was placed from outside: nothing set another in code and
    # nothing appeared in the checkout
    assert jax.config.jax_compilation_cache_dir == cache_before
    assert not (Path(bench.__file__).parent / ".jax_cache").exists()
    assert line["metric"] == \
        "cifar10_resnet20_train_images_per_sec_per_chip"
    assert line["value"] == 123.0 and line["vs_baseline"] == 1.5
    assert line["configs"]["train"]["value"] == 123.0
    assert line["vs_resident_baseline"] == 1.01


def test_main_budget_trims_later_configs_but_still_prints(monkeypatch,
                                                          capsys):
    def slow_cfg():
        time.sleep(0.2)
        return _fake_config(7.0)()

    monkeypatch.setattr(bench, "CONFIGS",
                        {"train": slow_cfg, "extra": _fake_config()})
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.setenv("MMLSPARK_BENCH_BUDGET_S", "0.01")
    assert bench.main() == 0
    line = json.loads(capsys.readouterr().out.strip())
    # first config always runs; the over-budget one is skipped, visibly
    assert line["configs"]["train"]["value"] == 7.0
    assert line["configs"]["extra"]["skipped"] is True
    assert line["value"] == 7.0


def test_main_rejects_unknown_config(monkeypatch):
    monkeypatch.setattr(sys, "argv", ["bench.py", "--configs", "nope"])
    with pytest.raises(SystemExit):
        bench.main()


def test_set_state_drops_out_spec_memo():
    """set_model/_set_state must release the eval_shape memo, which keys
    on (and therefore pins) the previous compiled closure and the whole
    param tree it captured."""
    from mmlspark_tpu.models.jax_model import JaxModel
    m = JaxModel(inputCol="x", outputCol="o")
    m._out_spec_cache = (("k",), object())
    m._set_state({"params": {}})
    assert m._out_spec_cache is None


def test_fleet_reshard_lane_is_registered():
    """The elastic-mesh lane must stay wired: registered under CONFIGS
    (so ``--configs fleet_reshard`` resolves), carrying the open-loop
    delivery-ratio unit the gate's goodput checks key on, and listed in
    XL_CONFIGS so the emulated 8-device mesh is forced BEFORE the first
    jax import — without it the 4x2 serve placement and the 2x2x2 train
    placement both fail mesh construction on a 1-device host."""
    assert "fleet_reshard" in bench.CONFIGS
    assert bench.CONFIG_UNITS["fleet_reshard"] == "delivery ratio"
    assert "fleet_reshard" in bench.XL_CONFIGS


def test_benchgate_accepts_fleet_reshard_baseline(tmp_path):
    """BENCH_r12.json's wrapper shape must round-trip through the gate:
    load_baseline unwraps ``parsed`` and gate() goes green when fresh
    equals baseline, red when goodput drops through a live reshard."""
    from mmlspark_tpu.observability import benchgate
    lane = {"value": 1.0, "unit": "delivery ratio", "vs_baseline": 1.0,
            "goodput": 1.0, "arrival_p99_ms": 140.0, "deadline_ms": 5000.0,
            "steady_compiles": 0, "train_loss_delta": 0.0}
    line = {"metric": "bench_fleet_reshard", "value": 1.0,
            "unit": "delivery ratio", "vs_baseline": 1.0,
            "configs": {"fleet_reshard": dict(lane)}}
    p = tmp_path / "BENCH_r12.json"
    p.write_text(json.dumps({"cmd": "python bench.py --configs "
                             "fleet_reshard", "n": 10, "parsed": line,
                             "rc": 0, "tail": ""}))
    assert benchgate.load_baseline(str(p))["configs"]["fleet_reshard"][
        "goodput"] == 1.0
    assert benchgate.gate(dict(line), str(p))["green"] is True
    degraded = json.loads(json.dumps(line))
    degraded["configs"]["fleet_reshard"]["goodput"] = 0.5
    degraded["configs"]["fleet_reshard"]["value"] = 0.5
    degraded["value"] = 0.5
    assert benchgate.gate(degraded, str(p))["green"] is False
