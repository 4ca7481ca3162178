"""The comparison of ``laguna-xs.2-train-ep8share-8k`` has to fail its
control (the reference one precision down, in the program's place), a step
that drops the window (plain causal attention in the sliding layers), at a
size a test run can hold. The same control at the
cell's own size is ``benchmark/tools/control.py``, on the chip; the dropped
window's is this file run there as a script,

    python3 -m benchmark.tests.test_control_lm_laguna --seeds 1,2

which prints what ``window_dropped`` reads at 2 x 8,192 beside the limits.

Readings behind ``toy_lm_laguna.LIMITS`` (bf16 against float32 at the toy's
size, on the CPU, six seeds, these three among them): sound runs read at
most 7.3e-5 (losses), 0.024 and 0.0050 (norm gaps), 0.0076 to 0.0248 (the
gradient's relative difference), 0.0020 to 0.0078 of the choices flipped at
margins of 0.00005 to 0.0041; the fp8 control reads 0.067 to 0.087 on the
gradient (the one it must fail, and fails on every seed), 0.017 to 0.071
and 0.0088 to 0.016 on the norm gaps (a toy's leaves are too few to
separate them: their limits are the other toys'), 0.020 to 0.039 of the
choices flipped at margins of 0.0042 to 0.0091 (a worst slot's reading,
which precision does not separate from a sound run's), and 2.7e-5 to
4.2e-4 on the losses (precision hardly moves them)."""
import pytest

from benchmark.harness import spec
from benchmark.tests import toy, toy_lm_laguna

SEEDS = [3, 2 ** 31 + 5, 3_000_000_019]


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_the_gradient_and_the_flip_share(seed):
    cell = toy_lm_laguna.cell()
    runner = spec.load_plugin("runners", "train_lm_dense")
    row = runner.control(cell, seed, "fp8")
    lim = cell.config["limits"]
    assert row["compared"]["first_grad_rel_diff"] > lim["grad_rel_diff"]
    assert row["compared"]["routing_flip_share"] > lim["routing_flip_share"]
    assert "loss_mtp_step0_rel_gap" not in row["compared"]


def test_a_step_that_drops_the_window_is_not_correct(tmp_path, monkeypatch):
    """The program with plain causal attention in its sliding layers (the
    reference path under ``window=None``) against the reference the
    configuration states: the same parameter tree, another model."""
    from mmlspark_tpu.parallel import sequence
    real = sequence._reference_attention
    monkeypatch.setattr(sequence, "_reference_attention",
                        lambda q, k, v, causal, window=None: real(
                            q, k, v, causal))
    parts = toy.run(toy_lm_laguna.cell(), tmp_path, seconds=0.5)
    assert parts["correct"] is False


def window_dropped(cell, seed):
    """What the comparison reads with the reference WITHOUT the band
    (plain causal attention in the sliding layers) in the program's
    place, against the reference the configuration states."""
    from benchmark.references import laguna
    from benchmark.runners import train_lm, train_lm_dense
    tokens = train_lm._all_tokens(cell, seed)
    want = train_lm._reference(cell, seed, tokens)
    steps = int(cell.traffic["check_steps"])
    batch = int(cell.traffic["batch_per_chip"]) * cell.chips
    got = laguna.train_reference(
        cell.config, seed, tokens[:steps * batch].reshape(steps, batch, -1),
        steps=steps, optimizer=cell.config["optimizer"], window=False)
    got["choices"] = [r["choice"] for r in got["routing"]]
    for key in ("grad_norms", "delta_norms"):
        got[key] = list(got[key].values())
    return train_lm_dense.compare(got, want)


def test_a_reference_without_the_window_is_another_reference():
    """And the other way round, through the comparison itself: the
    reference with the band dropped in the program's place reads far past
    the gradient's limits (at the cell's own size, on the chip, past five
    kinds of limit of six: the loss does not separate it, PERF.md section
    2)."""
    cell = toy_lm_laguna.cell()
    compared = window_dropped(cell, SEEDS[0])
    lim = cell.config["limits"]
    assert compared["first_grad_rel_diff"] > lim["grad_rel_diff"]
    assert compared["first_grad_norm_worst_leaf_gap"] > lim["grad_norm_gap"]


if __name__ == "__main__":
    import argparse
    import json
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    real = spec.load_cell(toy_lm_laguna.CELL)
    for seed in (int(s) for s in ap.parse_args().seeds.split(",")):
        print(json.dumps({"workload": real.name, "seed": seed,
                          "control": "window_dropped",
                          "compared": window_dropped(real, seed),
                          "limits": real.config["limits"]}), flush=True)
