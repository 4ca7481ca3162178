"""The comparison of ``sdar-30b-a3b-train-ep8share-4k`` has to fail its two
controls, at a size a test run can hold: the reference one precision down
in the program's place (fp8), and the float32 reference under a PLAIN
CAUSAL mask over the ``2 L`` positions in the program's place (the mask a
system without the block-diffusion one would run). The same controls at the
cell's own size are ``benchmark/tools/control.py``, on the chip
(``--precision fp8`` and ``--precision causal``).

Readings behind ``toy_lm_sdar.LIMITS`` (bf16 against float32 at the toy's
size, on the CPU, six seeds, these three among them, the head norms' scales
from 2.25 as the cell's; read at a learning rate of 1e-4, the cell's 1e-5
moves the later steps' numbers alone): sound runs read at
most 8.4e-4 (losses), 0.17 and 0.012 (norm gaps), 0.079 to 0.235 (the
gradient's relative difference: 0.010 with scales from 1 and 0.025-0.036
from 1.5, a sharper softmax reads wider and a toy's heads of 16 wider
still), 0.008 to 0.018 of the choices flipped at margins of 0.0005 to
0.0022; the fp8 control reads 0.91 to 1.23 on the gradient (the one it must
fail, and fails on every seed), 0.105 to 0.154 of the choices flipped at
margins of 0.023 to 0.029, 0.21 to 0.41 (not apart from a sound run's) and
0.037 to 0.048 on the norm gaps and 1.7e-4 to 5.4e-3 on the losses; the
causal-mask control reads 1.06 to 1.39 on the gradient, 0.43 to 0.50 of the
choices flipped, and on the losses 0.0041 to 0.0129 at the worst of the
three steps (0.0010 to 0.0077 on step 0 alone: with seeded weights every
loss sits near ln 127, so even another model's loss differs from it by
parts in a thousand)."""
import pytest

from benchmark.harness import spec
from benchmark.tests import toy, toy_lm_sdar

SEEDS = [3, 2 ** 31 + 5, 3_000_000_019]


@pytest.mark.parametrize("seed", SEEDS)
def test_fp8_control_fails_the_gradient(seed):
    cell = toy_lm_sdar.cell()
    runner = spec.load_plugin("runners", "train_lm_diffusion")
    row = runner.control(cell, seed, "fp8")
    lim = cell.config["limits"]
    assert row["compared"]["first_grad_rel_diff"] > lim["grad_rel_diff"]
    assert "loss_mtp_step0_rel_gap" not in row["compared"]


@pytest.mark.parametrize("seed", SEEDS)
def test_causal_mask_control_fails_the_loss(seed):
    cell = toy_lm_sdar.cell()
    runner = spec.load_plugin("runners", "train_lm_diffusion")
    row = runner.control(cell, seed, "causal")
    lim = cell.config["limits"]
    assert max(row["compared"][f"loss_step{s}_rel_gap"]
               for s in range(3)) > lim["loss_rel_gap"]
    assert row["compared"]["first_grad_rel_diff"] > lim["grad_rel_diff"]


def test_a_step_under_a_plain_causal_mask_is_not_correct(tmp_path,
                                                         monkeypatch):
    """The program itself with the block-diffusion mask dropped (the
    reference path under ``block_diffusion=None``) against the reference
    the configuration states: the same parameter tree, another model."""
    from mmlspark_tpu.parallel import sequence
    real = sequence._reference_attention
    monkeypatch.setattr(
        sequence, "_reference_attention",
        lambda q, k, v, causal, window=None, block_diffusion=None: real(
            q, k, v, causal))
    parts = toy.run(toy_lm_sdar.cell(), tmp_path, seconds=0.5)
    assert parts["correct"] is False
