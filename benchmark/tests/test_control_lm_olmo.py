"""The comparison of ``olmo-hybrid-7b-train-8k`` has to fail its control
(the reference one precision down, in the program's place) and a delta rule
that is broken underneath, at a size a test run can hold. The same control
at the cell's own size is ``benchmark/tools/control.py``, on the chip.

Readings behind ``toy_lm_olmo.LIMITS`` (bf16 against float32 at the toy's
size, on the CPU, six seeds, these three among them): sound runs read at
most 6.1e-5 (losses), 0.0047 and 0.0172 (norm gaps) and 0.0121 (the
gradient's relative difference); the fp8 control reads 0.49 on the
gradient and 1.0 on both norm gaps (the embedding's rows, normal(0, 0.02)
with no multiplier, lie in fp8's subnormals: the first mixer's input is
crushed) and 2e-4 to 2.8e-3 on the losses."""
import pytest

from benchmark.harness import spec
from benchmark.tests import toy, toy_lm_olmo

SEEDS = [3, 2 ** 31 + 5, 3_000_000_019]


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_the_gradient_limit(seed):
    cell = toy_lm_olmo.cell()
    runner = spec.load_plugin("runners", "train_lm_dense")
    row = runner.control(cell, seed, "fp8")
    lim = cell.config["limits"]
    assert row["compared"]["first_grad_rel_diff"] > lim["grad_rel_diff"]
    assert "loss_mtp_step0_rel_gap" not in row["compared"]
    assert "routing_flip_share" not in row["compared"]


def test_beta_without_its_factor_of_two_is_not_correct(tmp_path,
                                                        monkeypatch):
    """The delta rule with ``beta`` in (0, 1) (``linear_allow_neg_eigval``
    left out of the program) trains, and is another model."""
    from mmlspark_tpu.ops import linear_attention as la
    real = la.gated_delta_rule
    monkeypatch.setattr(
        la, "gated_delta_rule",
        lambda q, k, v, g, beta, **kw: real(q, k, v, g, 0.5 * beta, **kw))
    parts = toy.run(toy_lm_olmo.cell(), tmp_path, seconds=0.5)
    assert parts["correct"] is False


def test_norms_on_the_inputs_are_not_correct(tmp_path, monkeypatch):
    """The same parts wired as a pre-norm block have the same parameter
    tree and the same cost, and are another model."""
    from mmlspark_tpu.models.zoo import decoder
    real = decoder._half
    monkeypatch.setattr(decoder, "_half",
                        lambda norm, part, x, norm_output: real(
                            norm, part, x, False))
    parts = toy.run(toy_lm_olmo.cell(), tmp_path, seconds=0.5)
    assert parts["correct"] is False
