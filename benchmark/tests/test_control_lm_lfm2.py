"""The comparison of ``lfm2-24b-a2b-train-ep8share-8k`` has to fail its
control (the reference one precision down, in the program's place), a gate
that trains where the configuration freezes it, and a short convolution
that is broken underneath, at a size a test run can hold. The same control
at the cell's own size is ``benchmark/tools/control.py``, on the chip.

Readings behind ``toy_lm_lfm2.LIMITS`` (bf16 against float32 at the toy's
size, on the CPU, six seeds, these three among them): sound runs read at
most 5.1e-5 (losses), 0.0125 and 0.0042 (norm gaps), 0.0102 (the
gradient's relative difference), 0.00195 of the choices flipped at a margin
of at most 0.00029; the fp8 control reads 0.074 to 0.080 on the gradient,
0.27 to 0.29 and 0.70 to 0.75 on the norm gaps, 0.029 to 0.049 of the
choices flipped at margins of 0.0059 to 0.0075, and 7e-6 to 2.1e-4 on the
losses (precision hardly moves them: their limit is the other toys')."""
import pytest

from benchmark.harness import spec
from benchmark.tests import toy, toy_lm_lfm2

SEEDS = [3, 2 ** 31 + 5, 3_000_000_019]


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_the_gradient_and_the_flip_limits(seed):
    cell = toy_lm_lfm2.cell()
    runner = spec.load_plugin("runners", "train_lm_dense")
    row = runner.control(cell, seed, "fp8")
    lim = cell.config["limits"]
    assert row["compared"]["first_grad_rel_diff"] > lim["grad_rel_diff"]
    assert row["compared"]["first_grad_norm_worst_leaf_gap"] \
        > lim["grad_norm_gap"]
    assert row["compared"]["routing_flip_share"] > lim["routing_flip_share"]
    assert row["compared"]["routing_flip_margin"] > lim["routing_flip_margin"]
    assert "loss_mtp_step0_rel_gap" not in row["compared"]


def test_a_gate_that_trains_is_not_correct(tmp_path, monkeypatch):
    """The program with ``gate_grad=True`` against the reference the
    configuration states (the gate takes no gradient): the routers' kernels
    and, through the tokens, every layer below get another gradient."""
    from benchmark.references import lfm2_moe
    real = lfm2_moe.zoo_args
    monkeypatch.setattr(lfm2_moe, "zoo_args", lambda cfg, length: dict(
        real(cfg, length), gate_grad=True))
    parts = toy.run(toy_lm_lfm2.cell(), tmp_path, seconds=0.5)
    assert parts["correct"] is False


def test_a_reference_that_trains_its_gate_is_another_reference():
    """And the other way round, through the comparison itself: the
    reference with a trained gate in the program's place."""
    from benchmark.runners import train_lm, train_lm_dense
    cell, trained = toy_lm_lfm2.cell(), toy_lm_lfm2.cell()
    trained.config["program"]["zoo_args"]["gate_grad"] = True
    tokens = train_lm._all_tokens(cell, SEEDS[0])
    got = train_lm._reference(trained, SEEDS[0], tokens)
    got["choices"] = [r["choice"] for r in got["routing"]]
    compared = train_lm_dense.compare(
        got, train_lm._reference(cell, SEEDS[0], tokens))
    lim = cell.config["limits"]
    assert compared["routing_flip_share"] == 0      # the same forward pass
    assert compared["loss_step0_rel_gap"] < 1e-6
    assert compared["first_grad_rel_diff"] > lim["grad_rel_diff"] \
        or compared["first_grad_norm_worst_leaf_gap"] > lim["grad_norm_gap"]


def test_a_convolution_with_an_activation_is_not_correct(tmp_path,
                                                         monkeypatch):
    """The taps followed by ``silu``, as the two recurrent families'
    convolutions are: the same parameter tree and cost, another model."""
    import jax
    from mmlspark_tpu.ops import linear_attention as la
    real = la.causal_conv1d
    monkeypatch.setattr(la, "causal_conv1d", lambda x, kernel, bias=None:
                        jax.nn.silu(real(x, kernel, bias)))
    parts = toy.run(toy_lm_lfm2.cell(), tmp_path, seconds=0.5)
    assert parts["correct"] is False
