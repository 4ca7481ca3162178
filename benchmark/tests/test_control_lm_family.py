"""The ``train_lm_family`` comparison has to fail its control (the
reference one precision down, in the program's place) and a recurrence
that is broken underneath, at a size a test run can hold. The same control
at the cell's own size is ``benchmark/tools/control.py``, on the chip."""
import pytest

from benchmark.harness import spec
from benchmark.tests import toy, toy_lm_family

SEEDS = [3, 2 ** 31 + 5, 3_000_000_019]


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_the_gradient_and_the_flip_limits(seed):
    cell = toy_lm_family.cell()
    runner = spec.load_plugin("runners", "train_lm_family")
    row = runner.control(cell, seed, "fp8")
    lim = cell.config["limits"]
    assert row["compared"]["first_grad_rel_diff"] > lim["grad_rel_diff"]
    assert row["compared"]["routing_flip_share"] > lim["routing_flip_share"]
    assert row["compared"]["routing_flip_margin"] > lim["routing_flip_margin"]
    assert "loss_mtp_step0_rel_gap" not in row["compared"]


def test_a_state_that_forgets_nothing_is_not_correct(tmp_path, monkeypatch):
    """The gated delta rule with its decay left out (``g`` = 0: plain
    DeltaNet) trains, and is another model."""
    import jax.numpy as jnp
    from mmlspark_tpu.ops import linear_attention as la
    real = la._chunked

    def undecayed(q, k, v, g, beta, chunk, dtype):
        return real(q, k, v, jnp.zeros_like(g), beta, chunk, dtype)
    monkeypatch.setattr(la, "_chunked", undecayed)
    parts = toy.run(toy_lm_family.cell(), tmp_path, seconds=0.5)
    assert parts["correct"] is False
