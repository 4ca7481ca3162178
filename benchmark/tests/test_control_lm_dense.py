"""The ``train_lm_dense`` comparison has to fail its control (the
reference one precision down, in the program's place) and a state-space
layer that is broken underneath, at a size a test run can hold. The same
control at the cell's own size is ``benchmark/tools/control.py``, on the
chip."""
import pytest

from benchmark.harness import spec
from benchmark.tests import toy, toy_lm_dense

SEEDS = [3, 2 ** 31 + 5, 3_000_000_019]


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_the_gradient_limit(seed):
    cell = toy_lm_dense.cell()
    runner = spec.load_plugin("runners", "train_lm_dense")
    row = runner.control(cell, seed, "fp8")
    lim = cell.config["limits"]
    assert row["compared"]["first_grad_rel_diff"] > lim["grad_rel_diff"]
    assert "loss_mtp_step0_rel_gap" not in row["compared"]
    assert "routing_flip_share" not in row["compared"]


def test_a_state_that_forgets_nothing_is_not_correct(tmp_path, monkeypatch):
    """Mamba-2's rule with its decay left out (``A`` = 0: a running sum)
    trains, and is another model."""
    import jax.numpy as jnp
    from mmlspark_tpu.ops import linear_attention as la
    real = la._ssd_chunked

    def undecayed(x, dt, A, Bm, Cm, chunk, dtype):
        return real(x, dt, jnp.zeros_like(A), Bm, Cm, chunk, dtype)
    monkeypatch.setattr(la, "_ssd_chunked", undecayed)
    parts = toy.run(toy_lm_dense.cell(), tmp_path, seconds=0.5)
    assert parts["correct"] is False


def test_a_head_that_is_not_tied_is_not_correct(tmp_path, monkeypatch):
    """The table's gradient with its second source cut (the head reads a
    copy that carries no gradient): the loss is the same at step 0, the
    first gradient is not."""
    import jax
    from benchmark.references import granite_hybrid as ref
    monkeypatch.setattr(
        ref, "head_kernel",
        lambda params: jax.lax.stop_gradient(
            params["params"]["token_embedding"]["embedding"].T))
    parts = toy.run(toy_lm_dense.cell(), tmp_path, seconds=0.5)
    assert parts["correct"] is False
