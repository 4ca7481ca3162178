"""The comparison that decides ``correct`` has to fail its control (the
reference one precision down, in the program's place) and a timed path
that is broken underneath: at a size a test run can hold. The same control
at the cells' own size is ``benchmark/tools/control.py``, on the chip."""
import pytest

from benchmark.harness import spec
from benchmark.tests import toy

SEEDS = [3, 2 ** 31 + 5, 3_000_000_019]


@pytest.mark.parametrize("seed", SEEDS)
def test_train_control_fails_the_gradient_limit(seed):
    cell = toy.train_cell()
    runner = spec.load_plugin("runners", "train")
    row = runner.control(cell, seed, "fp8")
    limit = cell.config["limits"]["grad_rel_diff"]
    assert row["compared"]["first_grad_rel_diff"] > limit


def test_train_step_that_returns_its_state_unchanged_is_not_correct(
        tmp_path, monkeypatch):
    from mmlspark_tpu.parallel.trainer import DistributedTrainer
    real = DistributedTrainer.train_step
    seen = {}

    def stuck(self, state, batch, rng, **kw):
        if "metrics" in seen:               # the state comes back as it was
            return state, seen["metrics"]
        state, seen["metrics"] = real(self, state, batch, rng, **kw)
        return state, seen["metrics"]
    monkeypatch.setattr(DistributedTrainer, "train_step", stuck)
    parts = toy.run(toy.train_cell(), tmp_path, seconds=0.5)
    assert parts["correct"] is False


def test_part_of_the_batch_left_out_is_not_correct(tmp_path, monkeypatch):
    import jax.numpy as jnp
    import optax
    real = optax.softmax_cross_entropy_with_integer_labels

    def half(logits, labels, **kw):
        n = logits.shape[0] // 2            # the second half never counts
        return jnp.tile(real(logits[:n], labels[:n], **kw), 2)
    monkeypatch.setattr(optax, "softmax_cross_entropy_with_integer_labels",
                        half)
    parts = toy.run(toy.train_cell(), tmp_path, seconds=0.5)
    assert parts["correct"] is False
