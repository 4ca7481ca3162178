"""The ``train_lm`` comparison has to fail its control (the reference one
precision down, in the program's place) and a timed path that is broken
underneath, at a size a test run can hold. The same control at the cell's
own size is ``benchmark/tools/control.py``, on the chip."""
import pytest

from benchmark.harness import spec
from benchmark.tests import toy, toy_lm

SEEDS = [3, 2 ** 31 + 5, 3_000_000_019]


@pytest.mark.parametrize("seed", SEEDS)
def test_train_lm_control_fails_the_gradient_and_the_flip_limits(seed):
    cell = toy_lm.train_lm_cell()
    runner = spec.load_plugin("runners", "train_lm")
    row = runner.control(cell, seed, "fp8")
    lim = cell.config["limits"]
    assert row["compared"]["first_grad_rel_diff"] > lim["grad_rel_diff"]
    assert row["compared"]["routing_flip_share"] > lim["routing_flip_share"]
    assert row["compared"]["routing_flip_margin"] > lim["routing_flip_margin"]


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
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
    parts = toy.run(toy_lm.train_lm_cell(), tmp_path, seconds=0.5)
    assert parts["correct"] is False


def test_a_layer_that_drops_the_slots_past_a_capacity_is_not_correct(
        tmp_path, monkeypatch):
    """The expert layer with a capacity put back: the slots past the first
    three of each held expert are left out of the grouped products."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models.zoo import moe
    real = jax.lax.ragged_dot

    def capped(x, w, sizes, **kw):
        return real(x, w, jnp.minimum(sizes, 3), **kw)
    monkeypatch.setattr(moe.jax.lax, "ragged_dot", capped)
    parts = toy.run(toy_lm.train_lm_cell(), tmp_path, seconds=0.5)
    assert parts["correct"] is False
