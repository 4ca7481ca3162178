"""The comparison of ``kimi-linear-48b-a3b-train-ep32share-16k`` has to fail
its control (the reference one precision down, in the program's place) and a
step whose decay is a head's one number where the model's is a channel's,
at a size a test run can hold. The same control at the cell's own size is
``benchmark/tools/control.py``, on the chip.

Readings behind ``toy_lm_kimi.LIMITS`` (bf16 against float32 at the toy's
size, on the CPU, six seeds, these three among them): sound runs read at
most 9.4e-5 (losses), 0.0035 to 0.0165 and 0.0039 to 0.0082 (norm gaps),
0.0081 to 0.0219 (the gradient's relative difference), 0 to 0.0039 of the
choices flipped at margins up to 0.00034; the fp8 control reads 0.2465 to
0.2636 on the gradient (the one it must fail, and fails on every seed),
0.127 to 0.212 and 0.035 to 0.076 on the norm gaps, 0.082 to 0.100 of the
choices flipped at margins of 0.0123 to 0.0189, and 7.4e-5 to 9.9e-4 on the
losses (precision hardly moves them: the other toys' limit)."""
import pytest

from benchmark.harness import spec
from benchmark.tests import toy, toy_lm_kimi

SEEDS = [3, 2 ** 31 + 5, 3_000_000_019]


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_the_gradient_and_the_flip_share(seed):
    cell = toy_lm_kimi.cell()
    runner = spec.load_plugin("runners", "train_lm_family")
    row = runner.control(cell, seed, "fp8")
    lim = cell.config["limits"]
    assert row["compared"]["first_grad_rel_diff"] > lim["grad_rel_diff"]
    assert row["compared"]["routing_flip_share"] > lim["routing_flip_share"]
    assert row["compared"]["routing_flip_margin"] > lim["routing_flip_margin"]
    assert "loss_mtp_step0_rel_gap" not in row["compared"]


def test_a_decay_that_is_a_heads_one_number_is_not_correct(tmp_path,
                                                          monkeypatch):
    """The program with every channel of a head decaying by the head's
    MEAN (Gated DeltaNet's scalar where KDA has a vector) trains, and is
    another model: the same parameter tree, another rule."""
    import jax.numpy as jnp
    from mmlspark_tpu.ops import linear_attention as la
    real = la._chunked_kda

    def one_number(q, k, v, g, beta, chunk, dtype):
        mean = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
        return real(q, k, v, mean, beta, chunk, dtype)
    monkeypatch.setattr(la, "_chunked_kda", one_number)
    parts = toy.run(toy_lm_kimi.cell(), tmp_path, seconds=0.5)
    assert parts["correct"] is False
