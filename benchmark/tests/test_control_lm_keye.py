"""The comparison of ``keye-vl2-30b-a3b-train-ep8share-16k`` has to fail its
two controls (the reference one precision down, and the reference with
every causal key chosen, each in the program's place) and a step that
returns its state unchanged, at a size a test run can hold. The same
controls at the cell's own size are ``benchmark/tools/control.py``
(``--precision fp8`` / ``causal``), on the chip.

Readings behind ``toy_lm_keye.LIMITS`` (bf16 against float32 at the toy's
size, on the CPU, six seeds, these three among them) are in
``toy_lm_keye.py``."""
import pytest

from benchmark.harness import spec
from benchmark.tests import toy, toy_lm_keye
from benchmark.tests.test_rehearsal_lm_dense import _checks

SEEDS = [3, 2 ** 31 + 5, 3_000_000_019]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_fp8_control_fails_the_selection_and_the_gradient(seed):
    cell = toy_lm_keye.cell()
    runner = spec.load_plugin("runners", "train_lm_sparse")
    row = runner.control(cell, seed, "fp8")
    lim = cell.config["limits"]
    assert row["compared"]["selection_flip_share"] \
        > lim["selection_flip_share"]
    assert row["compared"]["selection_flip_margin"] \
        > lim["selection_flip_margin"]
    assert row["compared"]["first_grad_rel_diff"] > lim["grad_rel_diff"]
    assert row["compared"]["first_grad_rel_diff_indexer"] \
        > lim["grad_rel_diff_indexer"]
    assert row["compared"]["selection_pairs_gap"] == 0
    assert row["compared"]["routing_flip_share"] > lim["routing_flip_share"]
    assert "loss_mtp_step0_rel_gap" not in row["compared"]
    assert "loss_indexer_step0_rel_gap" in row["compared"]


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_the_causal_control_fails_the_selection(seed):
    """The float32 reference with the selection left out: every causal key
    chosen. Three quarters of the toy's queries keep more keys than they
    may: the flips count them, and so does the count of kept pairs, held
    to the ``min(k, t + 1)`` a query exactly. The reference follows the
    handed selection in step 0, so that step's loss and gradient are its
    own computation again and read 0;
    from step 1 on the indexer's loss is another number."""
    cell = toy_lm_keye.cell()
    runner = spec.load_plugin("runners", "train_lm_sparse")
    row = runner.control(cell, seed, "causal")
    lim = cell.config["limits"]
    assert row["compared"]["selection_flip_share"] > 1.0 \
        > lim["selection_flip_share"]
    assert row["compared"]["selection_pairs_gap"] > 1.0
    assert row["compared"]["selection_flip_margin"] \
        > lim["selection_flip_margin"]
    assert row["compared"]["first_grad_rel_diff"] < 1e-6
    assert row["compared"]["loss_indexer_step1_rel_gap"] \
        > 10 * lim["loss_rel_gap"]


def test_a_program_that_keeps_half_the_chosen_keys_is_not_correct(
        tmp_path, monkeypatch, capsys):
    """A planted fault: the choice keeps the top 4 where it must keep 8, a
    SUBSET of the right keys. No pair it keeps is one the reference did
    not choose; the flips count the pairs it dropped as well, the count is
    held exactly, and from step 1 on the reference chooses for itself."""
    from mmlspark_tpu.ops import sparse_attention
    whole = sparse_attention._topk_mask_xla
    monkeypatch.setattr(sparse_attention, "_topk_mask_xla",
                        lambda scores, k: whole(scores, k // 2))
    cell = toy_lm_keye.cell()
    parts = toy.run(cell, tmp_path, seconds=0.5)
    assert parts["correct"] is False
    checks = _checks(capsys.readouterr().out)
    lim = cell.config["limits"]
    assert checks["selection_pairs_gap"]["value"] > 0.2
    assert checks["selection_flip_share"]["value"] \
        > 5 * lim["selection_flip_share"]
    for s in (1, 2):
        assert checks[f"loss_indexer_step{s}_rel_gap"]["value"] \
            > lim["loss_rel_gap"]


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        tmp_path, monkeypatch):
    import optax
    monkeypatch.setattr(optax, "apply_updates", lambda params, updates: params)
    parts = toy.run(toy_lm_keye.cell(), tmp_path, seconds=0.5)
    assert parts["correct"] is False
