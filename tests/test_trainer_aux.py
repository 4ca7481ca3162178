"""A ``DistributedTrainer`` loss that returns ``(loss, aux)``: the scalars
it reports beside itself ride the device-resident ring, with no host sync,
and come out as gauges at a flush; their keys are found in the first trace
of the step. And the tracing of the ``glm4_moe_lite`` step that uses
it: named scopes in the step program, counters per trace, gauges per flush
(the style of ``tests/test_hot_spans.py``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from mmlspark_tpu.models.zoo import build_model
from mmlspark_tpu.observability import metrics as obsmetrics
from mmlspark_tpu.observability import syncs as obssyncs
from mmlspark_tpu.parallel.mesh import mesh_from_config
from mmlspark_tpu.parallel.trainer import DistributedTrainer
from mmlspark_tpu.train.lm_loss import next_token_loss

ROWS = 8


def _batches(n):
    rng = np.random.default_rng(0)
    return [{"x": rng.normal(size=(ROWS, 3)).astype(np.float32),
             "y": np.ones((ROWS,), np.float32)} for _ in range(n)]


def _plain(params, batch, rng):
    return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)


def _with_aux(params, batch, rng):
    err = batch["x"] @ params["w"] - batch["y"]
    return jnp.mean(err ** 2), {"err.max": jnp.max(jnp.abs(err)),
                                "w.norm": jnp.linalg.norm(params["w"])}


def _trainer(loss_fn, devices=None, **kw):
    trainer = DistributedTrainer(
        loss_fn, optax.sgd(0.1),
        mesh=mesh_from_config(devices or jax.devices()[:1]), **kw)
    state = trainer.init(lambda: {"w": jnp.zeros((3,), jnp.float32)})
    return trainer, state


@pytest.mark.parametrize("devices", [1, 4])
def test_aux_scalars_ride_the_ring_without_a_sync(devices):
    trainer, state = _trainer(_with_aux, jax.devices()[:devices])
    before = obssyncs.total()
    seen = []
    for b in _batches(5):
        state, m = trainer.train_step(state, trainer.put_batch(b),
                                      jax.random.PRNGKey(0))
        assert set(m) == {"loss", "err.max", "w.norm"}
        seen.append(m)
    assert obssyncs.total() == before           # 0 syncs a step
    ring = trainer.flush_metrics()
    assert obssyncs.total() == before + 1       # the flush is the one
    assert set(ring) == {"loss", "step", "err.max", "w.norm"}
    assert int(ring["step"]) == 5
    for name in ("loss", "err.max", "w.norm"):
        assert ring[name].shape == (trainer.flush_steps(),)
        np.testing.assert_array_equal(
            ring[name][:5], [np.float32(m[name]) for m in seen])
    # the newest value of each aux scalar, as a gauge of its name
    for name in ("err.max", "w.norm"):
        assert obsmetrics.gauge(name).value == float(seen[-1][name])


def test_without_aux_the_ring_and_the_metrics_are_the_loss_alone():
    trainer, state = _trainer(_plain)
    state, m = trainer.train_step(state, trainer.put_batch(_batches(1)[0]),
                                  jax.random.PRNGKey(0))
    assert set(m) == {"loss"}
    assert set(trainer.flush_metrics()) == {"loss", "step"}


def test_aux_and_plain_steps_train_alike():
    a, sa = _trainer(_with_aux)
    p, sp = _trainer(_plain)
    for b in _batches(3):
        sa, ma = a.train_step(sa, a.put_batch(b), jax.random.PRNGKey(0))
        sp, mp = p.train_step(sp, p.put_batch(b), jax.random.PRNGKey(0))
        np.testing.assert_array_equal(ma["loss"], mp["loss"])
    np.testing.assert_array_equal(sa["params"]["w"], sp["params"]["w"])
    np.testing.assert_allclose(
        a.eval_step(sa, a.put_batch(_batches(1)[0]), None),
        p.eval_step(sp, p.put_batch(_batches(1)[0]), None))


def test_accumulated_microbatches_average_the_aux_scalars():
    trainer, state = _trainer(_with_aux, accum_steps=2)
    b = _batches(1)[0]
    state0 = jax.tree_util.tree_map(np.asarray, state["params"])
    _, m = trainer.train_step(state, trainer.put_batch(b),
                              jax.random.PRNGKey(0))
    halves = [_with_aux(state0, {k: v[i * 4:(i + 1) * 4]
                                 for k, v in b.items()}, None)
              for i in range(2)]
    np.testing.assert_allclose(
        m["err.max"], np.mean([h[1]["err.max"] for h in halves]), rtol=1e-6)
    np.testing.assert_allclose(
        m["loss"], np.mean([h[0] for h in halves]), rtol=1e-6)


def test_the_loss_alone_is_evaluated_before_and_after_the_first_step():
    trainer, state = _trainer(_with_aux)
    b = trainer.put_batch(_batches(1)[0])
    before = trainer.eval_step(state, b, None)      # no step has run yet
    np.testing.assert_allclose(before, _with_aux(
        {"w": np.zeros((3,), np.float32)}, _batches(1)[0], None)[0])
    state, _ = trainer.train_step(state, b, jax.random.PRNGKey(0))
    assert float(trainer.eval_step(state, b, None)) < float(before)


@pytest.mark.parametrize("aux, error", [
    ({"loss": 1.0}, ValueError),                    # the ring's own names
    ({"step": 1.0}, ValueError),
    ({"rows": jnp.ones((ROWS,))}, TypeError),       # not a scalar
    (jnp.ones(()), TypeError),                      # not a dict
])
def test_an_aux_the_ring_cannot_hold_is_refused_at_the_first_step(
        aux, error):
    trainer, state = _trainer(lambda p, b, r: (_plain(p, b, r), aux))
    with pytest.raises(error, match="aux"):
        trainer.train_step(state, trainer.put_batch(_batches(1)[0]),
                           jax.random.PRNGKey(0))


# ----------------------------------------------- the glm4_moe_lite step
AUX = ("loss.main", "loss.mtp", "moe.slots_here", "moe.rows_moved",
       "moe.overflow_layers", "moe.load_max_over_mean")


def _lm_trainer(length=16, chunk=8, edit=lambda params: params, **model):
    """The tiny model's trainer, its state and a batch of two rows;
    ``edit`` changes the seeded parameters, ``model`` the zoo entry's
    arguments."""
    module = build_model("glm4_moe_lite_tiny",
                         **{"experts_held": (4, 2), **model})["module"]

    def loss_fn(params, batch, rng):
        out = module.apply(params, batch["tokens"], hidden=True)
        loss, parts = next_token_loss(
            out, params["params"]["lm_head"]["kernel"], batch["tokens"],
            chunk=chunk, dtype=jnp.float32)
        return loss, {**parts, **out["stats"]}
    trainer = DistributedTrainer(
        loss_fn, optax.adamw(1e-3), mesh=mesh_from_config(jax.devices()[:1]))
    tokens = np.random.default_rng(1).integers(
        0, 96, size=(2, length)).astype(np.int32)
    state = trainer.init(lambda: edit(module.init(
        jax.random.PRNGKey(0), jnp.asarray(tokens))))
    return trainer, state, {"tokens": tokens}


def test_the_lm_step_counts_its_grouped_products_and_publishes_its_gauges():
    calls = obsmetrics.counter("moe.grouped_calls.ragged_dot")
    attn = obsmetrics.counter("attention.fused_calls.reference")
    trainer, state, batch = _lm_trainer()
    c0, a0 = calls.value, attn.value
    before = obssyncs.total()
    for _ in range(3):
        state, m = trainer.train_step(state, trainer.put_batch(batch),
                                      jax.random.PRNGKey(0))
    assert obssyncs.total() == before
    # counted per TRACE of the loss, as attention.fused_calls.* are (a
    # recomputed block is traced once), and the trainer traces a loss with
    # aux twice: the first trace stops at its keys. Three routed layers
    # (two blocks and the MTP block) x gate, up, down; four blocks'
    # attention
    assert calls.value - c0 == 2 * 9
    assert attn.value - a0 == 2 * 4
    ring = trainer.flush_metrics()
    assert set(ring) == set(AUX) | {"loss", "step"}
    for name in AUX:
        assert obsmetrics.gauge(name).value == float(m[name])
    # 4 of the 8 experts are held: about half of 3 layers x 32 tokens x 2
    assert 0 < float(m["moe.slots_here"]) < 3 * 32 * 2
    assert float(m["moe.load_max_over_mean"]) >= 1.0
    assert float(m["moe.overflow_layers"]) == 0     # buffers of every slot
    assert float(m["moe.rows_moved"]) == 3 * 32 * 2
    np.testing.assert_allclose(
        m["loss"], m["loss.main"] + 0.3 * m["loss.mtp"], rtol=1e-6)


@pytest.mark.parametrize("onto_held, layers", [(False, 0), (True, 3)])
def test_the_layers_that_ran_at_full_size_are_counted_in_the_ring(
        onto_held, layers):
    """2 of 16 experts over 512 tokens x top-2: the expert-order buffers
    hold 512 of the 1,024 slots. A router biased onto the held experts
    sends all of them here, in the two routed blocks and the MTP block,
    and the ring says how many rows each step's buffers held."""
    def bias_onto_held(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, v: v.at[4:6].set(10.0)
            if "router_bias" in jax.tree_util.keystr(path) else v, params)
    trainer, state, batch = _lm_trainer(
        length=256, chunk=64, num_experts=16, experts_held=(2, 4),
        max_len=256, **({"edit": bias_onto_held} if onto_held else {}))
    before = obssyncs.total()
    for _ in range(2):
        state, m = trainer.train_step(state, trainer.put_batch(batch),
                                      jax.random.PRNGKey(0))
    assert obssyncs.total() == before       # the predicate stays on device
    ring = trainer.flush_metrics()
    np.testing.assert_array_equal(ring["moe.overflow_layers"][:2], layers)
    assert obsmetrics.gauge("moe.overflow_layers").value == layers
    rows = 3 * (1024 if onto_held else 512)
    np.testing.assert_array_equal(ring["moe.rows_moved"][:2], rows)
    assert obsmetrics.gauge("moe.rows_moved").value == rows
    if onto_held:
        np.testing.assert_array_equal(ring["moe.slots_here"][:2], 3 * 1024)
    else:
        assert np.all(ring["moe.slots_here"][:2] <= 3 * 512)
    assert np.all(np.isfinite(ring["loss"][:2]))


def test_the_lm_step_program_carries_its_named_scopes():
    trainer, state, batch = _lm_trainer()
    batch, rng = trainer.put_batch(batch), jax.random.PRNGKey(0)
    state, _ = trainer.train_step(state, batch, rng)    # finds the keys
    assert set(trainer._aux_names) == set(AUX)
    fn = trainer._get_train_step(False)
    with trainer.mesh:
        text = fn.lower(state, trainer._init_ring(), batch,
                        rng).as_text(debug_info=True)
    for scope in ("mla_attention", "moe_router", "moe_dispatch",
                  "moe_experts", "moe_combine", "mtp", "lm_loss",
                  "loss_and_grad", "optimizer_update", "metrics_ring"):
        assert scope in text, scope
