"""``sdar_moe`` at a toy size against the plain reference
(``benchmark/references/sdar_moe.py``), and what it brought: rows of
``[noised copy | clean copy]`` under the block-diffusion mask, in the
reference path and in both flash kernels; positions that repeat within a
row; the masked-diffusion loss; a softmax-routed layer with no shared
expert. float32 on the CPU.

Tolerances: both sides compute in float32 on one backend, so they differ
only by the order of additions (grouped products and a chunked loss against
dense loops and whole logits): 1e-5 relative (and absolute, on rows of
unit size) on hidden rows and losses, 1e-4 on gradients, 2e-3 on the norm
of three Adam steps; the kernels in interpret mode against the masked
dense product 2e-5 absolute on unit normal inputs. What has to be exact is exact: what a position may not see
moves none of its bits, an unmasked position's zero weight, a frozen gate's
zero gradient, the argument absent against today's call.
"""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmark.references import sdar_moe as ref  # noqa: E402
from mmlspark_tpu.models.zoo import build_model  # noqa: E402
from mmlspark_tpu.models.zoo.moe import DroplessMoe  # noqa: E402
from mmlspark_tpu.models.zoo.parts import (  # noqa: E402
    plain_frequencies, rotary)
from mmlspark_tpu.observability import metrics as obsmetrics  # noqa: E402
from mmlspark_tpu.ops import pallas_attention as pa  # noqa: E402
from mmlspark_tpu.parallel import sequence  # noqa: E402
from mmlspark_tpu.train.lm_loss import masked_diffusion_loss  # noqa: E402

CFG = dict(hidden_size=32, num_hidden_layers=3, num_attention_heads=4,
           num_key_value_heads=2, head_dim=8, moe_intermediate_size=16,
           num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
           rope_theta=1e4, rms_norm_eps=1e-6, vocab_size=96,
           program={"zoo_args": {"dtype": jnp.float32, "gate_grad": False,
                                 "block_length": 4}},
           deployment={"num_experts_published": 8, "experts_first": 0})
OPT = dict(learning_rate=1e-2, beta1=0.9, beta2=0.95, eps=1e-8,
           weight_decay=0.1)
ROWS, LEN, BLOCK = 2, 16, 4
MASK = ref.mask_token(CFG)


def _cfg(**zoo_args):
    return dict(CFG, program={"zoo_args": {
        **CFG["program"]["zoo_args"], **zoo_args}})


def _rows(seed, steps=1, eps=1e-3, block=BLOCK):
    """(tokens, noised, weight), each (steps, ROWS, LEN)."""
    tokens = np.random.default_rng(seed).integers(
        0, MASK, size=(steps * ROWS, LEN)).astype(np.int32)
    noised, weight = ref.noise(seed, tokens, block, eps, MASK)
    return tuple(a.reshape(steps, ROWS, LEN)
                 for a in (tokens, noised, weight))


def _module(cfg=CFG):
    return build_model("sdar_moe", **ref.zoo_args(cfg, LEN))["module"]


def _ids(batch):
    return jnp.concatenate([batch["noised"], batch["tokens"]], axis=1)


def _loss_fn(module, chunk=8):
    def loss_fn(params, batch, rng):
        out = module.apply(params, _ids(batch), hidden=True)
        loss, aux = masked_diffusion_loss(
            out, params["params"]["lm_head"]["kernel"], batch["tokens"],
            batch["weight"], chunk=chunk, dtype=jnp.float32)
        return loss, {**aux, **out["stats"]}
    return loss_fn


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b)


def _away(path, v):
    """Scales off 1 and routers' scores apart, so that none is a factor a
    wrong wiring could hide behind."""
    name = jax.tree_util.keystr(path)
    if "scale" in name:
        return v + jnp.linspace(-0.5, 0.5, v.size).reshape(v.shape)
    if "router']['kernel" in name:
        return 8.0 * v
    return v


@pytest.fixture(scope="module")
def params():
    return jax.tree_util.tree_map_with_path(
        _away, ref.init_params(CFG, jax.random.PRNGKey(7)))


def _close(got, want, rtol=1e-4):
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            g, w, rtol=rtol, atol=rtol * float(jnp.abs(w).max()) + 1e-9,
            err_msg=jax.tree_util.keystr(path))


# ------------------------------------------------- the model on the path
def test_reference_tree_is_the_programs_tree(params):
    module = _module()
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 2 * LEN), jnp.int32))
    assert jax.tree_util.tree_structure(shapes) \
        == jax.tree_util.tree_structure(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(shapes),
                            jax.tree_util.tree_leaves(params)):
        assert a.shape == b.shape, jax.tree_util.keystr(path)


def test_the_published_configuration_counts_its_parameters():
    """Three counts agree: the configuration file's, the reference's
    ``param_shapes`` and the program's own tree at the cell's size (shapes
    only, nothing is allocated); and the published model's from the same
    function."""
    cfg = json.loads((ROOT / "benchmark/configs/sdar-30b-a3b.json")
                     .read_text())
    here = ref.parameters(cfg)
    assert here == 456_346_624
    assert "456,346,624" in cfg["deployment"]["parameters_here"]
    module = build_model("sdar_moe", **ref.zoo_args(cfg, 4096))["module"]
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8192), jnp.int32))
    assert sum(int(np.prod(x.shape)) for x in
               jax.tree_util.tree_leaves(shapes)) == here
    dep = cfg["deployment"]
    published = dict(
        cfg, num_hidden_layers=dep["num_hidden_layers_published"],
        num_experts=dep["num_experts_published"],
        vocab_size=dep["vocab_size_published"])
    assert ref.parameters(published) == 30_532_122_624
    assert "30,532,122,624" in dep["parameters_published"]
    # every published width, head count and the rotary rule as catalogued
    assert {k: cfg[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "moe_intermediate_size", "num_experts_per_tok",
        "rope_theta", "rope_scaling", "rms_norm_eps",
        "norm_topk_prob")} == {
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 4, "head_dim": 128,
        "moe_intermediate_size": 768, "num_experts_per_tok": 8,
        "rope_theta": 1000000, "rope_scaling": None, "rms_norm_eps": 1e-6,
        "norm_topk_prob": True}
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert dep["chips_sharing_each_layer"] == 8
    assert {"block_length", "noise_schedule", "unshifted_target", "qk_norm",
            "mask_token"} <= set(cfg["assumed"])


def test_hidden_rows_of_both_halves_match_the_reference(params):
    tokens, noised, _ = (a[0] for a in _rows(1))
    got = jax.jit(lambda p, x: _module().apply(p, x, hidden=True)["hidden"])(
        params, _ids({"tokens": tokens, "noised": noised}))
    assert got.shape == (ROWS, 2 * LEN, 32) and got.dtype == jnp.float32
    rows = jax.jit(lambda p, t, n: ref.diffusion_rows(
        CFG, _mm, p, t, n)["hidden"])
    for b in range(ROWS):
        np.testing.assert_allclose(
            got[b], rows(params, jnp.asarray(tokens[b]),
                         jnp.asarray(noised[b])), rtol=1e-5, atol=1e-5)


def _ref_loss_and_grads(cfg, params, rows):
    grad = jax.jit(jax.value_and_grad(
        lambda p, *row: ref.sequence_loss(cfg, None, None, ROWS, p, *row),
        has_aux=True))
    loss, total = 0.0, None
    for row in zip(*rows):
        (part, _), g = grad(params, *(jnp.asarray(x) for x in row))
        loss = loss + part
        total = g if total is None else jax.tree_util.tree_map(
            jnp.add, total, g)
    return loss, total


@pytest.mark.parametrize("gate_grad", [False, True])
def test_losses_and_gradients_match_the_reference(params, gate_grad):
    cfg = _cfg(gate_grad=gate_grad)
    tokens, noised, weight = (a[0] for a in _rows(2))
    batch = {"tokens": jnp.asarray(tokens), "noised": jnp.asarray(noised),
             "weight": jnp.asarray(weight)}
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        _loss_fn(_module(cfg)), has_aux=True))(params, batch, None)
    want_loss, want = _ref_loss_and_grads(cfg, params,
                                          (tokens, noised, weight))
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    np.testing.assert_allclose(aux["loss.main"], want_loss, rtol=1e-5)
    np.testing.assert_allclose(aux["diffusion.masked_share"],
                               (weight > 0).mean(), rtol=1e-6)
    _close(grads, want)
    named = {jax.tree_util.keystr(k): np.asarray(v) for k, v in
             jax.tree_util.tree_leaves_with_path(grads)}
    routers = [v for k, v in named.items() if "['router']" in k]
    assert len(routers) == 3
    for g in routers:       # a frozen gate's router gets EXACTLY nothing
        assert np.any(g) == gate_grad


def test_three_adamw_steps_through_the_trainer_match_the_reference():
    from mmlspark_tpu.parallel.mesh import mesh_from_config
    from mmlspark_tpu.parallel.trainer import DistributedTrainer
    seed = 11
    tokens, noised, weight = _rows(3, steps=3)
    want = ref.train_reference(CFG, seed, tokens, noised, weight, steps=3,
                               optimizer=OPT)
    trainer = DistributedTrainer(
        _loss_fn(_module()),
        optax.adamw(OPT["learning_rate"], b1=OPT["beta1"], b2=OPT["beta2"],
                    eps=OPT["eps"], weight_decay=OPT["weight_decay"],
                    mask=lambda p: jax.tree_util.tree_map(
                        lambda x: x.ndim >= 2, p)),
        mesh=mesh_from_config(jax.devices()[:1]))
    key = jax.random.PRNGKey(seed)
    state = trainer.init(lambda: ref.init_params(CFG, key))
    start = jax.tree_util.tree_map(np.asarray, state["params"])
    for s in range(3):
        state, m = trainer.train_step(state, trainer.put_batch({
            "tokens": tokens[s], "noised": noised[s], "weight": weight[s]}),
            jax.random.PRNGKey(0))
        np.testing.assert_allclose(m["loss"], want["losses"][s], rtol=1e-5)
        if s == 0:      # the first gradient, from AdamW's first moment
            mu = state["opt_state"][0].mu
            for g, w in zip(jax.tree_util.tree_leaves(mu),
                            want["first_grad"]):
                np.testing.assert_allclose(
                    np.asarray(g) / (1 - OPT["beta1"]), w, rtol=1e-4,
                    atol=1e-4 * float(np.abs(w).max()) + 1e-9)
    moved = ref.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, state["params"], start))
    for k, v in moved.items():
        np.testing.assert_allclose(float(v), want["delta_norms"][k],
                                   rtol=2e-3, err_msg=k)
    # every routed slot of the uncut toy model is held here: both halves
    assert float(m["moe.slots_here"]) == 3 * ROWS * 2 * LEN * 2
    np.testing.assert_allclose(m["diffusion.masked_share"],
                               (weight[2] > 0).mean(), rtol=1e-6)
    ring = trainer.flush_metrics()
    assert "diffusion.masked_share" in ring
    assert want["mtp"] == [] and len(want["routing"]) == 3
    assert want["routing"][0]["choice"].shape == (ROWS * 2 * LEN, 2)
    assert want["routing"][0]["ranked"].shape == (ROWS * 2 * LEN, 8)


def test_at_block_length_one_the_clean_half_is_the_plain_causal_forward(
        params):
    """The tie to the old path: with ``B`` = 1 a clean position sees the
    clean positions up to itself and nothing else, which is the causal
    mask; its hidden rows are the same weights' plain causal forward on
    ``x_0`` alone (the reference's, positions ``0..L-1``)."""
    cfg = _cfg(block_length=1)
    tokens, noised, _ = (a[0] for a in _rows(4, block=1))
    got = jax.jit(lambda p, x: _module(cfg).apply(
        p, x, hidden=True)["hidden"])(
            params, _ids({"tokens": tokens, "noised": noised}))
    causal = jax.jit(lambda p, t: ref.hidden_rows(
        cfg, _mm, p, t, jnp.arange(LEN), ref.causal_seen(LEN))["hidden"])
    for b in range(ROWS):
        np.testing.assert_allclose(
            got[b, LEN:], causal(params, jnp.asarray(tokens[b])),
            rtol=1e-5, atol=1e-5)


def test_no_leak_a_noised_position_sees_its_block_and_the_clean_past(params):
    """Logits of the noised positions of block 2 (positions 8-11 of 16):
    bit for bit unmoved by a clean token of their own or a later block and
    by another block's noised copy; moved by an earlier clean block and by
    their own noised block."""
    module = _module()
    logits = jax.jit(lambda p, x: module.apply(p, x))
    tokens, noised, _ = (a[0][:1] for a in _rows(5))
    base = np.asarray(logits(params, _ids(
        {"tokens": tokens, "noised": noised})))[0, 8:12]

    def moved(half, position):
        t, n = tokens.copy(), noised.copy()
        (n if half == "noised" else t)[0, position] = \
            (int((n if half == "noised" else t)[0, position]) + 1) % MASK
        out = np.asarray(logits(params, _ids(
            {"tokens": t, "noised": n})))[0, 8:12]
        return not np.array_equal(out, base)
    for position in (8, 11, 12, 15):        # own and later clean blocks
        assert not moved("clean", position), position
    for position in (0, 7, 12, 15):         # other blocks' noised copies
        assert not moved("noised", position), position
    for position in (0, 7):                 # an earlier clean block
        assert moved("clean", position), position
    for position in (8, 11):                # its own noised block
        assert moved("noised", position), position


# ---------------------------------------------------------------- the loss
def test_all_masked_is_the_mean_cross_entropy_without_a_shift():
    rng = np.random.default_rng(0)
    hidden = jnp.asarray(rng.normal(size=(2, 2 * 8, 16)), jnp.float32)
    kernel = jnp.asarray(rng.normal(size=(16, 24)), jnp.float32)
    targets = jnp.asarray(rng.integers(0, 24, size=(2, 8)), jnp.int32)
    loss, aux = masked_diffusion_loss(
        {"hidden": hidden}, kernel, targets, jnp.ones((2, 8)), chunk=4,
        dtype=jnp.float32)
    logp = jax.nn.log_softmax(hidden[:, :8] @ kernel, -1)
    want = -jnp.take_along_axis(logp, targets[..., None], -1).mean()
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    assert float(aux["diffusion.masked_share"]) == 1.0
    # the clean half's rows reach no loss
    other = hidden.at[:, 8:].set(0.0)
    assert float(masked_diffusion_loss(
        {"hidden": other}, kernel, targets, jnp.ones((2, 8)), chunk=4,
        dtype=jnp.float32)[0]) == float(loss)
    with pytest.raises(ValueError, match=r"noised \| clean"):
        masked_diffusion_loss({"hidden": hidden[:, :8]}, kernel, targets,
                              jnp.ones((2, 8)))


def test_an_unmasked_position_adds_nothing_and_a_masked_one_its_weight():
    rng = np.random.default_rng(1)
    hidden = jnp.asarray(rng.normal(size=(1, 2 * 8, 16)), jnp.float32)
    kernel = jnp.asarray(rng.normal(size=(16, 24)), jnp.float32)
    targets = jnp.asarray(rng.integers(0, 24, size=(1, 8)), jnp.int32)
    weights = jnp.asarray([[0, 2.5, 0, 0, 1000.0, 0, 1.0, 0]], jnp.float32)

    def loss(t):
        return masked_diffusion_loss({"hidden": hidden}, kernel, t, weights,
                                     chunk=8, dtype=jnp.float32)
    base, aux = loss(targets)
    logp = jax.nn.log_softmax(hidden[0, :8] @ kernel, -1)
    ce = -logp[jnp.arange(8), targets[0]]
    np.testing.assert_allclose(base, (weights[0] * ce).sum() / 8, rtol=1e-6)
    assert float(aux["diffusion.masked_share"]) == 3 / 8
    for position in (0, 2, 3, 5, 7):        # another target, weight 0
        t = targets.at[0, position].set((targets[0, position] + 1) % 24)
        assert float(loss(t)[0]) == float(base)
    # and its gradient reaches no unmasked row
    g = jax.grad(lambda h: masked_diffusion_loss(
        {"hidden": h}, kernel, targets, weights, chunk=8,
        dtype=jnp.float32)[0])(hidden)
    live = np.asarray(jnp.abs(g[0]).sum(-1) > 0)
    assert live.tolist() == [False, True, False, False, True, False, True,
                             False] + [False] * 8


def test_noise_is_the_seeds_alone_and_weighs_one_over_t():
    tokens = np.random.default_rng(3).integers(0, MASK, size=(64, 32))
    a = ref.noise(2 ** 31 + 9, tokens, 4, 1e-3, MASK)
    b = ref.noise(2 ** 31 + 9, tokens.copy(), 4, 1e-3, MASK)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    noised, weight = a
    assert not np.array_equal(noised, ref.noise(
        2 ** 31 + 10, tokens, 4, 1e-3, MASK)[0])
    masked = noised == MASK
    assert np.array_equal(masked, weight > 0)
    assert np.array_equal(noised[~masked], tokens[~masked])
    # one t a block: the masked positions of a block carry one weight
    blocks = weight.reshape(64, 8, 4)
    top = blocks.max(-1, keepdims=True)
    assert np.all((blocks == 0) | (blocks == top))
    assert weight[masked].min() >= 1.0 and weight.max() <= 1000.0
    # E[w] = 1 a position under the linear schedule, about half masked
    assert 0.8 < weight.mean() < 1.2 and 0.4 < masked.mean() < 0.6


# ------------------------------------------------------ the routed layer
def _layer(held, first, **kw):
    return DroplessMoe(32, 16, 8, 4, experts_held=(held, first),
                       dtype=jnp.float32, scores="softmax", **kw)


def _share(p, first, count):
    ffn = dict(p["params"])
    for name in ("experts_gate", "experts_up", "experts_down"):
        ffn[name] = ffn[name][first:first + count]
    return {"params": ffn}


def test_four_shares_add_up_to_the_uncut_reference_no_shared_expert():
    """The deployment's layout at toy widths: 16 softmax-routed experts,
    four a token, four chips with four each and NO shared expert: the
    shares' partial results sum to the uncut layer as the REFERENCE
    computes it, every slot computed exactly once and nothing counted
    twice."""
    whole = _layer(16, 0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    p = whole.init(jax.random.PRNGKey(3), x)
    p["params"]["router"]["kernel"] = 8.0 * p["params"]["router"]["kernel"]
    assert set(p["params"]) == {"router", "experts_gate", "experts_up",
                                "experts_down"}
    d = dict(ref.dims(CFG), experts=16, held=16, first=0, top_k=4,
             gate_grad=True)
    want = jax.vmap(lambda row: ref._experts(d, _mm, p["params"], row)[0])(x)
    total, slots = 0.0, 0
    for first in range(0, 16, 4):
        y, stats = _layer(4, first).apply(_share(p, first, 4), x)
        total, slots = total + y, slots + int(stats["slots_here"])
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-6)
    assert slots == 2 * 16 * 4
    np.testing.assert_allclose(whole.apply(p, x)[0], want, rtol=1e-5,
                               atol=1e-6)
    # one share of the reference is that share of the program
    d4 = dict(d, held=4, first=8)
    part = jax.vmap(lambda row: ref._experts(
        d4, _mm, _share(p, 8, 4)["params"], row)[0])(x)
    np.testing.assert_allclose(
        _layer(4, 8).apply(_share(p, 8, 4), x)[0], part, rtol=1e-5,
        atol=1e-6)


# ------------------------------------------------------- positions, masks
def test_rotary_at_given_positions():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 3, 8))
    freqs = plain_frequencies(8, 1e4)
    plain = rotary(x, freqs)
    assert np.array_equal(np.asarray(rotary(
        x, freqs, positions=jnp.arange(16))), np.asarray(plain))
    text = [jax.jit(lambda x: rotary(x, freqs, *extra)).lower(x).as_text()
            for extra in ((), (1.0, None))]
    assert text[0] == text[1]
    # a row of two copies: each half turns as a row of its own
    twice = rotary(x, freqs, positions=jnp.arange(16) % 8)
    np.testing.assert_array_equal(np.asarray(twice[:, :8]),
                                  np.asarray(rotary(x[:, :8], freqs)))
    np.testing.assert_array_equal(np.asarray(twice[:, 8:]),
                                  np.asarray(rotary(x[:, 8:], freqs)))


@pytest.mark.parametrize("half,block", [(8, 1), (16, 4), (12, 3), (8, 8)])
def test_the_mask_is_the_references_and_a_quarter_is_live(half, block):
    mask = pa.block_diffusion_mask(half, block)
    assert np.array_equal(mask, np.asarray(
        ref.block_diffusion_seen(half, block)))
    assert mask.sum() == half * half + half * block \
        == ref.live_pairs(half, block)
    assert not mask[half:, :half].any()         # clean never sees noised
    assert mask[np.arange(2 * half), np.arange(2 * half)].all()
    # by loops, from the three cases
    count = 0
    for i in range(2 * half):
        for j in range(2 * half):
            bi, bj = (i % half) // block, (j % half) // block
            seen = (bj == bi) if i < half and j < half else \
                (bj < bi) if i < half else (bj <= bi) if j >= half else False
            assert mask[i, j] == seen, (i, j)
            count += seen
    assert count == ref.live_pairs(half, block)


def test_block_diffusion_needs_causal_halves_and_whole_blocks():
    q = jnp.ones((1, 32, 2, 8))
    for bad in dict(causal=False), dict(window=4), \
            dict(block_diffusion=(15, 3)), dict(block_diffusion=(16, 3)), \
            dict(block_diffusion=(16, 0)):
        kw = {"causal": True, "block_diffusion": (16, 4), **bad}
        with pytest.raises(ValueError, match="block_diffusion"):
            sequence.full_attention(q, q, q, **kw)


# ------------------------------------------------------------- the kernels
H_K, D_K = 2, 32


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 128, so that a half of 256 or 384 positions is two or
    three tiles and an interpreted call takes a second."""
    monkeypatch.setattr(pa, "_DIFFUSION_TILE", 128)


def _qkvd(half, seed=0, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return tuple(jax.random.normal(k, (1, 2 * half, H_K, D_K), dtype)
                 for k in keys)


@pytest.mark.parametrize("half,block", [
    (256, 1), (256, 4), (256, 128), (384, 4), (384, 64)])
def test_masked_flash_matches_the_masked_dense_product(small_tiles, half,
                                                       block):
    """Forward and backward in interpret mode, ``L`` of two and three
    tiles, ``B`` of 1, 4, part of a tile and a whole tile."""
    q, k, v, do = _qkvd(half, seed=block)
    assert pa.supports_block_diffusion(q.shape, (half, block), 128, 128)
    want, pull = jax.vjp(lambda q, k, v: sequence._reference_attention(
        q, k, v, True, None, (half, block)), q, k, v)
    got, pull_k = jax.vjp(lambda q, k, v: pa.flash_attention(
        q, k, v, True, 128, 128, block_diffusion=(half, block)), q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-5)
    for g, w in zip(pull_k(do), pull(do)):
        np.testing.assert_allclose(g, w, atol=2e-5)


def test_the_argument_absent_lowers_to_todays_call():
    """``block_diffusion=None`` is today's program: the same text, forward
    and backward, whether the argument is given or left out, and the same
    bits; a masked row's text carries its own two names and neither the
    causal backward's nor a band's."""
    q, k, v, do = (t.astype(jnp.bfloat16) for t in _qkvd(128, 2))

    def f(*extra, **kw):
        def run(q, k, v, do):
            out, pull = jax.vjp(lambda q, k, v: pa.flash_attention(
                q, k, v, True, 128, 128, *extra, **kw), q, k, v)
            return out, pull(do)
        return jax.jit(run)

    def text(*extra, names=False, **kw):
        return f(*extra, **kw).lower(q, k, v, do).as_text(debug_info=names)
    assert text() == text(None, None)
    for a, b in zip(jax.tree_util.tree_leaves(f()(q, k, v, do)),
                    jax.tree_util.tree_leaves(f(None, None)(q, k, v, do))):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    masked = text(names=True, block_diffusion=(128, 4))
    plain = text(names=True)
    assert "block_diffusion_attention_fwd" in masked
    assert "block_diffusion_attention_bwd" in masked
    assert "long_attention_bwd" not in masked
    assert "window_attention" not in masked
    assert "block_diffusion" not in plain and "long_attention_bwd" in plain


def test_the_tiles_lie_in_one_half_and_hold_whole_blocks():
    assert pa._fwd_tiles(256, 256, 8192, 128, None, (4096, 4)) == (512, 512)
    assert pa._diffusion_tile(256, 4096) == 512
    assert pa._diffusion_tile(256, 768) == 256
    assert pa.supports_block_diffusion((4, 8192, 32, 128), (4096, 4))
    assert pa.supports_block_diffusion((1, 1024, 2, 64), (512, 512))
    # a block that straddles two tiles, a half that is no whole tile, a
    # row the flash kernel does not take at all (since PR 51 its cap
    # counts K + V in bytes, 16 MiB: float32 at 16,384 x 128 is its edge)
    assert not pa.supports_block_diffusion((1, 1536, 2, 64), (768, 3))
    assert not pa.supports_block_diffusion((1, 768, 2, 64), (384, 4))
    assert pa.supports_block_diffusion((1, 16384, 2, 128), (8192, 4))
    assert not pa.supports_block_diffusion((1, 32768, 2, 128), (16384, 4))
    with pytest.raises(ValueError, match="supports_block_diffusion"):
        pa.flash_attention(*_qkvd(192)[:3], True, block_diffusion=(192, 4))
    # the causal calls' tiles are what they were
    assert pa._fwd_tiles(256, 256, 8192, 128) == (1024, 512)
    assert pa._fwd_tiles(256, 256, 8192, 128, 512) == (512, 512)
    assert pa._bwd_tile(256, 8192) == 512


def test_full_attention_hands_the_mask_through_and_counts_it(small_tiles):
    q, k, v, _ = _qkvd(256, 3)
    names = ("attention.fused_calls.block_diffusion",
             "attention.fused_calls.flash",
             "attention.fused_calls.reference", "attention.flash_fallbacks")
    before = {n: obsmetrics.counter(n).value for n in names}

    def since():
        return {n.rsplit(".", 1)[1]: obsmetrics.counter(n).value - before[n]
                for n in names}
    want = sequence._reference_attention(q, k, v, True, None, (256, 4))
    got = sequence.full_attention(q, k, v, True, "require",
                                  block_diffusion=(256, 4))
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert since() == {"block_diffusion": 1, "flash": 0, "reference": 0,
                       "flash_fallbacks": 0}
    # on the CPU "auto" runs the masked reference path
    np.testing.assert_allclose(sequence.full_attention(
        q, k, v, True, block_diffusion=(256, 4)), want, atol=1e-6)
    assert since()["reference"] == 1
    # a shape no kernel takes is refused under "require", never passed to
    # the causal kernel
    short = tuple(t[:, :64] for t in (q, k, v))
    with pytest.raises(ValueError, match="no fused kernel"):
        sequence.full_attention(*short, True, "require",
                                block_diffusion=(32, 4))


def test_a_row_the_kernel_cannot_take_counts_as_a_fallback(monkeypatch):
    monkeypatch.setattr(sequence, "_on_chip", lambda: True)
    q = jnp.ones((1, 64, 2, 32))
    before = obsmetrics.counter("attention.flash_fallbacks").value
    got = sequence.full_attention(q, q, q, True, block_diffusion=(32, 4))
    assert got.shape == q.shape
    assert obsmetrics.counter("attention.flash_fallbacks").value \
        == before + 1


def test_the_mixer_names_its_scope_and_keeps_the_kernels_residuals(
        small_tiles):
    """``block_diffusion_attention`` around the call, under
    ``grouped_attention`` (a scope the benchmark's split counts as
    attention), the loss under ``lm_loss``; the masked call's residuals
    carry the causal call's checkpoint name, so ``_remat_block``'s one
    list keeps them and a recomputed block holds no second forward call."""
    module = build_model("sdar_moe_tiny")["module"]
    ids = jnp.zeros((1, 16), jnp.int32)
    p = jax.jit(module.init)(jax.random.PRNGKey(0), ids)

    def loss(p):
        return masked_diffusion_loss(
            module.apply(p, ids, hidden=True), p["params"]["lm_head"][
                "kernel"], ids[:, :8], jnp.ones((1, 8)), chunk=8)[0]
    text = jax.jit(loss).lower(p).as_text(debug_info=True)
    assert "grouped_attention/block_diffusion_attention" in text
    assert "lm_loss" in text and "moe_router" in text
    q, k, v, _ = _qkvd(256, 4)
    policy = jax.checkpoint_policies.save_only_these_names(
        pa.FLASH_RESIDUALS)
    jaxpr = str(jax.make_jaxpr(jax.grad(jax.checkpoint(
        lambda q: pa.flash_attention(
            q, k, v, True, 128, 128, block_diffusion=(256, 4)).sum(),
        policy=policy)))(q))
    assert pa.FLASH_RESIDUALS in jaxpr
    assert jaxpr.count(pa._DIFFUSION_FWD_NAME) == 1
    assert jaxpr.count(pa._DIFFUSION_BWD_NAME) == 1
