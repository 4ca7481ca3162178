"""``lfm2_moe`` at its tiny preset against the plain reference
(``benchmark/references/lfm2_moe.py``), and the parts it brought: the
gated short convolution, grouped attention with a norm a head and rotary
positions, a routed layer without a shared expert whose gate may take no
gradient, a family whose mixer AND feed-forward part both depend on the
layer's index. float32 on the CPU.

Tolerances: both sides compute in float32 on one backend, so they differ
only by the order of additions (grouped products and a chunked loss against
dense loops and whole logits): 1e-5 relative on logits and losses, 1e-4 on
gradients, 2e-3 on the norm of three Adam steps (``g / (sqrt(v) + eps)``
amplifies a relative gradient error where ``g`` is near zero). What has to
be exact is exact: a frozen gate's zero gradient (the families' outputs
against a named commit's are ``tests/test_decoder_programs.py``'s).
"""
import inspect
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark.references import lfm2_moe as ref  # noqa: E402
from mmlspark_tpu.models.zoo import build_model, decoder  # noqa: E402
from mmlspark_tpu.models.zoo.decoder import (  # noqa: E402
    LFM2_24B_A2B_LAYERS)
from mmlspark_tpu.models.zoo.parts import (  # noqa: E402
    SHORT_CONV_IN, GroupedAttention, ShortConv, plain_frequencies)
from mmlspark_tpu.models.zoo.moe import DroplessMoe  # noqa: E402
from mmlspark_tpu.observability import metrics as obsmetrics  # noqa: E402
from mmlspark_tpu.train.lm_loss import next_token_loss  # noqa: E402

KINDS = ("conv", "full_attention", "conv", "conv")
CFG = dict(hidden_size=32, num_hidden_layers=4, layer_types=list(KINDS),
           num_dense_layers=1, intermediate_size=48,
           moe_intermediate_size=16, num_experts=8, num_experts_per_tok=2,
           routed_scaling_factor=1, norm_topk_prob=True,
           use_expert_bias=True, num_attention_heads=4,
           num_key_value_heads=2,
           rope_parameters={"rope_theta": 1e6, "rope_type": "default"},
           norm_eps=1e-5, conv_L_cache=3, conv_bias=False, vocab_size=96,
           program={"zoo_args": {"dtype": jnp.float32, "gate_grad": False}},
           deployment={"num_experts_published": 8, "experts_first": 0})
OPT = dict(learning_rate=1e-2, beta1=0.9, beta2=0.95, eps=1e-8,
           weight_decay=0.1)
ROWS, LEN = 2, 16
REPO = Path(__file__).resolve().parent.parent


def _defaults(name):
    """The zoo entry's own defaults, by keyword: what a family is lives in
    its entry, the module it builds holds parts."""
    return {k: p.default for k, p in inspect.signature(
        getattr(decoder, name)).parameters.items()}
GATES = pytest.mark.parametrize("gate_grad", [False, True],
                                ids=["frozen_gate", "trained_gate"])


def _cfg(gate_grad=False, **changes):
    program = {"zoo_args": {"dtype": jnp.float32, "gate_grad": gate_grad}}
    return dict(CFG, program=program, **changes)


def _tokens(seed, steps=1):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], size=(steps, ROWS, LEN)).astype(np.int32)


def _module(cfg=CFG):
    return build_model("lfm2_moe", **ref.zoo_args(cfg, 64))["module"]


def _loss_fn(module, chunk=8):
    def loss_fn(params, batch, rng):
        out = module.apply(params, batch["tokens"], hidden=True)
        loss, aux = next_token_loss(
            out, ref.head_kernel(params), batch["tokens"], chunk=chunk,
            dtype=jnp.float32)
        return loss, {**aux, **out["stats"]}
    return loss_fn


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b)


def _away(path, v):
    """Scales off 1 and a router bias off 0, so that neither is a factor
    a wrong wiring could hide behind."""
    name = jax.tree_util.keystr(path)
    if "scale" in name:
        return v + jnp.linspace(-0.5, 0.5, v.size).reshape(v.shape)
    if "router_bias" in name:
        return v + jnp.linspace(-0.02, 0.02, v.size)
    if "router']['kernel" in name:
        return 8.0 * v          # scores that differ between experts
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


# ------------------------------------------------- the short convolution
@pytest.mark.parametrize("bias", [False, True])
def test_short_conv_is_its_three_term_sum_and_causal(bias):
    layer = ShortConv(32, 3, bias, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 32))
    p = layer.init(jax.random.PRNGKey(2), u)
    assert set(p["params"]) == {"attn_in", "conv_kernel", "attn_out"} | (
        {"conv_bias"} if bias else set())
    w = p["params"]
    bcx = np.asarray(u) @ np.asarray(w["attn_in"]["kernel"])
    B, C, x = bcx[..., :32], bcx[..., 32:64], bcx[..., 64:]
    z, k = B * x, np.asarray(w["conv_kernel"])
    c = np.zeros_like(z)
    for t in range(12):         # c_t = k_0 z_{t-2} + k_1 z_{t-1} + k_2 z_t
        for j in range(3):
            if t - 2 + j >= 0:
                c[:, t] += k[j] * z[:, t - 2 + j]
    if bias:
        c = c + np.asarray(w["conv_bias"])
    want = (C * c) @ np.asarray(w["attn_out"]["kernel"])
    got = layer.apply(p, u)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    if not bias:
        # the first position sees one tap, the second two: zeros before
        np.testing.assert_allclose(
            got[:, 0], (C[:, 0] * k[2] * z[:, 0])
            @ np.asarray(w["attn_out"]["kernel"]), rtol=1e-5, atol=1e-6)
        # the reference's layer is the same layer
        d = ref.dims(CFG)
        for b in range(2):
            np.testing.assert_allclose(
                got[b], ref._short_conv(d, _mm, w, u[b]), rtol=1e-5,
                atol=1e-6)
    # a later token moves no earlier output, and does move its own
    later = layer.apply(p, u.at[:, 7].add(1.0))
    assert np.array_equal(np.asarray(later[:, :7]), np.asarray(got[:, :7]))
    assert not np.allclose(later[:, 7], got[:, 7])
    assert not np.allclose(later[:, 9], got[:, 9])      # two tokens on
    assert np.array_equal(np.asarray(later[:, 10:]), np.asarray(got[:, 10:]))


def test_short_conv_is_counted_per_trace():
    before = obsmetrics.counter("short_conv.calls").value
    layer = ShortConv(16, dtype=jnp.float32)
    u = jnp.ones((1, 8, 16))
    layer.apply(layer.init(jax.random.PRNGKey(0), u), u)
    assert obsmetrics.counter("short_conv.calls").value >= before + 1


# ---------------------------------------------------- the softmax part
def test_attention_with_a_norm_a_head_and_rotary_positions(params):
    """Per-head norms with ONE scale of ``head_dim``, rotary positions on
    the whole head, four query heads over two key/value heads: the
    reference's layer, one sequence at a time."""
    d = ref.dims(CFG)
    layer = GroupedAttention(32, 4, 2, 8, None, jnp.float32, None, 1e-5,
                             norm_heads=True,
                             rotary_freqs=plain_frequencies(8, 1e6))
    p = params["params"]["block1"]["attn"]
    assert p["query_norm"]["scale"].shape == (8,)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 12, 32))
    got = layer.apply({"params": p}, x)
    for b in range(2):
        np.testing.assert_allclose(
            got[b], ref._attention(d, _mm, p, x[b]), rtol=1e-5, atol=1e-6)
    # positions matter (shifting a row in time changes it) ...
    rolled = layer.apply({"params": p}, jnp.roll(x, 1, axis=1))
    assert not np.allclose(rolled[:, 2:], got[:, 1:-1], atol=1e-3)
    # ... and the norm is over a head, not over the whole projection
    whole = GroupedAttention(32, 4, 2, 8, None, jnp.float32, None, 1e-5,
                             rotary_freqs=plain_frequencies(8, 1e6))
    assert whole.init(jax.random.PRNGKey(0), x)["params"]["query_norm"][
        "scale"].shape == (32,)


# ----------------------------------------- the model and its reference
def test_reference_tree_is_the_programs_tree_and_layer_kinds(params):
    """Layer kinds follow ``layer_types`` x ``dense_layers``, each on its
    own; the reference's shapes are the program's."""
    module = _module()
    own = module.init(jax.random.PRNGKey(0), jnp.zeros((1, LEN), jnp.int32))
    shapes = lambda t: jax.tree_util.tree_map(lambda x: x.shape, t)
    assert shapes(own) == shapes(params)
    blocks = own["params"]
    assert "lm_head" not in blocks          # one table
    for i, kind in enumerate(KINDS):
        mixer, ffn = blocks[f"block{i}"]["attn"], blocks[f"block{i}"]["ffn"]
        assert ("attn_in" in mixer) == (kind == "conv")
        assert ("attn_query" in mixer) == (kind == "full_attention")
        assert ("mlp_gate" in ffn) == (i < 1)
        assert ("router" in ffn) == (i >= 1)
        assert "shared" not in ffn
    # the two kinds vary independently: attention under a dense part, and
    # a convolution under a routed one
    other = build_model("lfm2_moe_tiny", layer_types=(
        "full_attention", "conv", "conv"), dense_layers=2)["module"]
    tree = other.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, LEN), jnp.int32))["params"]
    assert "attn_query" in tree["block0"]["attn"] \
        and "mlp_gate" in tree["block0"]["ffn"]
    assert "attn_in" in tree["block1"]["attn"] \
        and "mlp_gate" in tree["block1"]["ffn"]
    assert "attn_in" in tree["block2"]["attn"] \
        and "router" in tree["block2"]["ffn"]
    assert ref.routed_blocks(CFG) == ["block1", "block2", "block3"]


def test_a_layer_type_of_another_name_raises():
    with pytest.raises(ValueError, match="'conv' or 'full_attention'"):
        build_model("lfm2_moe_tiny", layer_types=("conv", "linear_attention"))
    with pytest.raises(ValueError, match="layer_types"):
        ref.dims(dict(CFG, layer_types=["conv", "mamba", "conv", "conv"]))


def test_logits_match_the_reference(params):
    tokens = _tokens(1)[0]
    got = _module().apply(params, jnp.asarray(tokens))
    assert got.shape == (ROWS, LEN, CFG["vocab_size"])
    assert got.dtype == jnp.float32
    for b in range(ROWS):
        want = ref.logits(CFG, params, jnp.asarray(tokens[b]))
        np.testing.assert_allclose(got[b], want, rtol=1e-5, atol=1e-6)


def _ref_loss_and_grads(cfg, params, tokens):
    grad = jax.jit(jax.value_and_grad(
        lambda p, t: ref.sequence_loss(cfg, None, len(tokens), p, t),
        has_aux=True))
    loss, total = 0.0, None
    for row in tokens:
        (part, _), g = grad(params, jnp.asarray(row))
        loss = loss + part
        total = g if total is None else jax.tree_util.tree_map(
            jnp.add, total, g)
    return loss, total


@GATES
def test_losses_and_gradients_match_the_reference(params, gate_grad):
    cfg = _cfg(gate_grad)
    tokens = _tokens(2)[0]
    (loss, aux), grads = jax.value_and_grad(
        _loss_fn(_module(cfg)), has_aux=True)(
            params, {"tokens": jnp.asarray(tokens)}, None)
    want_loss, want = _ref_loss_and_grads(cfg, params, tokens)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    np.testing.assert_allclose(aux["loss.main"], want_loss, rtol=1e-5)
    _close(grads, want)
    named = {jax.tree_util.keystr(k): np.asarray(v) for k, v in
             jax.tree_util.tree_leaves_with_path(grads)}
    routers = [v for k, v in named.items() if "['router']" in k]
    assert len(routers) == 3
    # a frozen gate's router gets EXACTLY nothing, in program and
    # reference alike; a trained one's gets something in every layer
    for g in routers:
        assert np.any(g) == gate_grad
    for k, v in jax.tree_util.tree_leaves_with_path(want):
        if "['router']" in jax.tree_util.keystr(k):
            assert bool(np.any(np.asarray(v))) == gate_grad
    assert not any(np.any(v) for k, v in named.items()
                   if "router_bias" in k)


def test_three_adamw_steps_match_the_reference():
    from mmlspark_tpu.parallel.mesh import mesh_from_config
    from mmlspark_tpu.parallel.trainer import DistributedTrainer
    seed, tokens = 11, _tokens(3, steps=3)
    want = ref.train_reference(CFG, seed, tokens, steps=3, optimizer=OPT)
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
        state, m = trainer.train_step(
            state, trainer.put_batch({"tokens": tokens[s]}),
            jax.random.PRNGKey(0))
        np.testing.assert_allclose(m["loss"], want["losses"][s], rtol=1e-5)
        np.testing.assert_allclose(m["loss.main"], want["main"][s],
                                   rtol=1e-5)
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
        if "['router']" in k:
            # no gradient: three steps of decay alone, lr x wd a step
            size = float(np.linalg.norm(start["params"][k.split("'")[3]][
                "ffn"]["router"]["kernel"]))
            np.testing.assert_allclose(float(v), size * (
                1 - (1 - OPT["learning_rate"] * OPT["weight_decay"]) ** 3),
                rtol=1e-3)
    # every routed slot of the uncut tiny model is held here
    assert float(m["moe.slots_here"]) == 3 * ROWS * LEN * 2
    assert float(m["moe.overflow_layers"]) == 0
    assert float(m["moe.rows_moved"]) == float(m["moe.slots_here"])
    assert want["mtp"] == [] and len(want["routing"]) == 3
    assert want["routing"][0]["choice"].shape == (ROWS * LEN, 2)
    assert want["routing"][0]["ranked"].shape == (ROWS * LEN, 8)


@pytest.mark.parametrize("kept", [True, False], ids=["kept", "let_go"])
def test_the_kept_projection_rows_change_no_gradient(monkeypatch, params,
                                                     kept):
    """``SHORT_CONV_IN`` names the ``[B | C | x]`` rows for
    ``_remat_block``'s one list: kept or made again, the gradients are
    those of blocks that recompute nothing, and with the name kept a
    recomputed block multiplies by ``W_in`` no second time."""
    import flax.linen as nn
    tokens = jnp.asarray(_tokens(6)[0])

    def grads():
        module = _module()
        return jax.grad(lambda p: jnp.sum(module.apply(
            p, tokens, hidden=True)["hidden"] ** 2))
    if not kept:
        real = decoder._remat_block
        monkeypatch.setattr(
            decoder, "_remat_block", lambda *a, **kw: real(
                *a, **dict(kw, let_go=(SHORT_CONV_IN,))))
    got = jax.jit(grads())(params)
    text = str(jax.make_jaxpr(grads())(params))
    # 3 conv layers: forward, backward's two products, and the
    # recomputation's only where the rows are let go
    wide = text.count("f32[2,16,96] = dot_general")
    assert wide == (3 if kept else 6), wide
    monkeypatch.setattr(nn, "remat", lambda cls, **kw: cls)
    _close(got, jax.jit(grads())(params), rtol=1e-5)


# ------------------------------------------------------ the routed layer
def _layer(held, first, experts=8, top_k=2, **kw):
    return DroplessMoe(32, experts, 16, top_k, experts_held=(held, first),
                       dtype=jnp.float32, weight_eps=1e-6, **kw)


def _share(p, first, count):
    ffn = dict(p["params"])
    for name in ("experts_gate", "experts_up", "experts_down"):
        ffn[name] = ffn[name][first:first + count]
    return {"params": ffn}


def test_eight_shares_of_eight_experts_add_up_to_the_uncut_reference():
    """The deployment's layout at tiny widths: 64 experts, four a token,
    eight chips with eight each and NO shared expert. The parts the
    shares compute sum to the uncut layer as the REFERENCE computes it
    (a dense loop over all 64), every slot computed exactly once."""
    whole = _layer(64, 0, experts=64, top_k=4)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    p = whole.init(jax.random.PRNGKey(3), x)
    assert "shared" not in p["params"]
    p["params"]["router"]["kernel"] = 8.0 * p["params"]["router"]["kernel"]
    p["params"]["router_bias"] = jnp.linspace(-0.02, 0.02, 64)
    d = dict(ref.dims(CFG), experts=64, held=64, first=0, top_k=4)
    want = jnp.stack([ref._experts(d, _mm, p["params"], x[b])[0]
                      for b in range(2)])
    total, slots = 0.0, 0
    for first in range(0, 64, 8):
        y, stats = _layer(8, first, experts=64, top_k=4).apply(
            _share(p, first, 8), x)
        total, slots = total + y, slots + int(stats["slots_here"])
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-6)
    assert slots == 2 * 16 * 4
    np.testing.assert_allclose(whole.apply(p, x)[0], want, rtol=1e-5,
                               atol=1e-6)
    # one share of the reference is that share of the program
    d8 = dict(d, held=8, first=16)
    part = jnp.stack([ref._experts(d8, _mm, _share(p, 16, 8)["params"],
                                   x[b])[0] for b in range(2)])
    np.testing.assert_allclose(
        _layer(8, 16, experts=64, top_k=4).apply(_share(p, 16, 8), x)[0],
        part, rtol=1e-5, atol=1e-6)


def test_a_frozen_gates_gradients_are_those_of_weights_fed_in_as_constants():
    """``gate_grad=False``: the forward pass is the trained gate's; the
    router's kernel gets exactly zero; the tokens' gradient is that of a
    layer whose gate weights come in from outside as constants."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    trained, frozen = _layer(4, 2), _layer(4, 2, gate_grad=False)
    p = trained.init(jax.random.PRNGKey(3), x)
    p["params"]["router"]["kernel"] = 8.0 * p["params"]["router"]["kernel"]
    assert np.array_equal(np.asarray(trained.apply(p, x)[0]),
                          np.asarray(frozen.apply(p, x)[0]))

    def loss(layer):
        return lambda p, x: jnp.sum(jnp.sin(layer.apply(p, x)[0]))
    gp, gx = jax.grad(loss(frozen), argnums=(0, 1))(p, x)
    tp, tx = jax.grad(loss(trained), argnums=(0, 1))(p, x)
    assert not np.any(np.asarray(gp["params"]["router"]["kernel"]))
    assert np.any(np.asarray(tp["params"]["router"]["kernel"]))
    assert not np.allclose(gx, tx, rtol=1e-3)

    # by hand: the weights from the scores, computed OUTSIDE the function
    # that is differentiated, then a dense loop over the four held experts
    w = p["params"]
    xf = x.reshape(32, 32)
    s = jax.nn.sigmoid(xf @ w["router"]["kernel"])
    _, choice = jax.lax.top_k(s + w["router_bias"], 2)
    gate = jnp.take_along_axis(s, choice, -1)
    gate = gate / (gate.sum(-1, keepdims=True) + 1e-6)
    weight = jnp.stack([jnp.sum(jnp.where(choice == 2 + e, gate, 0.0), -1)
                        for e in range(4)])         # constants from here

    def by_hand(banks, xf):
        y = 0.0
        for e in range(4):
            h = jax.nn.silu(xf @ banks[0][e]) * (xf @ banks[1][e])
            y = y + weight[e][:, None] * (h @ banks[2][e])
        return jnp.sum(jnp.sin(y))
    banks = (w["experts_gate"], w["experts_up"], w["experts_down"])
    hb, hx = jax.grad(by_hand, argnums=(0, 1))(banks, xf)
    np.testing.assert_allclose(gx.reshape(32, 32), hx, rtol=1e-4, atol=1e-7)
    for name, h in zip(("experts_gate", "experts_up", "experts_down"), hb):
        np.testing.assert_allclose(gp["params"][name], h, rtol=1e-4,
                                   atol=1e-7, err_msg=name)


def test_weight_eps_is_in_the_denominator():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 32))
    exact = DroplessMoe(32, 8, 16, 2, dtype=jnp.float32)
    p = exact.init(jax.random.PRNGKey(3), x)
    large = DroplessMoe(32, 8, 16, 2, dtype=jnp.float32, weight_eps=1.0)
    s = jax.nn.sigmoid(x.reshape(8, 32) @ p["params"]["router"]["kernel"])
    top = jax.lax.top_k(s, 2)[0].sum(-1)
    np.testing.assert_allclose(
        large.apply(p, x)[0].reshape(8, 32),
        exact.apply(p, x)[0].reshape(8, 32) * (top / (top + 1.0))[:, None],
        rtol=1e-5, atol=1e-7)


# -------------------------------------------- the benchmark's own counts
def _cell_config():
    with open(REPO / "benchmark" / "configs" / "lfm2-24b-a2b.json") as f:
        return json.load(f)


def _count(tree):
    return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple)))


def test_parameter_counts_of_the_published_defaults_at_the_cells_cut():
    """ISSUE 40's table, from ``jax.eval_shape`` of the zoo entry's own
    init and from the reference's shapes."""
    cfg = _cell_config()
    module = build_model(
        "lfm2_moe", vocab=cfg["vocab_size"],
        layer_types=cfg["layer_types"], dense_layers=1,
        experts_held=(8, 0))["module"]
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)))["params"]
    own = jax.tree_util.tree_map(lambda x: x.shape, shapes)
    assert own == ref.param_shapes(cfg)["params"]
    assert _count(own) == ref.parameters(cfg) == 469_285_248
    assert _count(own["block0"]) == 89_139_200
    assert _count(own["block0"]["attn"]) == 16_783_360
    assert _count(own["block0"]["attn"]["attn_in"]) == 12_582_912
    assert _count(own["block0"]["attn"]["conv_kernel"]) == 6_144
    assert _count(own["block0"]["ffn"]) == 72_351_744
    assert _count(own["block1"]) == 86_118_592
    assert _count(own["block1"]["attn"]) == 10_485_888
    assert _count(own["block1"]["ffn"]) == 75_628_608
    assert _count(own["block1"]["ffn"]["router"]) == 131_072
    assert _count(own["block1"]["ffn"]["router_bias"]) == 64
    for i in (2, 3, 4):
        assert _count(own[f"block{i}"]) == 92_416_064
    assert _count(own["token_embedding"]) == 16_777_216
    assert "469,285,248" in cfg["deployment"]["parameters_here"]


def test_required_flops_follow_the_counts():
    """ISSUE 40 counts 405.8 MFLOP a token forward: 178.3 (layer 0), 64.2
    (the attention layer at 8,192), 3 x 43.3 (conv layers under a routed
    part), 33.6 (the head's eighth); 9.97 TFLOP a row trained."""
    cfg = _cell_config()
    parts = ref._fwd_flops_per_token(cfg, 8192)
    assert parts["conv"] == 2 * (4 * 2048 * 2048 + 3 * 2048)
    assert parts["mlp"] == 2 * 3 * 2048 * 11776
    assert parts["attention"] == 2 * (2 * 2048 * 2048 + 2 * 2048 * 512) \
        + 2 * 4096 * 32 * 2 * 64
    assert parts["routed"] == 2 * 2048 * 64 + 0.5 * 2 * 3 * 2048 * 1536
    assert parts["head"] == 2 * 2048 * 8192
    assert 178.2e6 < parts["conv"] + parts["mlp"] < 178.4e6
    assert 64.1e6 < parts["attention"] + parts["routed"] < 64.3e6
    assert 405.7e6 < parts["total"] < 405.9e6
    assert parts["total"] == pytest.approx(
        4 * parts["conv"] + parts["attention"] + parts["mlp"]
        + 4 * parts["routed"] + parts["head"])
    assert 9.96e12 < ref.train_flops_per_item(cfg) < 9.98e12
    assert 0.32 < 4 * parts["conv"] / parts["total"] < 0.34
    call = ref.kernel_calls(cfg, 4, 8192, 65536.0)
    assert call == {
        "flash_fwd": {"rows": 4, "len": 8192, "heads": 32, "head_dim": 64},
        "expert_matmul": {"slots": 65536.0, "dim": 2048, "width": 1536,
                          "held": 8, "layers": 4},
        "short_conv": {"rows": 4, "len": 8192, "dim": 2048, "taps": 3,
                       "layers": 4}}
    flops, nbytes = ref.short_conv_cost(call["short_conv"])
    assert nbytes == 4 * 32768 * 15 * 2048 * 2 == 8_053_063_680
    assert flops == 4 * 32768 * 3 * 8 * 2048
    # bandwidth-bound on a v5e by a factor of hundreds
    assert (nbytes / 819e9) / (flops / 197e12) > 100
    # GLM's cost functions read the two inherited shapes as they are
    from benchmark.references import glm47_flash
    assert glm47_flash.expert_matmul_cost(call["expert_matmul"])[0] \
        == 18 * 65536 * 2048 * 1536
    assert glm47_flash.flash_fwd_cost(call["flash_fwd"])[0] \
        == 4 * 2 * 2 * 8192 * 8192 / 2 * 2048


def test_configuration_holds_the_catalogued_numbers():
    """Every number of the catalogue's row under its own key, but for the
    five reduced ones; no width among those."""
    cfg = _cell_config()
    published = dict(
        conv_L_cache=3, conv_bias=False, hidden_size=2048,
        intermediate_size=11776, layer_types=list(LFM2_24B_A2B_LAYERS),
        max_position_embeddings=128000, model_type="lfm2_moe",
        moe_intermediate_size=1536, norm_eps=1e-5, norm_topk_prob=True,
        num_attention_heads=32, num_dense_layers=2, num_experts=64,
        num_experts_per_tok=4, num_hidden_layers=40, num_key_value_heads=8,
        rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
        routed_scaling_factor=1, use_expert_bias=True, vocab_size=65536)
    assert len(LFM2_24B_A2B_LAYERS) == 40
    assert [i for i, k in enumerate(LFM2_24B_A2B_LAYERS)
            if k == "full_attention"] == list(range(2, 40, 4))
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == sorted(cfg["reduced"]) == [
        "layer_types", "num_dense_layers", "num_experts",
        "num_hidden_layers", "vocab_size"]
    assert set(cfg["reduced_from"]) == set(cfg["reduced"])
    dep = cfg["deployment"]
    assert dep["chips_sharing_each_layer"] == 8
    assert cfg["num_experts"] * 8 == dep["num_experts_published"] == 64
    assert cfg["vocab_size"] * 8 == dep["vocab_size_published"] == 65536
    assert dep["experts_first"] == 0
    # one leading dense layer, then published layers 2-5: a whole period
    assert cfg["layer_types"] == [LFM2_24B_A2B_LAYERS[0]] + list(
        LFM2_24B_A2B_LAYERS[2:6])
    assert (cfg["runner"], cfg["reference"]) == ("train_lm_dense",
                                                 "lfm2_moe")
    assert cfg["program"] == {"zoo": "lfm2_moe", "loss_chunk": 2048,
                              "zoo_args": {"gate_grad": False}}
    assert {"tied_table", "attention_head_dim", "qk_norm", "rotary_pairing",
            "in_proj_column_order", "weight_eps", "router_bias",
            "gate_grad", "init", "optimizer", "compute_dtype", "packing",
            "recomputation", "fit"} <= set(cfg["assumed"])
    assert "no gradient" in cfg["assumed"]["gate_grad"].lower()
    # the zoo entry's defaults are the published numbers
    whole, entry = build_model("lfm2_moe")["module"], _defaults("lfm2_moe")
    uncut = dict(cfg, **{k: published[k] for k in cfg["reduced"]},
                 program={"zoo": "lfm2_moe"})
    args = ref.zoo_args(uncut, 8192)
    args.pop("max_len")
    routed = whole.ffns[-1](None)           # the routed layer as built
    assert args.pop("experts_held") == (64, 0) \
        and entry["experts_held"] is None and routed.experts_held is None
    for k, v in args.items():
        assert entry[k] == v, k
    assert entry["gate_grad"] is True and routed.gate_grad is True
    assert entry["head_dim"] * entry["heads"] == entry["dim"] == whole.dim
    # the mixer AND the feed-forward part by the layer's index
    assert [i for i in range(40) if whole.mixers[i] is whole.mixers[2]] \
        == list(range(2, 40, 4))
    assert [i for i in range(40) if whole.ffns[i] is whole.ffns[0]] == [0, 1]
    assert whole.tied and not whole.split and whole.mtp is None
    # the cell, its traffic and its three metrics
    with open(REPO / "BENCHMARK.json") as f:
        bench = json.load(f)
    cell = [w for w in bench["workloads"]
            if w["name"] == "lfm2-24b-a2b-train-ep8share-8k"]
    assert cell and cell[0]["config"] == "lfm2-24b-a2b" \
        and cell[0]["traffic"] == "train-lm-8k-x4" and cell[0]["chips"] == 1
    for name in ("shortconv.layer_ms", "shortconv.gate_conv_ms",
                 "shortconv.gate_conv_roofline"):
        entry = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry and entry[0]["workloads"] == [cell[0]["name"]], name
        assert (REPO / "benchmark" / "metrics" / f"{name}.json").exists()


def test_parameter_names_fall_under_the_sharding_rules_that_exist(params):
    from jax.sharding import PartitionSpec as P
    from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh
    from mmlspark_tpu.parallel.sharding import param_shardings
    mesh = make_mesh(MeshSpec(data=1, expert=4, tensor=2), jax.devices())
    spec = {jax.tree_util.keystr(k): v.spec for k, v in
            jax.tree_util.tree_leaves_with_path(
                param_shardings(params, mesh))}
    whole = lambda spec: all(axis is None for axis in spec)
    mixer = "['params']['block0']['attn']"
    assert spec[mixer + "['attn_out']['kernel']"] == P("tensor", None)
    assert whole(spec[mixer + "['conv_kernel']"])
    soft = "['params']['block1']['attn']"
    for name in ("attn_query", "attn_key", "attn_value"):
        assert spec[soft + f"['{name}']['kernel']"] == P(None, "tensor"), name
    assert spec[soft + "['attn_out']['kernel']"] == P("tensor", None)
    for name in ("query_norm", "key_norm"):
        assert whole(spec[soft + f"['{name}']['scale']"]), name
    ffn = "['params']['block1']['ffn']"
    for name in ("experts_gate", "experts_up", "experts_down"):
        assert spec[ffn + f"['{name}']"][0] == "expert", name
    assert spec["['params']['block0']['ffn']['mlp_down']['kernel']"] \
        == P("tensor", None)
