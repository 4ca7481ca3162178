"""``laguna`` at a toy size against the plain reference
(``benchmark/references/laguna.py``), and what it brought: a softmax part
whose query heads, window, rotary rule and head gate are arguments, two
settings of it in one model by a published list, YaRN's frequencies, a
routed layer of many small experts beside a shared one, and the flash
kernels taught a window. float32 on the CPU.

Tolerances: both sides compute in float32 on one backend, so they differ
only by the order of additions (grouped products and a chunked loss against
dense loops and whole logits): 1e-5 relative on logits and losses, 1e-4 on
gradients, 2e-3 on the norm of three Adam steps (``g / (sqrt(v) + eps)``
amplifies a relative gradient error where ``g`` is near zero); the kernels
in interpret mode against the masked dense product 2e-5 absolute on unit
normal inputs. What has to be exact is exact: the band's edge, a frozen
gate's zero gradient, ``window=None`` against today's call.
"""
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark.references import laguna as ref  # noqa: E402
from mmlspark_tpu.models.zoo import build_model, decoder  # noqa: E402
from mmlspark_tpu.models.zoo.moe import DroplessMoe  # noqa: E402
from mmlspark_tpu.models.zoo.parts import (  # noqa: E402
    ATTN_QKV, GroupedAttention, SwiGluMlp, plain_frequencies, rotary,
    yarn_frequencies)
from mmlspark_tpu.observability import metrics as obsmetrics  # noqa: E402
from mmlspark_tpu.ops import pallas_attention as pa  # noqa: E402
from mmlspark_tpu.parallel import sequence  # noqa: E402
from mmlspark_tpu.train.lm_loss import next_token_loss  # noqa: E402

KINDS = ["full_attention", "sliding_attention", "sliding_attention",
         "full_attention"]
YARN = {"rope_theta": 5e5, "rope_type": "yarn", "factor": 4,
        "original_max_position_embeddings": 8, "beta_slow": 1,
        "beta_fast": 4, "attention_factor": 1.1386,
        "partial_rotary_factor": 0.5}
CFG = dict(hidden_size=32, num_hidden_layers=4, layer_types=KINDS,
           mlp_layer_types=["dense", "sparse", "sparse", "sparse"],
           num_attention_heads_per_layer=[2, 4, 4, 2],
           num_key_value_heads=2, head_dim=8, sliding_window=6,
           rope_parameters={
               "full_attention": YARN,
               "sliding_attention": {"rope_type": "default",
                                     "rope_theta": 1e4,
                                     "partial_rotary_factor": 1}},
           gating=True, tie_word_embeddings=False, attention_bias=False,
           moe_apply_router_weight_on_input=False, intermediate_size=48,
           moe_intermediate_size=8, shared_expert_intermediate_size=8,
           num_experts=8, num_experts_per_tok=2,
           moe_routed_scaling_factor=2.5, rms_norm_eps=1e-6, vocab_size=96,
           program={"zoo_args": {"dtype": jnp.float32, "gate_grad": False}},
           deployment={"num_experts_published": 8, "experts_first": 0})
OPT = dict(learning_rate=1e-2, beta1=0.9, beta2=0.95, eps=1e-8,
           weight_decay=0.1)
ROWS, LEN = 2, 16       # rows longer than the window AND than YaRN's 8


def _cfg(gate_grad=False, **changes):
    program = {"zoo_args": {"dtype": jnp.float32, "gate_grad": gate_grad}}
    return dict(CFG, program=program, **changes)


def _tokens(seed, steps=1):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], size=(steps, ROWS, LEN)).astype(np.int32)


def _module(cfg=CFG):
    return build_model("laguna", **ref.zoo_args(cfg, 64))["module"]


def _loss_fn(module, chunk=8):
    def loss_fn(params, batch, rng):
        out = module.apply(params, batch["tokens"], hidden=True)
        loss, aux = next_token_loss(
            out, params["params"]["lm_head"]["kernel"], batch["tokens"],
            chunk=chunk, dtype=jnp.float32)
        return loss, {**aux, **out["stats"]}
    return loss_fn


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b)


def _away(path, v):
    """Scales off 1, a router bias off 0 and head gates off one half, so
    that none is a factor a wrong wiring could hide behind."""
    name = jax.tree_util.keystr(path)
    if "scale" in name:
        return v + jnp.linspace(-0.5, 0.5, v.size).reshape(v.shape)
    if "router_bias" in name:
        return v + jnp.linspace(-0.02, 0.02, v.size)
    if "router']['kernel" in name or "attn_head_gate" in name:
        return 8.0 * v          # scores and gates that differ
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


# --------------------------------------------------------------- positions
def test_yarn_frequencies_keep_the_fast_pairs_and_slow_the_slow_ones():
    """Published numbers (64 turned dimensions, theta 500,000, factor 64,
    original 4,096, beta 64 / 1): the first pair turns as plain rotary,
    the last 64 times slower, the ramp lies between pairs 5 and 16 (the
    floor and ceiling of 5.66 and 15.8), and the reference's own form of
    the rule agrees to float64."""
    got = np.asarray(yarn_frequencies(64, 5e5, 64.0, 4096, 64.0, 1.0))
    plain = 5e5 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:6], plain[:6], rtol=1e-12)
    np.testing.assert_allclose(got[16:], plain[16:] / 64, rtol=1e-12)
    assert np.all(got[6:16] < plain[6:16]) \
        and np.all(got[6:16] > plain[6:16] / 64)
    want = ref.yarn_inv_freq(64, 5e5, {
        "factor": 64.0, "original_max_position_embeddings": 4096.0,
        "beta_fast": 64.0, "beta_slow": 1.0})
    np.testing.assert_allclose(got, want, rtol=1e-12)
    # the toy rule bends inside its four pairs too
    toy = np.asarray(yarn_frequencies(4, 5e5, 4.0, 8, 4.0, 1.0))
    assert toy[0] == 1.0 and 0 < toy[1] < 5e5 ** -0.5


def _split_form(x, inv_freq, factor=1.0):
    """The turn as the published models write it, in NumPy: the two halves
    of the first ``2 n`` dimensions sliced out, ``[x_1 cos - x_2 sin | x_2
    cos + x_1 sin]``, the rest put back behind them; float32, then the
    input's dtype. (cos and sin from jax: NumPy's differ in a last bit.)"""
    n = len(inv_freq)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos, sin = (np.asarray(f(ang) * factor)[None, :, None, :]
                for f in (jnp.cos, jnp.sin))
    x32 = np.asarray(x.astype(jnp.float32))
    x1, x2 = x32[..., :n], x32[..., n:2 * n]
    return jnp.asarray(np.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x32[..., 2 * n:]],
        -1)).astype(x.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rotary_turns_the_pairs_it_is_given(dtype):
    """The first ``2 n`` dimensions turn by the given frequencies, pair
    ``(i, i + n)``, cos and sin times the factor; the rest pass; it is the
    split form to the bit (the product with the pairing's matrix moves
    values, it rounds none)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 2, 8), dtype)
    freqs = (1.0, 0.25)
    got = rotary(x, freqs, 1.5)
    assert got.dtype == dtype
    assert np.array_equal(np.asarray(got[..., 4:]), np.asarray(x[..., 4:]))
    x32 = np.asarray(x.astype(jnp.float32))
    ang = np.arange(12)[:, None] * np.asarray(freqs)
    x1, x2 = x32[..., :2], x32[..., 2:4]
    cos, sin = (1.5 * f(ang)[None, :, None, :] for f in (np.cos, np.sin))
    tol = dict(rtol=1e-5, atol=1e-6) if dtype == jnp.float32 \
        else dict(rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(got[..., :2].astype(jnp.float32),
                               x1 * cos - x2 * sin, **tol)
    np.testing.assert_allclose(got[..., 2:4].astype(jnp.float32),
                               x2 * cos + x1 * sin, **tol)
    assert np.array_equal(np.asarray(got),
                          np.asarray(_split_form(x, freqs, 1.5)))
    whole = plain_frequencies(8, 1e4)
    assert np.array_equal(np.asarray(rotary(x, whole)),
                          np.asarray(_split_form(x, whole)))
    # and it differentiates as the split form does
    g = jax.grad(lambda x: (rotary(x, freqs, 1.5).astype(
        jnp.float32) ** 2).sum())(x)
    assert g.shape == x.shape and bool(jnp.isfinite(
        g.astype(jnp.float32)).all())
    with pytest.raises(ValueError, match="frequencies"):
        rotary(x, (1.0,) * 5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape,width,theta", [
    ((2, 16, 4, 64), 64, 1e6),      # lfm2: the whole head
    ((2, 16, 3, 256), 64, 1e7),     # qwen: the first 64 of 256
    ((2, 16, 1, 64), 64, 1e6),      # GLM: the one shared 64-wide key
], ids=["whole-head", "leading-slice", "one-shared-key"])
def test_the_one_turn_is_the_split_form_at_every_familys_shape(
        shape, width, theta, dtype):
    """Every family that turns does so through ``rotary``; at each one's
    head width, turned width and ``theta`` its values are the split form's
    BIT FOR BIT in float32 and in bfloat16, and its gradient (the turn by
    the opposite angle, of the cotangent) to a few units in the last place:
    the backward of a product sums in another order than a slice's, and a
    bfloat16 cotangent's two terms are rounded before they are added."""
    freqs = plain_frequencies(width, theta)
    x = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32
                          ).astype(dtype)
    got = rotary(x, freqs)
    assert got.dtype == dtype and got.shape == shape
    # (op by op: under ``jit`` the CPU backend contracts a multiply and an
    # add into one rounding, which NumPy does not)
    assert np.array_equal(np.asarray(got), np.asarray(_split_form(x, freqs)))
    w = jax.random.normal(jax.random.PRNGKey(2), shape, jnp.float32)
    g = jax.grad(lambda x: (rotary(x, freqs).astype(jnp.float32) * w).sum()
                 )(x)
    assert g.dtype == dtype
    want = _split_form(w, tuple(-f for f in freqs)).astype(dtype)
    ulp = float(jnp.finfo(dtype).eps)
    np.testing.assert_allclose(
        np.asarray(g.astype(jnp.float32)),
        np.asarray(want.astype(jnp.float32)), rtol=4 * ulp, atol=4 * ulp)


# ------------------------------------------------------- the softmax part
def _part(kind, heads, window=6):
    d = ref.dims(CFG)
    if kind == "sliding_attention":
        kw = dict(rotary_freqs=plain_frequencies(8, 1e4), window=window)
    else:
        kw = dict(rotary_factor=YARN["attention_factor"],
                  rotary_freqs=yarn_frequencies(4, 5e5, 4.0, 8, 4.0, 1.0))
    return d, GroupedAttention(32, heads, 2, 8, None, jnp.float32, None,
                               head_gate=True, **kw)


@pytest.mark.parametrize("block,kind,heads", [
    (0, "full_attention", 2), (1, "sliding_attention", 4)])
def test_each_setting_of_the_part_is_the_references_layer(params, block,
                                                          kind, heads):
    """Unequal head counts over two key/value heads, a window shorter
    than the row, YaRN past its original length, the gate a head."""
    d, layer = _part(kind, heads)
    p = params["params"][f"block{block}"]["attn"]
    assert p["attn_head_gate"]["kernel"].shape == (32, heads)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, LEN, 32))
    got = layer.apply({"params": p}, x)
    want = jax.jit(lambda band, x: ref._attention(d, _mm, kind, band, p, x),
                   static_argnums=0)
    for b in range(2):
        np.testing.assert_allclose(got[b], want(True, x[b]), rtol=1e-5,
                                   atol=1e-6)
        # the reference's control of the band: a sliding layer without it
        # is another layer, a full layer the same
        dropped = want(False, x[b])
        assert np.allclose(got[b], dropped, atol=1e-4) \
            == (kind == "full_attention")
    assert set(layer.init(jax.random.PRNGKey(0), x)["params"]) == set(p)


def test_the_bands_edge_a_key_at_window_minus_one_is_seen_and_no_further(
        params):
    """A query at ``i`` sees the key at ``i - (W - 1)`` and not the one at
    ``i - W``: moving the first moves the output, moving the second (or
    any later token) does not, and with the window off it does."""
    _, layer = _part("sliding_attention", 4)
    p = {"params": params["params"]["block1"]["attn"]}
    x = jax.random.normal(jax.random.PRNGKey(5), (1, LEN, 32))
    got, i = layer.apply(p, x), 12
    seen = layer.apply(p, x.at[0, i - 5].add(1.0))
    unseen = layer.apply(p, x.at[0, i - 6].add(1.0))
    assert not np.allclose(seen[0, i], got[0, i], atol=1e-4)
    assert np.array_equal(np.asarray(unseen[0, i]), np.asarray(got[0, i]))
    assert np.array_equal(
        np.asarray(layer.apply(p, x.at[0, i + 1].add(1.0))[0, i]),
        np.asarray(got[0, i]))
    _, whole = _part("sliding_attention", 4, window=None)
    far = whole.apply(p, x.at[0, i - 6].add(1.0))
    assert not np.allclose(far[0, i], whole.apply(p, x)[0, i], atol=1e-4)


def test_the_head_gate_closes_a_head_at_large_negative_and_opens_at_large(
        params):
    _, layer = _part("full_attention", 2)
    p = jax.tree_util.tree_map(jnp.asarray,
                               dict(params["params"]["block0"]["attn"]))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (1, LEN, 32))) + 0.1
    _, plain = _part("full_attention", 2)
    plain = plain.clone(head_gate=False)
    ungated = plain.apply({"params": {k: v for k, v in p.items()
                                      if k != "attn_head_gate"}}, x)
    # W_g = 0: every gate one half
    p["attn_head_gate"] = {"kernel": jnp.zeros((32, 2))}
    np.testing.assert_allclose(layer.apply({"params": p}, x), 0.5 * ungated,
                               rtol=1e-5, atol=1e-6)
    # x > 0, so a large W_g opens every gate and a large negative shuts it
    p["attn_head_gate"] = {"kernel": jnp.full((32, 2), 50.0)}
    np.testing.assert_allclose(layer.apply({"params": p}, x), ungated,
                               rtol=1e-5, atol=1e-6)
    p["attn_head_gate"] = {"kernel": jnp.full((32, 2), -50.0)}
    assert float(jnp.abs(layer.apply({"params": p}, x)).max()) < 1e-12
    # one head shut: the other head's part alone, through its rows of W_o
    p["attn_head_gate"] = {"kernel": jnp.stack(
        [jnp.full((32,), 50.0), jnp.full((32,), -50.0)], 1)}
    one = dict(p, attn_out={"kernel": p["attn_out"]["kernel"].at[8:].set(0)})
    del one["attn_head_gate"]
    np.testing.assert_allclose(
        layer.apply({"params": p}, x), plain.apply({"params": one}, x),
        rtol=1e-5, atol=1e-6)


# ----------------------------------------- the model and its reference
def test_reference_tree_is_the_programs_tree_and_layer_kinds(params):
    own = jax.eval_shape(_module().init, jax.random.PRNGKey(0),
                         jnp.zeros((1, LEN), jnp.int32))
    shapes = lambda t: jax.tree_util.tree_map(lambda x: x.shape, t)  # noqa
    assert shapes(own) == shapes(params)
    blocks = own["params"]
    assert "lm_head" in blocks              # untied tables
    for i, heads in enumerate(CFG["num_attention_heads_per_layer"]):
        mixer, ffn = blocks[f"block{i}"]["attn"], blocks[f"block{i}"]["ffn"]
        assert mixer["attn_query"]["kernel"].shape == (32, heads * 8)
        assert mixer["attn_key"]["kernel"].shape == (32, 2 * 8)
        assert "query_norm" not in mixer
        assert ("mlp_gate" in ffn) == (i == 0)
        assert ("router" in ffn) == ("shared" in ffn) == (i > 0)
    assert ref.routed_blocks(CFG) == ["block1", "block2", "block3"]
    assert ref.parameters(CFG) == sum(
        x.size for x in jax.tree_util.tree_leaves(own))


def test_the_published_configuration_counts_its_parameters():
    """The benchmark's file: every published width, 5 of 40 layers, 32 of
    256 experts held, an eighth of the tables; the count the issue's table
    gives, from the reference's shapes and from the program's own tree."""
    import json
    with open(Path(__file__).resolve().parent.parent / "benchmark"
              / "configs" / "laguna-xs.2.json") as f:
        cfg = json.load(f)
    assert ref.parameters(cfg) == 691_624_960
    module = build_model("laguna", **ref.zoo_args(cfg, 8192))["module"]
    tree = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 512), jnp.int32))
    shapes = jax.tree_util.tree_map(lambda x: tuple(x.shape), tree)
    assert shapes == ref.param_shapes(cfg)
    calls = ref.kernel_calls(cfg, 2, 8192, 1000.0)
    assert calls["flash_fwd"]["heads"] == 48
    assert calls["window_fwd"] == {"rows": 2, "len": 8192, "heads": 64,
                                   "head_dim": 128, "window": 512}
    parts = ref._fwd_flops_per_token(cfg, 8192)
    # the band and not the causal half: 49 and not 403 MFLOP a token
    assert 48e6 < parts["band"] < 50e6 and 200e6 < parts["full"] < 203e6
    assert 800e6 < parts["total"] < 804e6


def test_lists_of_unequal_length_or_another_kind_raise():
    with pytest.raises(ValueError, match="head counts"):
        build_model("laguna_tiny", heads_per_layer=(2, 4))
    with pytest.raises(ValueError, match="layer_types"):
        build_model("laguna_tiny", layer_types=(
            "full_attention", "linear_attention", "sliding_attention",
            "full_attention"))
    with pytest.raises(ValueError, match="layer_types"):
        ref.dims(dict(CFG, layer_types=KINDS[:3]))


def test_logits_match_the_reference(params):
    tokens = _tokens(1)[0]
    got = jax.jit(_module().apply)(params, jnp.asarray(tokens))
    assert got.shape == (ROWS, LEN, CFG["vocab_size"])
    assert got.dtype == jnp.float32
    logits = jax.jit(lambda p, t: ref.logits(CFG, p, t))
    for b in range(ROWS):
        want = logits(params, jnp.asarray(tokens[b]))
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


def test_losses_and_gradients_match_the_reference(params, gate_grad=False):
    """The cell's setting, a frozen gate (``lfm2_moe``'s tests hold the
    argument's two values against each other)."""
    cfg = _cfg(gate_grad)
    tokens = _tokens(2)[0]
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        _loss_fn(_module(cfg)), has_aux=True))(
            params, {"tokens": jnp.asarray(tokens)}, None)
    want_loss, want = _ref_loss_and_grads(cfg, params, tokens)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    np.testing.assert_allclose(aux["loss.main"], want_loss, rtol=1e-5)
    _close(grads, want)
    named = {jax.tree_util.keystr(k): np.asarray(v) for k, v in
             jax.tree_util.tree_leaves_with_path(grads)}
    routers = [v for k, v in named.items() if "['router']" in k]
    assert len(routers) == 3
    for g in routers:       # a frozen gate's router gets EXACTLY nothing
        assert np.any(g) == gate_grad
    assert not any(np.any(v) for k, v in named.items()
                   if "router_bias" in k)
    # every head gate, of both kinds of layer, gets a gradient
    gates = [v for k, v in named.items() if "attn_head_gate" in k]
    assert len(gates) == 4 and all(np.any(g) for g in gates)


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
    # every routed slot of the uncut toy model is held here
    assert float(m["moe.slots_here"]) == 3 * ROWS * LEN * 2
    assert float(m["moe.overflow_layers"]) == 0
    assert want["mtp"] == [] and len(want["routing"]) == 3
    assert want["routing"][0]["choice"].shape == (ROWS * LEN, 2)
    assert want["routing"][0]["ranked"].shape == (ROWS * LEN, 8)


# ------------------------------------------------------ the routed layer
def _layer(held, first, experts=16, top_k=4, **kw):
    return DroplessMoe(
        32, experts, 8, top_k, experts_held=(held, first), scaling=2.5,
        shared=lambda m: SwiGluMlp(32, 8, jnp.float32, name=m),
        dtype=jnp.float32, **kw)


def _share(p, first, count):
    ffn = dict(p["params"])
    for name in ("experts_gate", "experts_up", "experts_down"):
        ffn[name] = ffn[name][first:first + count]
    return {"params": ffn}


def test_four_shares_add_up_to_the_uncut_reference_the_shared_expert_once():
    """The deployment's layout at toy widths: 16 small experts, four a
    token, four chips with four each, one shared expert that every chip
    computes alike. The shares' partial results, the shared expert counted
    ONCE, sum to the uncut layer as the REFERENCE computes it (a dense
    loop over all 16), every slot computed exactly once."""
    whole = _layer(16, 0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    p = whole.init(jax.random.PRNGKey(3), x)
    p["params"]["router"]["kernel"] = 8.0 * p["params"]["router"]["kernel"]
    p["params"]["router_bias"] = jnp.linspace(-0.02, 0.02, 16)
    d = dict(ref.dims(CFG), experts=16, held=16, first=0, top_k=4)
    want = jax.vmap(lambda row: ref._experts(d, _mm, p["params"], row)[0])(x)
    shared = SwiGluMlp(32, 8, jnp.float32).apply(
        {"params": p["params"]["shared"]}, x)
    total, slots = 0.0, 0
    for first in range(0, 16, 4):
        y, stats = _layer(4, first).apply(_share(p, first, 4), x)
        total, slots = total + (y - shared), slots + int(stats["slots_here"])
    np.testing.assert_allclose(total + shared, want, rtol=1e-5, atol=1e-6)
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


def test_the_routing_weights_are_scaled_scores_over_their_sum():
    """DeepSeek-V3's rule as the configuration's ``assumed`` states it:
    the choice on score plus bias, the weights the chosen SCORES over
    their sum times 2.5, on the experts' output."""
    layer = _layer(16, 0)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 8, 32))
    p = layer.init(jax.random.PRNGKey(3), x)
    # a bias that decides the choice and is in no weight
    p["params"]["router_bias"] = jnp.where(jnp.arange(16) < 4, 10.0, 0.0)
    _, state = layer.apply(p, x, mutable=["intermediates"])
    choice = np.asarray(state["intermediates"]["router_choice"][0])
    assert set(choice.ravel()) == {0, 1, 2, 3}
    d = dict(ref.dims(CFG), experts=16, held=16, first=0, top_k=4)
    np.testing.assert_allclose(
        layer.apply(p, x)[0][0], ref._experts(d, _mm, p["params"], x[0])[0],
        rtol=1e-5, atol=1e-6)


# -------------------------------------------------- the windowed kernels
L_K, H_K, D_K = 512, 2, 32


def _qkvd(seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return tuple(jax.random.normal(k, (1, L_K, H_K, D_K), dtype)
                 for k in ks)


@pytest.mark.parametrize("window", [1, 5, 100, 128, 129, 300],
                         ids=lambda w: f"W{w}")
def test_windowed_flash_matches_the_masked_dense_product(window):
    """Forward and backward in interpret mode, tiles of 128: a window
    below, at and above a tile, and one wide enough that a tile lies clear
    between the band's lower edge and the diagonal."""
    q, k, v, do = _qkvd()
    got, pull = jax.vjp(lambda q, k, v: pa.flash_attention(
        q, k, v, True, 128, 128, window), q, k, v)
    want, pull_ref = jax.vjp(lambda q, k, v: sequence._reference_attention(
        q, k, v, True, window), q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-5)
    for g, w in zip(pull(do), pull_ref(do)):
        np.testing.assert_allclose(g, w, atol=2e-5)


def test_a_window_of_the_whole_row_is_the_plain_causal_call():
    q, k, v, do = _qkvd(1)
    plain, pull = jax.vjp(lambda q, k, v: pa.flash_attention(
        q, k, v, True, 128, 128), q, k, v)
    for window in (L_K, L_K + 77):
        got, pull_w = jax.vjp(lambda q, k, v: pa.flash_attention(
            q, k, v, True, 128, 128, window), q, k, v)
        assert np.array_equal(np.asarray(got), np.asarray(plain))
        for g, w in zip(pull_w(do), pull(do)):
            assert np.array_equal(np.asarray(g), np.asarray(w))


def test_window_none_lowers_to_the_call_without_the_argument():
    """``window=None`` is today's program: the same text, forward and
    backward, whether the argument is given or left out; a band's text
    differs and carries the band's names and no ``flash``-free causal
    one."""
    q, k, v, do = (t[:, :256].astype(jnp.bfloat16) for t in _qkvd(2))

    def text(*extra, names=False):
        def f(q, k, v, do):
            out, pull = jax.vjp(lambda q, k, v: pa.flash_attention(
                q, k, v, True, 128, 128, *extra), q, k, v)
            return out, pull(do)
        return jax.jit(f).lower(q, k, v, do).as_text(debug_info=names)
    assert text() == text(None)
    assert text(64) != text()
    plain, band = text(names=True), text(64, names=True)
    assert "window_attention_fwd" in band and "window_attention_bwd" in band
    assert "window_attention" not in plain
    assert "long_attention_bwd" in plain and "long_attention_bwd" not in band


def test_window_needs_causal_and_a_positive_width():
    q, k, v, _ = _qkvd()
    with pytest.raises(ValueError, match="causal band"):
        pa.flash_attention(q, k, v, False, 128, 128, 64)
    with pytest.raises(ValueError, match="causal band"):
        sequence.full_attention(q, k, v, causal=True, window=0)


def test_a_bands_tiles_stay_within_the_window():
    assert pa._fwd_tiles(256, 256, 8192, 128, 512) == (512, 512)
    assert pa._fwd_tiles(256, 256, 8192, 128, 4096) == (512, 512)
    assert pa._fwd_tiles(128, 128, 8192, 128, 300) == (256, 256)
    assert pa._fwd_tiles(128, 128, 8192, 128, 100) == (128, 128)
    assert pa._window_tile(256, 8192, 512) == 512
    # the causal calls' tiles are what they were
    assert pa._fwd_tiles(256, 256, 8192, 128) == (1024, 512)
    assert pa._bwd_tile(256, 8192) == 512


def test_full_attention_hands_the_window_through_and_counts_it():
    q, k, v, _ = _qkvd(3)
    names = ("attention.fused_calls.window", "attention.fused_calls.flash",
             "attention.fused_calls.reference", "attention.flash_fallbacks")
    before = {n: obsmetrics.counter(n).value for n in names}

    def since():
        return {n.rsplit(".", 1)[1]: obsmetrics.counter(n).value - before[n]
                for n in names}
    want = sequence._reference_attention(q, k, v, True, 100)
    got = sequence.full_attention(q, k, v, True, "require", window=100)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert since() == {"window": 1, "flash": 0, "reference": 0,
                       "flash_fallbacks": 0}
    # on the CPU "auto" runs the masked reference path, the band in it
    np.testing.assert_allclose(
        sequence.full_attention(q, k, v, True, window=100), want, atol=1e-6)
    assert since()["reference"] == 1
    # a window of the whole row is the causal call, counted as one
    sequence.full_attention(q, k, v, True, "require", window=L_K)
    assert since()["flash"] == 1 and since()["window"] == 1
    # a windowed shape no kernel takes (the short kernel knows no band) is
    # refused under "require", never passed silently to another kernel
    short = tuple(t[:, :64] for t in (q, k, v))
    with pytest.raises(ValueError, match="no fused kernel"):
        sequence.full_attention(*short, True, "require", window=16)
    assert sequence.full_attention(*short, True, "require").shape \
        == short[0].shape


def test_a_window_the_kernel_cannot_take_counts_as_a_fallback(monkeypatch):
    """On an accelerator under "auto": the masked reference path runs and
    ``attention.flash_fallbacks`` says so."""
    monkeypatch.setattr(sequence, "_on_chip", lambda: True)
    q = jnp.ones((1, 64, 2, 32))
    before = obsmetrics.counter("attention.flash_fallbacks").value
    got = sequence.full_attention(q, q, q, True, window=16)
    assert got.shape == q.shape
    assert obsmetrics.counter("attention.flash_fallbacks").value \
        == before + 1


def test_the_sliding_layers_name_their_scopes_and_residuals():
    """``window_attention_layer`` around a sliding layer's whole mixer,
    ``head_gate`` around the gate, both under ``grouped_attention`` (a
    scope the benchmark's split counts as attention); a band's residuals
    carry the causal call's checkpoint name, so ``_remat_block``'s one
    list keeps them and a recomputed block holds no second forward call."""
    module = build_model("laguna_tiny")["module"]
    tokens = jnp.zeros((1, 16), jnp.int32)
    p = jax.jit(module.init)(jax.random.PRNGKey(0), tokens)
    text = jax.jit(lambda p: module.apply(p, tokens)).lower(p).as_text(
        debug_info=True)
    assert "grouped_attention/window_attention_layer/head_gate" in text
    assert "grouped_attention/head_gate" in text
    q, k, v, _ = _qkvd(4)
    policy = jax.checkpoint_policies.save_only_these_names(
        pa.FLASH_RESIDUALS)
    jaxpr = str(jax.make_jaxpr(jax.grad(jax.checkpoint(
        lambda q: pa.flash_attention(q, k, v, True, 128, 128, 64).sum(),
        policy=policy)))(q))
    assert pa.FLASH_RESIDUALS in jaxpr
    assert jaxpr.count(pa._WINDOW_FWD_NAME) == 1
    assert jaxpr.count(pa._WINDOW_BWD_NAME) == 1


@pytest.mark.parametrize("kept", [True, False], ids=["kept", "let_go"])
def test_the_kept_q_k_v_rows_change_no_gradient(monkeypatch, params, kept):
    """``ATTN_QKV`` names the q, k and v projections' outputs (before the
    turn, the scale and the repeat of K and V) for ``_remat_block``'s one
    list: kept or made again, the gradients are those of blocks that
    recompute nothing, and with the name kept a recomputed block
    multiplies by ``W_q``, ``W_k`` and ``W_v`` no second time."""
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
                *a, **dict(kw, let_go=(ATTN_QKV,))))
    got = jax.jit(grads())(params)
    text = str(jax.make_jaxpr(grads())(params))
    # rows times a (32, 16) weight: k and v of all four layers and q of
    # the two full ones (2 heads of 8), ten products a pass; the backward's
    # contract over the weight's columns or over the tokens
    narrow = len(re.findall(
        r"f32\[2,16,16\] = dot_general\[\s*dimension_numbers="
        r"\(\(\[2\], \[0\]\)", text))
    assert narrow == (10 if kept else 20), narrow
    monkeypatch.setattr(nn, "remat", lambda cls, **kw: cls)
    _close(got, jax.jit(grads())(params), rtol=1e-5)
