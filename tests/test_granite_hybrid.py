"""``granite_hybrid`` at its tiny preset against the plain reference
(``benchmark/references/granite_hybrid.py``), and the parts it brought:
Mamba-2's state-space rule in its two forms, the convolution's bias, the
gate before the norm, grouped attention with nothing else at a softmax
scale of its own, a block whose residual additions are scaled, one table
for the embedding and the head. float32 on the CPU.

Tolerances: both sides compute in float32 on one backend, so they differ
only by the order of additions (chunked products against a token-by-token
scan; a chunked loss against whole logits): 1e-5 relative on logits and
losses, 1e-4 on gradients, 2e-3 on the norm of three Adam steps (``g /
(sqrt(v) + eps)`` amplifies a relative gradient error where ``g`` is near
zero; 3e-2 on ``A_log``, ``dt_bias`` and ``D_skip``, a few numbers a layer
whose gradients may lie near ``eps``). The rule alone: 2e-5 of the largest
element, values and gradients, while the running sum of ``dt A`` inside a
chunk stays under a few hundred; 1e-3 on the gradients where it reaches
10^4 (float32 holds such a sum to 1e-3, and the decays are exponentials of
its differences).
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

from benchmark.references import granite_hybrid as ref  # noqa: E402
from mmlspark_tpu.models.zoo import build_model, decoder  # noqa: E402
from mmlspark_tpu.models.zoo.decoder import (  # noqa: E402
    GRANITE_4_H_MICRO_LAYERS, PartsBlock)
from mmlspark_tpu.models.zoo.parts import (  # noqa: E402
    ATTN_QKV, DELTA_NET_QKVZ, MAMBA2_IN, MLP_GATE_UP, SELECTION,
    SHORT_CONV_IN,
    GroupedAttention, Mamba2Mixer, RMSNorm, SwiGluMlp)
from mmlspark_tpu.observability import metrics as obsmetrics  # noqa: E402
from mmlspark_tpu.ops import linear_attention as la  # noqa: E402
from mmlspark_tpu.train.lm_loss import next_token_loss  # noqa: E402

KINDS = ("mamba", "attention", "mamba") * 2
CFG = dict(hidden_size=32, num_hidden_layers=6, layer_types=list(KINDS),
           num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=4,
           mamba_d_head=8, mamba_d_state=8, mamba_n_groups=1, mamba_d_conv=4,
           shared_intermediate_size=48, embedding_multiplier=3.0,
           attention_multiplier=0.25, residual_multiplier=0.5,
           logits_scaling=2.0, vocab_size=96, rms_norm_eps=1e-5,
           program={"chunk": 8, "zoo_args": {"dtype": jnp.float32}})
OPT = dict(learning_rate=1e-2, beta1=0.9, beta2=0.95, eps=1e-8,
           weight_decay=0.1)
ROWS, LEN = 2, 20               # two and a half chunks of 8
REPO = Path(__file__).resolve().parent.parent


def _defaults(name):
    """The zoo entry's own defaults, by keyword: what a family is lives in
    its entry, the module it builds holds parts."""
    return {k: p.default for k, p in inspect.signature(
        getattr(decoder, name)).parameters.items()}


def _tokens(seed, steps=1):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], size=(steps, ROWS, LEN)).astype(np.int32)


def _module(cfg=CFG):
    return build_model("granite_hybrid", **ref.zoo_args(cfg, 64))["module"]


def _loss_fn(module, chunk=16):
    def loss_fn(params, batch, rng):
        out = module.apply(params, batch["tokens"], hidden=True)
        loss, aux = next_token_loss(
            out, ref.head_kernel(params), batch["tokens"], chunk=chunk,
            dtype=jnp.float32)
        return loss, {**aux, **out["stats"]}
    return loss_fn


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b)


@pytest.fixture(scope="module")
def params():
    return ref.init_params(CFG, jax.random.PRNGKey(7))


# compiled once a file: op-by-op dispatch of six blocks costs a minute
_apply = jax.jit(lambda p, t: _module().apply(p, t))
_ref_logits = jax.jit(lambda p, t: ref.logits(CFG, p, t))


def _ref_loss_and_grads(params, tokens, cfg=CFG):
    """The batch's loss and gradient by ``jax.grad`` of the reference's
    ``sequence_loss``, one sequence at a time."""
    grad = jax.jit(jax.value_and_grad(
        lambda p, t: ref.sequence_loss(cfg, None, len(tokens), p, t)))
    loss, total = 0.0, None
    for row in tokens:
        part, g = grad(params, jnp.asarray(row))
        loss = loss + part
        total = g if total is None else jax.tree_util.tree_map(
            jnp.add, total, g)
    return loss, total


# ---------------------------------------------------- the state-space rule
def _rule_inputs(L, decay, B=2, H=6, P=4, G=2, N=8, seed=0):
    """``decay``: "mild" (dt A down to -2 a token), "strong" (-20: a
    quotient of exponentials would be 0 / 0 a few tokens in) or "range"
    (-200: ONE token passes float32's range)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (B, L, H, P))
    Bm = jax.random.normal(ks[1], (B, L, G, N))
    Cm = jax.random.normal(ks[2], (B, L, G, N))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, L, H))) * (
        0.1 if decay == "mild" else 1.0)
    A = -jnp.exp(jax.random.uniform(ks[4], (H,), maxval=jnp.log(16.0))) * (
        10.0 if decay == "range" else 1.0)
    return x, dt, A, Bm, Cm


@pytest.mark.parametrize("decay", ["mild", "strong", "range"])
@pytest.mark.parametrize("length,chunk,groups", [
    (64, 16, 1), (50, 16, 2), (130, 64, 1), (256, 128, 3), (16, 16, 6),
    (37, 5, 1)])
def test_chunked_state_space_rule_is_the_token_by_token_rule(
        length, chunk, groups, decay):
    args = _rule_inputs(length, decay, G=groups)
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def run(impl):
        def f(*a):
            return la.ssd(*a, chunk=chunk, impl=impl)
        return jax.jit(lambda *a: (f(*a), jax.grad(
            lambda *b: jnp.sum(f(*b) * w), argnums=(0, 1, 2, 3, 4))(*a)))(
                *args)
    want, want_g = run("recurrent")
    got, got_g = run("chunked")
    assert got.shape == args[0].shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-5 * float(
        jnp.abs(want).max()))
    tol = 1e-3 if decay == "range" else 5e-5
    for name, a, b in zip("x dt A B C".split(), got_g, want_g):
        assert bool(jnp.isfinite(a).all()), name
        np.testing.assert_allclose(
            a, b, atol=tol * float(jnp.abs(b).max()) + 1e-9, err_msg=name)


@pytest.mark.parametrize("groups", [1, 2])
def test_the_rule_is_its_two_lines_by_hand(groups):
    """One row, by a Python loop over tokens in float64."""
    inputs = _rule_inputs(11, "mild", B=1, G=groups)
    x, dt, A, Bm, Cm = (np.asarray(a, np.float64) for a in inputs)
    H, P, N = x.shape[2], x.shape[3], Bm.shape[3]
    S = np.zeros((H, N, P))
    want = np.zeros((11, H, P))
    for t in range(11):
        for h in range(H):
            g = h // (H // groups)
            S[h] = np.exp(dt[0, t, h] * A[h]) * S[h] + dt[0, t, h] * np.outer(
                Bm[0, t, g], x[0, t, h])
            want[t, h] = S[h].T @ Cm[0, t, g]
    for impl, chunk in (("recurrent", 4), ("chunked", 4), ("chunked", 16)):
        got = la.ssd(*inputs, chunk=chunk, impl=impl)
        np.testing.assert_allclose(got[0], want, rtol=1e-4, atol=1e-5,
                                   err_msg=f"{impl} {chunk}")


def test_a_chunk_of_the_published_decay_passes_float32s_range():
    """The control of the "range" case above: at Mamba-2's own numbers (dt
    up to 0.1, A down to -16: dt A = -1.6 a token) the running sum over the
    published chunk of 256 is past -88 several times over, where exp(G_i) /
    exp(G_j) would be 0 / 0."""
    assert la.SSD_CHUNK == 256
    assert 256 * 0.1 * -16.0 < 4 * np.log(np.finfo(np.float32).tiny)
    dt, A = _rule_inputs(64, "range")[1:3]
    G = jnp.cumsum((dt * A).reshape(2, 4, 16, 6), axis=2)
    assert float(G.min()) < -200.0
    quotient = jnp.exp(G)[:, :, :, None] / jnp.exp(G)[:, :, None, :]
    assert not bool(jnp.isfinite(quotient).all())


def test_auto_takes_the_chunked_form_and_both_rules_are_counted():
    def counts():
        return {k: obsmetrics.counter(f"linear_attention.{k}").value
                for k in ("calls.chunked", "calls.recurrent", "fallbacks",
                          "rule_calls.ssd", "rule_calls.delta")}
    before = counts()
    for length in (8, 7):
        args = _rule_inputs(length, "mild")
        got = la.ssd(*args, chunk=8)
        want = la.ssd(*args, chunk=8, impl="recurrent")
        np.testing.assert_allclose(got, want, atol=1e-5)
    q = la.l2_normalize(jax.random.normal(jax.random.PRNGKey(0),
                                          (1, 8, 2, 4)))
    ones = jnp.ones((1, 8, 2))
    la.gated_delta_rule(q, q, q, -ones, 0.5 * ones, chunk=8)
    after = counts()
    assert after["calls.chunked"] - before["calls.chunked"] == 2
    assert after["calls.recurrent"] - before["calls.recurrent"] == 3
    assert after["rule_calls.ssd"] - before["rule_calls.ssd"] == 4
    assert after["rule_calls.delta"] - before["rule_calls.delta"] == 1
    assert after["fallbacks"] == before["fallbacks"]    # the CPU is no chip
    with pytest.raises(ValueError):
        la.ssd(*args, impl="scan")
    with pytest.raises(ValueError):                     # 6 heads, 4 groups
        la.ssd(args[0], args[1], args[2], jnp.zeros((2, 7, 4, 8)),
               jnp.zeros((2, 7, 4, 8)))
    with pytest.raises(ValueError):
        la.ssd(args[0], args[1][:, :3], *args[2:])


def test_convolution_with_a_bias_is_the_plain_loop_and_causal():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 11, 5))
    kernel = jax.random.normal(jax.random.PRNGKey(2), (4, 5))
    bias = jax.random.normal(jax.random.PRNGKey(3), (5,))
    got = la.causal_conv1d(x, kernel, bias)
    want = np.tile(np.asarray(bias), (2, 11, 1))
    for t in range(11):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += np.asarray(kernel[j]) * np.asarray(
                    x[:, t - 3 + j])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(la.causal_conv1d(x, kernel), want - np.asarray(
        bias), rtol=1e-5, atol=1e-6)            # without one, as before
    for b in range(2):      # the reference's own, one sequence at a time
        np.testing.assert_allclose(ref._conv(x[b], kernel, bias), want[b],
                                   rtol=1e-5, atol=1e-6)
    later = x.at[:, 6:].set(0.0)        # nothing before t = 6 sees it
    np.testing.assert_array_equal(
        la.causal_conv1d(later, kernel, bias)[:, :6], got[:, :6])


def test_the_gate_comes_before_the_norm_by_hand():
    """``rmsnorm(y * silu(z)) * w`` over ALL channels: not the norm of each
    head, and not gated after (the order ``GatedDeltaNet`` has)."""
    layer = Mamba2Mixer(12, 3, 4, 5, dtype=jnp.float32, chunk=4)
    u = jax.random.normal(jax.random.PRNGKey(1), (1, 6, 12))
    p = layer.init(jax.random.PRNGKey(2), u)["params"]
    p = jax.tree_util.tree_map(lambda v: v, p)
    p["gate_norm"]["scale"] = jnp.linspace(0.5, 1.5, 12)
    p["attn_gate_value_key_query_dt"]["kernel"] *= 20.0
    p["attn_out"]["kernel"] = jnp.eye(12)            # read y itself
    got = np.asarray(layer.apply({"params": p}, u))[0]
    proj = np.asarray(u[0] @ p["attn_gate_value_key_query_dt"]["kernel"])
    z = proj[:, :12]
    xbc = np.asarray(jax.nn.silu(ref._conv(
        jnp.asarray(proj[:, 12:34]), p["conv_kernel"], p["conv_bias"])))
    x = xbc[:, :12].reshape(6, 3, 4)
    Bm, Cm = xbc[:, 12:17], xbc[:, 17:22]
    dt = np.log1p(np.exp(proj[:, 34:] + np.asarray(p["dt_bias"])))
    A = -np.exp(np.asarray(p["A_log"]))
    S, y = np.zeros((3, 5, 4)), np.zeros((6, 3, 4))
    for t in range(6):
        for h in range(3):
            S[h] = np.exp(dt[t, h] * A[h]) * S[h] \
                + dt[t, h] * np.outer(Bm[t], x[t, h])
            y[t, h] = S[h].T @ Cm[t] + float(p["D_skip"][h]) * x[t, h]
    gated = y.reshape(6, 12) * (z / (1.0 + np.exp(-z)))
    want = gated / np.sqrt(np.mean(gated * gated, -1, keepdims=True) + 1e-5) \
        * np.asarray(p["gate_norm"]["scale"])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    plain = y.reshape(6, 12)
    late = plain / np.sqrt(np.mean(plain * plain, -1, keepdims=True) + 1e-5) \
        * np.asarray(p["gate_norm"]["scale"]) * (z / (1.0 + np.exp(-z)))
    assert np.abs(late - want).max() > 1e-2             # the order matters
    heads = gated.reshape(6, 3, 4)
    per_head = (heads / np.sqrt(np.mean(heads * heads, -1, keepdims=True)
                                + 1e-5)).reshape(6, 12) \
        * np.asarray(p["gate_norm"]["scale"])
    assert np.abs(per_head - want).max() > 1e-2         # so does the group


# ------------------------------------------------- the model as a whole
def test_reference_tree_is_the_programs_tree_and_layer_types(monkeypatch,
                                                             params):
    module = _module()
    own = module.init(jax.random.PRNGKey(0), jnp.zeros((1, LEN), jnp.int32))
    shapes = lambda t: jax.tree_util.tree_map(lambda x: x.shape, t)
    assert shapes(own) == shapes(params)
    assert shapes(own) == ref.param_shapes(CFG)
    p = own["params"]
    assert "lm_head" not in p                   # ONE table
    for i, kind in enumerate(KINDS):
        block = p[f"block{i}"]
        assert set(block) == {"norm1", "attn", "norm2", "ffn"}
        assert set(block["ffn"]) == {"mlp_gate", "mlp_up", "mlp_down"}
        if kind == "mamba":
            assert {"conv_bias", "D_skip", "gate_norm"} <= set(block["attn"])
        else:
            assert set(block["attn"]) == {"attn_query", "attn_key",
                                          "attn_value", "attn_out"}
    assert ref.routed_blocks(CFG) == []
    # the published list: forty layers, 36 : 4, and the cell's first period
    assert len(GRANITE_4_H_MICRO_LAYERS) == 40
    assert GRANITE_4_H_MICRO_LAYERS.count("attention") == 4
    assert [i for i, k in enumerate(GRANITE_4_H_MICRO_LAYERS)
            if k == "attention"] == [5, 15, 25, 35]
    whole, entry = build_model("granite_hybrid")["module"], _defaults(
        "granite_hybrid")
    assert entry["layer_types"] == GRANITE_4_H_MICRO_LAYERS
    assert [i for i in range(40) if whole.mixers[i] is whole.mixers[5]] \
        == [5, 15, 25, 35] and len(set(whole.ffns)) == 1
    assert (whole.embedding_multiplier, entry["attention_multiplier"],
            whole.residual_scale, whole.logits_scaling) == (
                12.0, 0.015625, 0.22, 8.0)
    assert whole.tied and whole.split and whole.mtp is None
    with pytest.raises(ValueError, match="'mamba' or 'attention' a layer"):
        build_model("granite_hybrid_tiny", layer_types=("mamba", "moe"))
    with pytest.raises(ValueError, match="layer_types"):
        build_model("granite_hybrid_tiny", layer_types=())
    with pytest.raises(ValueError):
        ref.dims(dict(CFG, num_hidden_layers=4))
    # the module's own init: Mamba-2's; the reference's, from its own draws
    for tree in (p, params["params"]):
        mixer = tree["block0"]["attn"]
        a = np.exp(np.asarray(mixer["A_log"]))
        assert np.all((a >= 1.0) & (a <= 16.0))
        dt = np.log1p(np.exp(np.asarray(mixer["dt_bias"])))
        assert np.all((dt >= 1e-3 * 0.999) & (dt <= 0.1 * 1.001))
        assert np.all(np.asarray(mixer["D_skip"]) == 1)
        assert np.all(np.asarray(mixer["gate_norm"]["scale"]) == 1)
    assert float(jnp.abs(params["params"]["block0"]["attn"][
        "conv_bias"]).max()) > 0
    # the tiny preset is this file's configuration
    seen = []
    monkeypatch.setattr(decoder, "granite_hybrid", lambda **kw: seen.append(kw))
    build_model("granite_hybrid_tiny")
    assert [{**entry, **kw} for kw in seen] == [
        {**entry, **ref.zoo_args(CFG, 64)}]


def test_logits_match_the_reference_with_all_four_multipliers(params):
    tokens = _tokens(1)[0]
    got = _apply(params, jnp.asarray(tokens))
    assert got.shape == (ROWS, LEN, CFG["vocab_size"])
    assert got.dtype == jnp.float32
    for b in range(ROWS):
        want = _ref_logits(params, jnp.asarray(tokens[b]))
        np.testing.assert_allclose(got[b], want, rtol=1e-5, atol=1e-6)
    # each multiplier is in the result: at 1 the logits are others
    for key in ("embedding_multiplier", "attention_multiplier",
                "residual_multiplier", "logits_scaling"):
        plain = dict(CFG, **{key: 1.0})
        other = _module(plain).apply(params, jnp.asarray(tokens))
        assert float(jnp.abs(other - got).max()) > 1e-4, key
        np.testing.assert_allclose(
            other[0], ref.logits(plain, params, jnp.asarray(tokens[0])),
            rtol=1e-5, atol=1e-6, err_msg=key)
    # the hidden rows the loss reads are ALREADY divided by logits_scaling
    out = _module().apply(params, jnp.asarray(tokens), hidden=True)
    assert out["stats"] == {}
    np.testing.assert_allclose(
        out["hidden"] @ ref.head_kernel(params), got, rtol=1e-5, atol=1e-6)


def test_losses_and_gradients_match_the_reference(params):
    tokens = _tokens(2)[0]
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        _loss_fn(_module()), has_aux=True))(
            params, {"tokens": jnp.asarray(tokens)}, None)
    want_loss, want = _ref_loss_and_grads(params, tokens)
    assert set(aux) == {"loss.main"}
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    np.testing.assert_allclose(aux["loss.main"], want_loss, rtol=1e-5)
    got = dict(jax.tree_util.tree_leaves_with_path(grads))
    for path, w in jax.tree_util.tree_leaves_with_path(want):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.abs(w).max())
        assert scale > 0, name                          # every leaf trains
        np.testing.assert_allclose(
            got[path], w, rtol=1e-4, atol=1e-4 * scale + 1e-9, err_msg=name)


def test_the_tied_tables_gradient_is_the_sum_of_its_two_sources(params):
    """The table read by the gather alone, by the head alone, and by both:
    the third gradient is the sum of the first two, and neither is zero."""
    module = _module()
    tokens = jnp.asarray(_tokens(5)[0])

    def loss(gathered, head):
        p = {"params": {**params["params"],
                        "token_embedding": {"embedding": gathered}}}
        out = module.apply(p, tokens, hidden=True)
        return next_token_loss(out, head.T, tokens, chunk=16,
                               dtype=jnp.float32)[0]
    table = params["params"]["token_embedding"]["embedding"]
    by_gather, by_head = jax.jit(jax.grad(loss, argnums=(0, 1)))(table, table)
    both = jax.jit(jax.grad(lambda t: loss(t, t)))(table)
    assert float(jnp.abs(by_gather).max()) > 0
    assert float(jnp.abs(by_head).max()) > 0
    np.testing.assert_allclose(both, by_gather + by_head, rtol=1e-5,
                               atol=1e-7)
    whole = jax.jit(jax.grad(lambda p: _loss_fn(module)(
        p, {"tokens": tokens}, None)[0]))(params)
    np.testing.assert_allclose(
        whole["params"]["token_embedding"]["embedding"], both, rtol=1e-5,
        atol=1e-7)
    want = _ref_loss_and_grads(params, np.asarray(tokens))[1]
    np.testing.assert_allclose(
        both, want["params"]["token_embedding"]["embedding"], rtol=1e-4,
        atol=1e-4 * float(jnp.abs(both).max()))


def test_the_references_walk_over_layers_gives_jax_grads_gradient():
    """``train_reference`` never holds the gradient whole: its first
    gradient, gathered layer by layer, is ``jax.grad`` of
    ``sequence_loss``, leaf for leaf in the program's order."""
    seed, tokens = 5, _tokens(6, steps=1)
    got = ref.train_reference(CFG, seed, tokens, steps=1, optimizer=OPT)
    start = ref.init_params(CFG, jax.random.PRNGKey(seed))
    loss, want = _ref_loss_and_grads(start, tokens[0])
    np.testing.assert_allclose(got["losses"][0], loss, rtol=1e-6)
    leaves = jax.tree_util.tree_leaves_with_path(want)
    assert len(got["first_grad"]) == len(leaves) == len(got["grad_norms"])
    for g, (path, w), (name, n) in zip(got["first_grad"], leaves,
                                       got["grad_norms"].items()):
        assert name == jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            g, w, rtol=1e-4, atol=1e-5 * float(jnp.abs(w).max()) + 1e-9,
            err_msg=name)
        np.testing.assert_allclose(n, float(jnp.sqrt(jnp.sum(w * w))),
                                   rtol=1e-4, err_msg=name)
    assert got["routing"] == [] and got["mtp"] == []


def test_three_adamw_steps_match_the_reference():
    from mmlspark_tpu.parallel.mesh import mesh_from_config
    from mmlspark_tpu.parallel.trainer import DistributedTrainer
    seed, tokens = 11, _tokens(3, steps=3)
    want = ref.train_reference(CFG, seed, tokens, steps=3, optimizer=OPT)
    assert want["mtp"] == [] and want["main"] == want["losses"]
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
        assert set(m) >= {"loss", "loss.main"}
        assert not [k for k in m if k.startswith("moe.")]
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
    assert list(moved) == list(want["delta_norms"])
    for k, v in moved.items():
        few = any(n in k for n in ("A_log", "dt_bias", "D_skip"))
        np.testing.assert_allclose(
            float(v), want["delta_norms"][k], rtol=3e-2 if few else 2e-3,
            atol=1e-12, err_msg=k)


def test_each_block_recomputed_in_halves_is_the_block_not_recomputed():
    """The step's jaxpr holds two checkpointed regions a block, and
    recomputation changes no gradient."""
    import flax.linen as nn
    module = _module()
    tokens = jnp.asarray(_tokens(4)[0])
    params = ref.init_params(CFG, jax.random.PRNGKey(3))

    def loss(p):
        return jnp.sum(jnp.sin(module.apply(p, tokens, hidden=True)[
            "hidden"]))
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    regions = sum(1 for eqn in jaxpr.eqns
                  if eqn.primitive.name in ("checkpoint", "remat2", "remat"))
    assert regions == 2 * len(KINDS)
    kept = jax.jit(jax.grad(loss))(params)
    real = nn.remat
    try:
        nn.remat = lambda cls, **kw: cls
        want = jax.jit(jax.grad(lambda p: loss(p)))(params)
    finally:
        nn.remat = real
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(kept),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            g, w, rtol=1e-6, atol=1e-6 * float(jnp.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("preset", [
    "glm4_moe_lite_tiny", "qwen3_next_tiny", "granite_hybrid_tiny",
    "lfm2_moe_tiny", "laguna_tiny", "keye_vl2_tiny"])
def test_every_family_builds_its_blocks_with_the_one_list(monkeypatch,
                                                          preset):
    """``_remat_block`` has ONE list of names for every family (the policy
    is a closure over its names): a name that no value of a block carries
    costs nothing, so the list does not know the family. This family's
    exception (the flash kernel's residuals alone, for want of a
    measurement of the room the 8,192-wide gate and up products take) went
    when the room was measured (PERF.md section 6, PR 36)."""
    from mmlspark_tpu.ops.pallas_attention import FLASH_RESIDUALS
    from mmlspark_tpu.ops.pallas_delta_rule import DELTA_CHUNK_TILES
    seen = []
    real = jax.checkpoint_policies.save_only_these_names

    def spy(*names):
        seen.append(names)
        return real(*names)
    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                        spy)
    build_model(preset)["module"].init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    assert seen and set(seen) == {
        (FLASH_RESIDUALS, MLP_GATE_UP, DELTA_CHUNK_TILES, DELTA_NET_QKVZ,
         SHORT_CONV_IN, MAMBA2_IN, ATTN_QKV, SELECTION)}


@pytest.mark.parametrize("kept", [True, False], ids=["kept", "let_go"])
def test_the_kept_mixer_rows_change_no_gradient(monkeypatch, params, kept):
    """``MAMBA2_IN`` names the ``[z | x | B | C | dt]`` rows for
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
                *a, **dict(kw, let_go=(MAMBA2_IN,))))
    got = jax.jit(grads())(params)
    text = str(jax.make_jaxpr(grads())(params))
    # 4 mixers, 32 + 48 + 4 columns: the forward's product, and the
    # recomputation's only where the rows are let go (the backward's two
    # products give (2, 20, 32) and (32, 84))
    wide = text.count("f32[2,20,84] = dot_general")
    assert wide == (4 if kept else 8), wide
    monkeypatch.setattr(nn, "remat", lambda cls, **kw: cls)
    for (path, g), w in zip(
            jax.tree_util.tree_leaves_with_path(got),
            jax.tree_util.tree_leaves(jax.jit(grads())(params))):
        np.testing.assert_allclose(
            g, w, rtol=1e-5, atol=1e-5 * float(jnp.abs(w).max()) + 1e-9,
            err_msg=jax.tree_util.keystr(path))


# ------------------------------------------------------------ the parts
def test_grouped_attention_four_to_one_at_a_scale_of_its_own():
    """32 query heads over 8 key/value heads, the published ratio, at a
    softmax scale that is not ``d^-1/2``, through ``full_attention``: the
    program repeats each key/value head 4 times and scales q, the
    reference indexes K/V by ``h // 4`` and scales the scores. No
    positions: the first token attends to itself alone, wherever it is."""
    d = dict(heads=32, kv_heads=8, head=4, attn_mult=0.3)
    layer = GroupedAttention(24, 32, 8, 4, 0.3, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 24))
    p = layer.init(jax.random.PRNGKey(2), x)
    p = jax.tree_util.tree_map(lambda v: 8.0 * v, p)
    calls = obsmetrics.counter("attention.fused_calls.reference").value
    got = jax.jit(layer.apply)(p, x)
    assert obsmetrics.counter(
        "attention.fused_calls.reference").value == calls + 1
    want = jax.jit(jax.vmap(lambda row: ref._attention(
        d, _mm, p["params"], row)))(x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    plain = GroupedAttention(24, 32, 8, 4, None, jnp.float32).apply(p, x)
    assert float(jnp.abs(plain - got).max()) > 1e-3     # the scale is used
    np.testing.assert_allclose(plain, jax.vmap(lambda row: ref._attention(
        dict(d, attn_mult=0.5), _mm, p["params"], row))(x), rtol=1e-5,
        atol=1e-6)                                      # None: 4^-1/2
    later = x.at[:, 5:].set(0.0)                        # causal
    np.testing.assert_allclose(layer.apply(p, later)[:, :5], got[:, :5],
                               rtol=1e-6, atol=1e-7)
    with pytest.raises(ValueError):
        GroupedAttention(24, 6, 4, 4, None, jnp.float32).init(
            jax.random.PRNGKey(0), x)


@pytest.mark.parametrize("groups", [1, 2])
def test_mixer_layer_is_the_reference_layer(groups):
    d = dict(ref.dims(CFG), groups=groups)
    layer = Mamba2Mixer(32, 4, 8, 8, groups, 4, 1e-5, 8, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 21, 32))
    p = layer.init(jax.random.PRNGKey(2), x)

    def away(path, v):          # every parameter away from its init
        name = jax.tree_util.keystr(path)
        if "kernel" in name or "conv_bias" in name:
            return 8.0 * v
        if "D_skip" in name or "scale" in name:
            return v + jnp.linspace(-0.5, 0.5, v.size).reshape(v.shape)
        return v
    p = jax.tree_util.tree_map_with_path(away, p)
    got = jax.jit(layer.apply)(p, x)
    want = jax.jit(jax.vmap(lambda row: ref._mamba(
        d, _mm, p["params"], row)))(x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the chunked form ran: 21 tokens are two and a half chunks of 8
    token = Mamba2Mixer(32, 4, 8, 8, groups, 4, 1e-5, 64, dtype=jnp.float32)
    np.testing.assert_allclose(jax.jit(token.apply)(p, x), want, rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("split", [False, True], ids=["whole", "halves"])
def test_a_block_with_a_residual_multiplier_is_its_two_equations(split):
    """``h = x + r mixer(norm1(x))``, ``y = h + r mlp(norm2(h))``, by hand
    from the parts, recomputed whole or in halves; at ``r`` = 1 the block
    is the one it was."""
    def parts(r):
        return decoder._remat_block(
            lambda n: RMSNorm(1e-5, name=n),
            lambda n: GroupedAttention(16, 4, 2, 4, 0.3, jnp.float32,
                                       name=n),
            lambda n: SwiGluMlp(16, 24, jnp.float32, name=n), None,
            split=split, residual_scale=r)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 16))
    p = parts(0.22).init(jax.random.PRNGKey(2), x)
    p = jax.tree_util.tree_map(lambda v: 4.0 * v, p)
    q = p["params"]

    def sub(module, name, v):
        return module.apply({"params": q[name]}, v)
    mixer = GroupedAttention(16, 4, 2, 4, 0.3, jnp.float32)
    mlp = SwiGluMlp(16, 24, jnp.float32)
    for r in (0.22, 1.0):
        got, stats = parts(r).apply(p, x)
        h = x + r * sub(mixer, "attn", sub(RMSNorm(1e-5), "norm1", x))
        want = h + r * sub(mlp, "ffn", sub(RMSNorm(1e-5), "norm2", h))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        assert stats == {}
    assert PartsBlock.__dataclass_fields__["residual_scale"].default == 1.0


# -------------------------------------------- the benchmark's own counts
def _cell_config():
    with open(REPO / "benchmark" / "configs"
              / "granite-4.0-h-micro.json") as f:
        return json.load(f)


def test_required_flops_follow_the_counts():
    """ISSUE 33 counts 1,617 MFLOP a token forward: 9 x 156.7 (Mamba-2
    layers: 56.0 the mixer, 4.3 of it the rule; 100.7 the feed-forward
    part), 155.2 (the attention layer at 8,192, 33.6 the causal scores),
    51.4 (the head); 39.7 TFLOP a row trained."""
    cfg = _cell_config()
    parts = ref._fwd_flops_per_token(cfg, 8192)
    assert 55.9e6 < parts["mamba"] < 56.1e6
    assert parts["mlp"] == 2 * 3 * 2048 * 8192
    assert 156.6e6 < parts["mamba"] + parts["mlp"] < 156.8e6
    assert 155.1e6 < parts["attention"] + parts["mlp"] < 155.3e6
    assert parts["attention"] == pytest.approx(
        2 * (2 * 2048 * 2048 + 2 * 2048 * 512) + 2 * 4096 * 32 * 2 * 64)
    assert parts["head"] == 2 * 2048 * 12544
    assert 1615e6 < parts["total"] < 1618e6
    assert ref.train_flops_per_item(cfg, 8192) == pytest.approx(
        3 * 8192 * parts["total"])
    assert 39.6e12 < ref.train_flops_per_item(cfg) < 39.8e12
    assert 0.30 < 9 * parts["mamba"] / parts["total"] < 0.33    # the third
    rule = ref.ssd_flops_per_token(ref.dims(cfg), 256)
    assert rule["total"] == pytest.approx(
        1 * 2 * 256 * 128 + 64 * 2 * (256 * 64 + 2 * 128 * 64))
    assert 4.2e6 < rule["total"] < 4.4e6
    count = lambda t: sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        t, is_leaf=lambda x: isinstance(x, tuple)))
    shapes = ref.param_shapes(cfg)["params"]
    assert count(shapes) == 772_160_448
    assert count(shapes["block0"]) == 76_182_976
    assert count(shapes["block0"]["attn"]) == 25_847_232
    assert count(shapes["block5"]) == 60_821_504
    assert count(shapes["block5"]["attn"]) == 10_485_760
    assert count(shapes["block0"]["ffn"]) == 50_331_648
    assert count(shapes["token_embedding"]) == 25_690_112
    assert "772,160,448" in cfg["deployment"]["parameters_here"]
    # one step of the cell: 9 layers x 1 row x 64 heads x 32 chunks
    call = ref.kernel_calls(cfg, 1, 8192)
    assert call == {
        "flash_fwd": {"rows": 1, "len": 8192, "heads": 32, "head_dim": 64},
        "ssd_walk": {"rows": 1, "len": 8192, "heads": 64, "state": 128,
                     "head_dim": 64, "chunk": 256, "layers": 9}}
    flops, nbytes = ref.ssd_walk_cost(call["ssd_walk"])
    chunks = 9 * 1 * 64 * 32
    assert flops == pytest.approx(chunks * 6 * 128 * 64)
    assert flops == pytest.approx(3 * 9 * 8192 * rule["walk"])
    assert nbytes == pytest.approx(chunks * 5 * 32 * 1024)


def test_configuration_holds_the_catalogued_numbers():
    """Every number of the catalogue's row under its own key, but for the
    three reduced ones; no width among those."""
    cfg = _cell_config()
    published = dict(
        attention_multiplier=0.015625, embedding_multiplier=12,
        hidden_size=2048, intermediate_size=8192,
        layer_types=list(GRANITE_4_H_MICRO_LAYERS), logits_scaling=8,
        mamba_chunk_size=256, mamba_d_conv=4, mamba_d_head=64,
        mamba_d_state=128, mamba_expand=2, mamba_n_groups=1,
        mamba_n_heads=64, max_position_embeddings=131072,
        num_attention_heads=32, num_experts_per_tok=0, num_hidden_layers=40,
        num_key_value_heads=8, num_local_experts=0,
        residual_multiplier=0.22, rms_norm_eps=1e-5, rope_theta=10000,
        shared_intermediate_size=8192, vocab_size=100352)
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == sorted(cfg["reduced"]) == [
        "layer_types", "num_hidden_layers", "vocab_size"]
    assert set(cfg["reduced_from"]) == set(cfg["reduced"])
    for flag, value in dict(attention_bias=False, mamba_proj_bias=False,
                            mamba_conv_bias=True, tie_word_embeddings=True,
                            hidden_act="silu", rope_scaling=None,
                            normalization_function="rmsnorm",
                            position_embedding_type="nope",
                            model_type="granitemoehybrid").items():
        assert cfg[flag] == value, flag
    dep = cfg["deployment"]
    assert dep["chips_sharing_each_layer"] == 1
    assert dep["pipeline_stages"] * cfg["num_hidden_layers"] \
        == dep["num_hidden_layers_published"] == 40
    assert cfg["vocab_size"] * dep["chips_sharing_the_table"] \
        == dep["vocab_size_published"] == 100352
    assert cfg["layer_types"] == list(GRANITE_4_H_MICRO_LAYERS[:10])
    assert cfg["layer_types"].count("mamba") == 9       # one whole period
    assert cfg["program"]["chunk"] == cfg["mamba_chunk_size"]
    assert cfg["mamba_expand"] * cfg["hidden_size"] \
        == cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    assert (cfg["runner"], cfg["reference"]) == ("train_lm_dense",
                                                 "granite_hybrid")
    with open(REPO / "benchmark" / "traffic" / "train-lm-8k.json") as f:
        traffic = json.load(f)
    assert (traffic["batch_per_chip"], traffic["tokens_per_row"]) == (1, 8192)
    # the zoo entry's defaults are the published numbers
    entry = _defaults("granite_hybrid")
    uncut = dict(cfg, **{k: published[k] for k in cfg["reduced"]})
    args = ref.zoo_args(uncut, 8192)
    args.pop("max_len")
    for k, v in args.items():
        assert entry[k] == v, k
    # attention's scale is NOT head^-1/2, and q's factor is a power of two
    assert cfg["attention_multiplier"] != 64 ** -0.5
    assert cfg["attention_multiplier"] * 64 ** 0.5 == 0.125


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
    assert spec[mixer + "['attn_gate_value_key_query_dt']['kernel']"] == P(
        None, "tensor")
    assert spec[mixer + "['attn_out']['kernel']"] == P("tensor", None)
    for name in ("['conv_kernel']", "['conv_bias']", "['A_log']",
                 "['dt_bias']", "['D_skip']", "['gate_norm']['scale']"):
        assert whole(spec[mixer + name]), name
    soft = "['params']['block1']['attn']"
    for name in ("attn_query", "attn_key", "attn_value"):
        assert spec[soft + f"['{name}']['kernel']"] == P(None, "tensor"), name
    assert spec[soft + "['attn_out']['kernel']"] == P("tensor", None)
    ffn = "['params']['block1']['ffn']"
    assert spec[ffn + "['mlp_gate']['kernel']"] == P(None, "tensor")
    assert spec[ffn + "['mlp_up']['kernel']"] == P(None, "tensor")
    assert spec[ffn + "['mlp_down']['kernel']"] == P("tensor", None)
    assert whole(spec["['params']['block1']['norm1']['scale']"])
    assert spec["['params']['token_embedding']['embedding']"] == P(
        "tensor", None)
