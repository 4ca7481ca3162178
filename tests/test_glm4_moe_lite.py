"""``glm4_moe_lite`` at its tiny preset against the plain reference
(``benchmark/references/glm47_flash.py``), float32 on the CPU.

Tolerances: both sides compute in float32 on one backend, so they differ
only by the order of additions (the program's grouped products and chunked
loss against the reference's dense loops): 1e-5 relative on logits and
losses, 1e-4 on gradients (sums over 32 tokens and up to 96 features of
products of four such numbers), 2e-3 of the largest element on Adam steps
(``g / (sqrt(v) + eps)`` amplifies a relative gradient error where ``g`` is
near zero).
"""
import functools
import sys
from pathlib import Path

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark.references import glm47_flash as ref  # noqa: E402
from mmlspark_tpu.models.zoo import build_model  # noqa: E402
from mmlspark_tpu.models.zoo.parts import (  # noqa: E402
    MlaAttention, SwiGluMlp, plain_frequencies, rotary)
from mmlspark_tpu.models.zoo import moe  # noqa: E402
from mmlspark_tpu.models.zoo.moe import DroplessMoe  # noqa: E402
from mmlspark_tpu.train.lm_loss import (  # noqa: E402
    chunked_cross_entropy, next_token_loss)

CFG = dict(hidden_size=32, num_attention_heads=2, q_lora_rank=24,
           kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4,
           v_head_dim=16, intermediate_size=64, moe_intermediate_size=16,
           n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
           routed_scaling_factor=1.8, num_hidden_layers=3,
           first_k_dense_replace=1, num_nextn_predict_layers=1,
           vocab_size=96, rms_norm_eps=1e-5, rope_theta=1e6,
           deployment={"n_routed_experts_published": 8, "experts_first": 0})
OPT = dict(learning_rate=1e-2, beta1=0.9, beta2=0.95, eps=1e-8,
           weight_decay=0.1)
ROWS, LEN = 2, 16


def _held(count, first=0):
    return dict(CFG, n_routed_experts=count, deployment={
        "n_routed_experts_published": 8, "experts_first": first})


def _tokens(seed, steps=1):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], size=(steps, ROWS, LEN)).astype(np.int32)


def _module(cfg=CFG, **kw):
    d = cfg["deployment"]
    return build_model(
        "glm4_moe_lite_tiny",
        experts_held=(cfg["n_routed_experts"], d["experts_first"]),
        **kw)["module"]


def _loss_fn(module, chunk=8):
    def loss_fn(params, batch, rng):
        out = module.apply(params, batch["tokens"], hidden=True)
        loss, aux = next_token_loss(
            out, params["params"]["lm_head"]["kernel"], batch["tokens"],
            mtp_weight=ref.MTP_WEIGHT, chunk=chunk, dtype=jnp.float32)
        return loss, {**aux, **out["stats"]}
    return loss_fn


@pytest.fixture(scope="module")
def params():
    return ref.init_params(CFG, jax.random.PRNGKey(7))


def test_reference_tree_is_the_programs_tree(params):
    module = _module()
    own = module.init(jax.random.PRNGKey(0), jnp.zeros((1, LEN), jnp.int32))
    shapes = lambda t: jax.tree_util.tree_map(lambda x: x.shape, t)
    assert shapes(own) == shapes(params)


def test_logits_match_the_reference(params):
    tokens = _tokens(1)[0]
    got = _module().apply(params, jnp.asarray(tokens))
    assert got.shape == (ROWS, LEN, CFG["vocab_size"])
    assert got.dtype == jnp.float32
    for b in range(ROWS):
        want = ref.logits(CFG, params, jnp.asarray(tokens[b]))
        np.testing.assert_allclose(got[b], want, rtol=1e-5, atol=1e-6)


def test_losses_and_gradients_match_the_reference(params):
    tokens = _tokens(2)[0]
    (loss, aux), grads = jax.value_and_grad(
        _loss_fn(_module()), has_aux=True)(
            params, {"tokens": jnp.asarray(tokens)}, None)
    want_loss = want_main = want_mtp = 0.0
    want = None
    for b in range(ROWS):
        (part, (main, mtp, _)), g = jax.value_and_grad(
            lambda p: ref.sequence_loss(CFG, None, ROWS, p,
                                        jnp.asarray(tokens[b])),
            has_aux=True)(params)
        want_loss, want_main, want_mtp = (
            want_loss + part, want_main + main, want_mtp + mtp)
        want = g if want is None else jax.tree_util.tree_map(
            jnp.add, want, g)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    np.testing.assert_allclose(aux["loss.main"], want_main, rtol=1e-5)
    np.testing.assert_allclose(aux["loss.mtp"], want_mtp, rtol=1e-5)
    np.testing.assert_allclose(loss, want_main + 0.3 * want_mtp, rtol=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(want)
    got = dict(jax.tree_util.tree_leaves_with_path(grads))
    for path, w in flat:
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(
            got[path], w, rtol=1e-4, atol=1e-4 * scale + 1e-9,
            err_msg=jax.tree_util.keystr(path))
    # no gradient reaches the router's bias, in either
    bias = [g for p, g in got.items() if "router_bias" in
            jax.tree_util.keystr(p)]
    assert len(bias) == 3 and all(not np.any(np.asarray(b)) for b in bias)


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
        np.testing.assert_allclose(m["loss.mtp"], want["mtp"][s], rtol=1e-5)
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
    # every routed slot of the uncut tiny model is held here
    assert float(m["moe.slots_here"]) == 3 * ROWS * LEN * 2
    assert float(m["moe.rows_moved"]) == 3 * ROWS * LEN * 2


# ------------------------------- what a block keeps for its backward
def _flash(q, k, v, causal=True):
    from mmlspark_tpu.parallel.sequence import full_attention
    return full_attention(q, k, v, causal=causal, use_flash="require")


_ATTENTION = {"flash": (_flash, 512), "reference": (None, 32)}
_flax_remat = nn.remat
# what ``decoder.py``'s ``nn.remat`` is, for the blocks compared with
_REMAT = {"kept": _flax_remat,               # the module as it is
          "input_only": lambda cls, **kw: _flax_remat(cls),
          "nothing_recomputed": lambda cls, **kw: cls}


def _block_grads(attention, jaxpr=False):
    """Gradients of the tiny model (three blocks and the MTP module's)
    under ``nn.remat`` as it stands when called, or their jaxpr."""
    attention_fn, length = _ATTENTION[attention]
    module = _module(max_len=length, attention_fn=attention_fn)
    tokens = jnp.asarray(np.random.default_rng(5).integers(
        0, CFG["vocab_size"], size=(1, length)).astype(np.int32))
    params = module.init(jax.random.PRNGKey(3), tokens)

    def loss(p):
        out = module.apply(p, tokens, hidden=True)
        return jnp.sum(jnp.sin(out["hidden"])) \
            + jnp.sum(jnp.sin(out["mtp_hidden"]))
    if jaxpr:
        return jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    return jax.jit(jax.grad(loss))(params)


@pytest.fixture(scope="module")
def kept_grads():
    """The module's own gradients, once per attention."""
    return {name: _block_grads(name) for name in _ATTENTION}


def _pallas_calls(jaxpr):
    """Names of the Pallas calls a jaxpr makes, one per call site, the
    jitted and rematerialised sub-programs walked."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"] or "")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names.extend(_pallas_calls(sub))
    return names


@pytest.mark.parametrize("remat", ["input_only", "nothing_recomputed"])
@pytest.mark.parametrize("attention", list(_ATTENTION))
def test_what_a_block_keeps_changes_no_gradient(monkeypatch, kept_grads,
                                                attention, remat):
    """The values a block keeps across its recomputation are the ones the
    recomputation would have made: gradients as with a block that keeps
    its input alone, and as with nothing recomputed at all. Under the
    flash kernel (interpret mode) the kernel's residuals and the SwiGLU
    products are kept, under the reference attention the products alone."""
    monkeypatch.setattr(nn, "remat", _REMAT[remat])
    want = _block_grads(attention)
    for (path, g), w in zip(
            jax.tree_util.tree_leaves_with_path(kept_grads[attention]),
            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            g, w, rtol=1e-6, atol=1e-6 * float(jnp.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("remat,forward_calls", [
    ("kept", 4), ("input_only", 8), ("nothing_recomputed", 4)])
def test_a_recomputed_block_holds_one_flash_forward(monkeypatch, remat,
                                                    forward_calls):
    """Four blocks in the gradient's jaxpr: the backward pass of a block
    that keeps the kernel's output and log-sum-exps has no second forward
    call."""
    monkeypatch.setattr(nn, "remat", _REMAT[remat])
    calls = _pallas_calls(_block_grads("flash", jaxpr=True))
    backward = [c for c in calls if c == "long_attention_bwd"]
    assert len(backward) == 4
    assert len(calls) - len(backward) == forward_calls


# ------------------------------------------------------ the expert layer
def _layer(held, first, **kw):
    return DroplessMoe(32, 8, 16, 2, experts_held=(held, first),
                       scaling=1.8, dtype=jnp.float32, **kw)


def _layer_params(key, shared=True):
    whole = _layer(8, 0, shared=(lambda n: SwiGluMlp(
        32, 16, jnp.float32, name=n)) if shared else None)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, 32))
    return whole, whole.init(key, x), x


def _share(p, first, count):
    ffn = dict(p["params"])
    for name in ("experts_gate", "experts_up", "experts_down"):
        ffn[name] = ffn[name][first:first + count]
    return {"params": ffn}


def test_the_shares_of_one_layer_add_up_to_the_uncut_reference():
    """Four chips hold two experts each; the shared expert, which every
    chip computes alike, is counted once. The sum is the uncut layer as
    the REFERENCE computes it (dense loop over all eight)."""
    whole, p, x = _layer_params(jax.random.PRNGKey(3))
    mm = lambda eq, a, b: jnp.einsum(eq, a, b)
    d = ref.dims(CFG)
    want = jnp.stack([ref._experts(d, mm, p["params"], x[b])[0]
                      for b in range(2)])
    shared_only = SwiGluMlp(32, 16, jnp.float32).apply(
        {"params": p["params"]["shared"]}, x)
    total = 0.0
    slots = 0
    for first in range(0, 8, 2):
        part = _layer(2, first, shared=lambda n: SwiGluMlp(
            32, 16, jnp.float32, name=n))
        y, stats = part.apply(_share(p, first, 2), x)
        total = total + (y - shared_only)
        slots += int(stats["slots_here"])
    np.testing.assert_allclose(total + shared_only, want, rtol=1e-5,
                               atol=1e-6)
    assert slots == 2 * 16 * 2            # every slot computed exactly once
    np.testing.assert_allclose(whole.apply(p, x)[0], want, rtol=1e-5,
                               atol=1e-6)


def test_dropless_under_a_router_biased_onto_two_experts():
    """Every token is sent to experts 5 and 6: the share that holds them
    computes every slot (far past any capacity), the others none."""
    whole, p, x = _layer_params(jax.random.PRNGKey(4), shared=False)
    bias = jnp.zeros((8,)).at[jnp.array([5, 6])].set(10.0)
    p = {"params": dict(p["params"], router_bias=bias)}
    S = 2 * 16
    y, stats, = whole.apply(p, x)
    assert int(stats["slots_here"]) == S * 2
    np.testing.assert_allclose(stats["load_max_over_mean"], 4.0)  # 2 of 8
    choice = whole.apply(p, x, mutable=["intermediates"])[1][
        "intermediates"]["router_choice"][0]
    assert set(np.unique(choice)) == {5, 6}
    # dense by hand: both experts on every token, gates from the scores
    xf = x.reshape(S, 32)
    s = jax.nn.sigmoid(xf @ p["params"]["router"]["kernel"])
    g = s[:, 5:7] / s[:, 5:7].sum(-1, keepdims=True) * 1.8
    want = 0.0
    for j, e in enumerate((5, 6)):
        h = jax.nn.silu(xf @ p["params"]["experts_gate"][e]) \
            * (xf @ p["params"]["experts_up"][e])
        want = want + g[:, j:j + 1] * (h @ p["params"]["experts_down"][e])
    np.testing.assert_allclose(y.reshape(S, 32), want, rtol=1e-5, atol=1e-6)
    y56, st56 = _layer(2, 5).apply(_share(p, 5, 2), x)
    np.testing.assert_allclose(y56, y, rtol=1e-5, atol=1e-6)
    assert int(st56["slots_here"]) == S * 2
    y01, st01 = _layer(2, 0).apply(_share(p, 0, 2), x)
    assert int(st01["slots_here"]) == 0 and not np.any(np.asarray(y01))


def test_a_share_that_holds_none_of_the_chosen_returns_the_shared_expert():
    whole, p, x = _layer_params(jax.random.PRNGKey(5))
    bias = jnp.zeros((8,)).at[jnp.array([5, 6])].set(10.0)
    p = {"params": dict(p["params"], router_bias=bias)}
    part = _layer(2, 0, shared=lambda n: SwiGluMlp(32, 16, jnp.float32,
                                                   name=n))
    y, stats = part.apply(_share(p, 0, 2), x)
    want = SwiGluMlp(32, 16, jnp.float32).apply(
        {"params": p["params"]["shared"]}, x)
    np.testing.assert_allclose(y, want, rtol=1e-6, atol=1e-7)
    assert int(stats["slots_here"]) == 0
    # and its gradient is finite: nothing divides by the empty load
    g = jax.grad(lambda q: part.apply(q, x)[0].sum())(_share(p, 0, 2))
    assert all(np.all(np.isfinite(np.asarray(v)))
               for v in jax.tree_util.tree_leaves(g))


def test_expert_layer_gradients_match_a_dense_loop():
    whole, p, x = _layer_params(jax.random.PRNGKey(6))
    mm = lambda eq, a, b: jnp.einsum(eq, a, b)
    d = ref.dims(_held(4, 2))
    part = _layer(4, 2, shared=lambda n: SwiGluMlp(32, 16, jnp.float32,
                                                   name=n))
    ps = _share(p, 2, 4)

    def prog(q, x):
        return jnp.sum(jnp.sin(part.apply(q, x)[0]))

    def dense(q, x):
        return sum(jnp.sum(jnp.sin(ref._experts(
            d, mm, q["params"], x[b])[0])) for b in range(2))
    got = jax.grad(prog, argnums=(0, 1))(ps, x)
    want = jax.grad(dense, argnums=(0, 1))(ps, x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-6)


# ------------------------------------- the weights, read without a gather
def _scores_and_choice(scores, experts, top_k, tokens=96):
    """Seeded scores of either kind and the choice their rule makes (the
    sigmoid's bias moves the ranking, never the scores)."""
    key = jax.random.PRNGKey(experts + top_k)
    logits = 3.0 * jax.random.normal(key, (tokens, experts))
    if scores == "sigmoid":
        s = jax.nn.sigmoid(logits)
        ranked = s + 0.3 * jax.random.normal(
            jax.random.fold_in(key, 1), (experts,))
    else:
        s = ranked = jax.nn.softmax(logits, -1)
    return s, jax.lax.top_k(ranked, top_k)[1]


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


def _equations(jaxpr, inside=()):
    """Every equation of ``jaxpr`` and of what it calls, with the calls on
    its way (``lax.switch``'s branch ``i`` is ``cond[i]``)."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        name = eqn.primitive.name
        for value in eqn.params.values():
            for i, sub in enumerate(
                    value if isinstance(value, (tuple, list)) else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub, inside + (
                        (f"cond[{i}]" if name == "cond" else name),))


_ROUTERS = [(64, 4), (128, 8), (256, 8), (512, 10)]   # the cells' four


@pytest.mark.parametrize("scores", ["sigmoid", "softmax"])
@pytest.mark.parametrize("experts, top_k", _ROUTERS)
def test_the_weights_are_the_gathers_to_the_bit(scores, experts, top_k):
    s, choice = _scores_and_choice(scores, experts, top_k)
    got = jax.jit(moe._at_choice)(s, choice)
    want = jnp.take_along_axis(s, choice, -1)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("scores", ["sigmoid", "softmax"])
@pytest.mark.parametrize("experts, top_k", _ROUTERS)
def test_the_weights_gradient_is_the_scatter_adds_to_the_bit(
        scores, experts, top_k):
    """What reaches ``s`` where ``gate_grad=True``: a token's choices are
    distinct, so every ``(token, expert)`` gets at most one addend."""
    s, choice = _scores_and_choice(scores, experts, top_k)
    g = jax.random.normal(jax.random.PRNGKey(11), choice.shape)
    pull = lambda read: jax.jit(
        lambda s, g: jax.vjp(lambda s: read(s, choice), s)[1](g)[0])(s, g)
    got = pull(moe._at_choice)
    want = pull(lambda s, c: jnp.take_along_axis(s, c, -1))
    assert np.count_nonzero(np.asarray(want)) == choice.size
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("what", ["forward", "vjp"])
def test_the_weights_are_read_with_no_gather_and_no_scatter(what):
    s, choice = _scores_and_choice("softmax", 128, 8)
    fn = lambda s, g: moe._at_choice(s, choice)
    if what == "vjp":
        fn = lambda s, g: jax.vjp(lambda s: moe._at_choice(s, choice),
                                  s)[1](g)[0]
    names = [eqn.primitive.name for eqn, _ in _equations(
        jax.make_jaxpr(fn)(s, jnp.ones(choice.shape)).jaxpr)]
    assert "reduce_sum" in names
    assert not [n for n in names if "gather" in n or "scatter" in n], names


@pytest.mark.parametrize("held, first", [(2, 5), (2, 0), (8, 0)], ids=[
    "the_two_every_token_chose", "none_of_the_chosen", "every_expert"])
def test_group_sizes_are_the_bincount_of_the_slots(held, first):
    """A router biased onto experts 5 and 6 (every slot in two groups),
    seen from the share that holds them, from one that holds neither and
    from a layer that holds all eight."""
    whole, p, x = _layer_params(jax.random.PRNGKey(4), shared=False)
    bias = jnp.zeros((8,)).at[jnp.array([5, 6])].set(10.0)
    p = {"params": dict(p["params"], router_bias=bias)}
    part = _layer(held, first)
    (_, stats), sown = part.apply(_share(p, first, held), x,
                                  mutable=["intermediates"])
    choice = sown["intermediates"]["router_choice"][0]
    local = choice - first
    key = jnp.where((local >= 0) & (local < held), local, held).reshape(-1)
    want = jnp.bincount(key, length=held + 1)[:held]
    got = jax.jit(moe._slots_by_expert, static_argnums=1)(local, held)
    assert got.dtype == jnp.int32 and got.shape == (held,)
    np.testing.assert_array_equal(got, want)
    assert int(stats["slots_here"]) == int(want.sum()) == (
        0 if (held, first) == (2, 0) else choice.size)


# ------------------------------ expert-order buffers on a ladder of sizes
# 2 of 16 experts over 512 tokens x top-2: an even router sends 128 slots
# here, and every rung below all 1,024 is one tile of 512 rows
WIDE = dict(slots=1024, bound=512)
# 3 of 16 experts over 2,048 tokens x top-2: an even router sends 768 slots
# here, so the ladder has a rung close over that, one at four times it
# (3,072) and all 4,096
TALL = dict(tokens=2048, held=(3, 4))


def _wide_params(scores, onto_held):
    """A seeded layer and its input; ``onto_held`` moves the router so
    that every token chooses the two held experts (feature 0 of the input
    is 1, so row 0 of the router's matrix is a bias for either kind of
    score)."""
    layer = DroplessMoe(32, 16, 16, 2, experts_held=(2, 4), scaling=1.8,
                        dtype=jnp.float32, scores=scores)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 256, 32))
    x = x.at[..., 0].set(1.0)
    p = dict(layer.init(jax.random.PRNGKey(8), x)["params"])
    if onto_held:
        kernel = p["router"]["kernel"]
        p["router"] = {"kernel": kernel.at[0, 4:6].add(12.0)}
    return layer, {"params": p}, x


def _tall_layer(scores):
    return DroplessMoe(32, 16, 16, 2, experts_held=TALL["held"],
                       scaling=1.8, dtype=jnp.float32, scores=scores)


def _tall_rungs():
    return moe._ladder(TALL["tokens"] * 2, TALL["held"][0], 16)


def _tall_params(scores, slots):
    """A seeded layer whose router sends exactly ``slots`` slots to the
    held experts: feature 0 of the input is 1 and its row of the router's
    matrix pushes the three held experts away for every token; feature 1
    marks the tokens that send both choices here (experts 4 and 5),
    feature 2 the one that sends one (expert 4, and the unheld expert 0).
    Expert 6 is held and chosen by nobody."""
    layer = _tall_layer(scores)
    both, one = divmod(slots, 2)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 1024, 32))
    marks = np.zeros((TALL["tokens"], 3), np.float32)
    marks[:, 0] = 1.0
    marks[:both, 1] = 1.0
    marks[both:both + one, 2] = 1.0
    # spread the marked tokens over both rows of the batch
    marks = marks[np.random.default_rng(9).permutation(TALL["tokens"])]
    x = x.at[..., :3].set(jnp.asarray(marks).reshape(2, 1024, 3))
    p = dict(layer.init(jax.random.PRNGKey(8), x)["params"])
    kernel = p["router"]["kernel"]
    kernel = kernel.at[0, 4:7].add(-12.0).at[1, 4:6].add(24.0)
    kernel = kernel.at[2, 4].add(24.0).at[2, 0].add(12.0)
    p["router"] = {"kernel": kernel}
    return layer, {"params": p}, x


def _dense_loop(layer, p, x):
    """The held experts' part of the layer, every held expert on every
    token, weighted by the gates of the slots that chose it."""
    q = p["params"]
    xf = x.reshape(-1, x.shape[-1])
    logits = xf @ q["router"]["kernel"]
    if layer.scores == "sigmoid":
        s = jax.nn.sigmoid(logits)
        ranked = s + q["router_bias"]
    else:
        s = ranked = jax.nn.softmax(logits, -1)
    _, choice = jax.lax.top_k(ranked, layer.top_k)
    gate = jnp.take_along_axis(s, choice, -1)
    gate = gate / gate.sum(-1, keepdims=True) * layer.scaling
    held, first = layer.experts_held
    y = 0.0
    for e in range(held):
        w = jnp.where(choice == first + e, gate, 0.0).sum(-1)
        h = jax.nn.silu(xf @ q["experts_gate"][e]) \
            * (xf @ q["experts_up"][e])
        y = y + w[:, None] * (h @ q["experts_down"][e])
    return y.reshape(x.shape)


def _programs(layer):
    """The layer's jitted output and gradients, and the dense loop's."""
    def sin_sum(fn):
        return lambda q, x: jnp.sum(jnp.sin(fn(q, x)))
    own = lambda q, x: layer.apply(q, x)[0]
    dense = lambda q, x: _dense_loop(layer, q, x)
    return (jax.jit(layer.apply),
            jax.jit(jax.grad(sin_sum(own), argnums=(0, 1))),
            jax.jit(dense),
            jax.jit(jax.grad(sin_sum(dense), argnums=(0, 1))))


@functools.lru_cache(maxsize=None)
def _tall_programs(scores):
    """Every load of a kind of score runs one set of programs."""
    return _programs(_tall_layer(scores))


# a load of the three-rung layer, from its ladder: the rung it lands in
# and its slots (inside the rung, up to its last row, or one slot past the
# rung below)
_TALL_LOADS = {"rung0_inside": lambda rungs: (0, 300),
               "rung0_to_its_edge": lambda rungs: (0, rungs[0]),
               "rung1_one_more": lambda rungs: (1, rungs[0] + 1),
               "rung1_to_its_edge": lambda rungs: (1, rungs[1]),
               "rung2_one_more": lambda rungs: (2, rungs[1] + 1),
               "rung2_every_slot": lambda rungs: (2, rungs[2])}


@pytest.mark.parametrize("scores", ["sigmoid", "softmax"])
@pytest.mark.parametrize("load", ["within_the_bound", "past_the_bound",
                                  *_TALL_LOADS])
def test_bounded_buffers_give_the_dense_loop_at_either_size(scores, load):
    """Output and gradients of a layer whose buffers are smaller than its
    slots, at every rung of its ladder: loads inside a rung, up to its
    last row and one slot more, which runs the same path at the next size;
    the last rung holds every slot and says so."""
    if load in _TALL_LOADS:
        rungs = _tall_rungs()
        assert len(rungs) == 3 and rungs[-1] == 2 * TALL["tokens"]
        index, slots = _TALL_LOADS[load](rungs)
        layer, p, x = _tall_params(scores, slots)
        apply, grad, dense, dense_grad = _tall_programs(scores)
    else:       # a seeded router's load, or every token onto the held
        index, rungs = int(load == "past_the_bound"), (
            WIDE["bound"], WIDE["slots"])
        slots = WIDE["slots"] if index else None
        layer, p, x = _wide_params(scores, bool(index))
        apply, grad, dense, dense_grad = _programs(layer)
    y, stats = apply(p, x)
    if slots is None:
        assert 0 < int(stats["slots_here"]) <= rungs[0]
    else:
        assert int(stats["slots_here"]) == slots
    assert int(stats["rows"]) == rungs[index]
    assert int(stats["overflowed"]) == int(index == len(rungs) - 1)
    np.testing.assert_allclose(y, dense(p, x), rtol=1e-5, atol=1e-6)
    for (path, g), w in zip(
            jax.tree_util.tree_leaves_with_path(grad(p, x)),
            jax.tree_util.tree_leaves(dense_grad(p, x))):
        np.testing.assert_allclose(
            g, w, rtol=1e-5, atol=1e-5 * float(jnp.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("slots, held, experts", [
    (32768, 8, 64), (81920, 32, 512), (131072, 8, 64), (1024, 2, 16)],
    ids=["glm", "qwen", "lfm2", "wide"])
def test_the_ladder_of_a_share(slots, held, experts):
    """Ascending whole tiles, at most four sizes; the lowest within 1.5
    times an even router's share (rounded up to a tile), one rung at four
    times it, the last every slot."""
    rungs = moe._ladder(slots, held, experts)
    tile = moe._ROWS_TILE
    tiled = lambda rows: min(slots, -(-rows // tile) * tile)
    even = slots * held // experts
    assert list(rungs) == sorted(set(rungs)) and 2 <= len(rungs) <= 4
    assert all(r % tile == 0 for r in rungs)
    assert even <= rungs[0] <= tiled(even + even // 2)
    assert tiled(4 * even) in rungs and rungs[-1] == slots


def _slot_sized_arrays(jaxpr, rows, found, inside=()):
    """Every value of ``rows`` rows of the layer's or its experts' width
    that an equation of ``jaxpr`` makes (the sort's keys have one column),
    with the branches of each ``cond`` on its way (``lax.switch``'s branch
    ``i`` is the ladder's rung ``i``)."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            if len(shape) >= 2 and shape[0] == rows and shape[-1] >= 16:
                found.append((inside, eqn.primitive.name, shape))
        for name, value in eqn.params.items():
            subs = value if isinstance(value, (tuple, list)) else (value,)
            for i, sub in enumerate(subs):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    step = (f"cond[{i}]" if eqn.primitive.name == "cond"
                            else eqn.primitive.name)
                    _slot_sized_arrays(sub, rows, found, inside + (step,))
    return found


@pytest.mark.parametrize("what", ["forward", "gradient"])
@pytest.mark.parametrize("ladder", ["wide", "tall"])
def test_no_slot_sized_array_outside_the_overflow_branch(ladder, what):
    """Between the sort and the sum by token every array has its rung's
    rows: each branch makes arrays of its own size, and nothing of a
    rung's rows is made outside that rung's branch (so nothing of ``S*K``
    rows but where a step overflows), in the forward pass or the
    backward."""
    if ladder == "wide":
        layer, p, x = _wide_params("softmax", False)
        rungs = (WIDE["bound"], WIDE["slots"])
    else:
        layer, p, x = _tall_params("softmax", 300)
        rungs = _tall_rungs()
    fn = lambda q, x: jnp.sum(layer.apply(q, x)[0])
    if what == "gradient":
        fn = jax.grad(fn, argnums=(0, 1))
    jaxpr = jax.make_jaxpr(fn)(p, x).jaxpr
    tokens = x.shape[0] * x.shape[1]
    for i, rows in enumerate(rungs):
        found = _slot_sized_arrays(jaxpr, rows, [])
        assert any(f"cond[{i}]" in inside and shape == (rows, 32)
                   for inside, _, shape in found), (i, rows)
        if rows != tokens:      # the layer's input and output have those
            assert all(f"cond[{i}]" in inside for inside, _, _ in found), [
                f for f in found if f"cond[{i}]" not in f[0]]


@pytest.mark.parametrize("gate_grad", [True, False])
def test_the_layers_gathers_and_scatters_are_the_ones_it_names(gate_grad):
    """What a ``DroplessMoe`` forward-and-gradient jaxpr still moves by
    index, each by what it moves: a rung's rows of the layer's width
    between token order and expert order (``x[token]`` and
    ``segment_sum``, each other's transposes), a rung's scalars of the
    slot axis (the combine's ``weight`` and its transpose). Nothing picks
    or counts scalars by an index over the expert axis: a gather or
    scatter of ``tokens x top_k`` scalars walks them one by one on the
    chip (PERF.md section 6, PR 48)."""
    layer = DroplessMoe(32, 16, 16, 2, experts_held=TALL["held"],
                        scaling=1.8, dtype=jnp.float32, scores="softmax",
                        gate_grad=gate_grad)
    _, p, x = _tall_params("softmax", 300)
    tokens, width, slots = TALL["tokens"], 32, 2 * TALL["tokens"]
    rungs = _tall_rungs()
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda q, x: jnp.sum(layer.apply(q, x)[0]), argnums=(0, 1)))(
            p, x).jaxpr

    def kind(name, table, moved):
        rows_of_a_rung = moved[0] in rungs
        if table == (tokens, width) and moved[1:] == (width,):
            return rows_of_a_rung and {
                "gather": "x[token]", "scatter-add": "segment_sum"}.get(name)
        if table == (slots,) and len(moved) == 1:
            return rows_of_a_rung and {
                "gather": "weight",
                "scatter-add": "weight, transposed"}.get(name)
        return None

    kinds = set()
    for eqn, inside in _equations(jaxpr):
        name = eqn.primitive.name
        if "gather" not in name and "scatter" not in name:
            continue
        # what is read or added into, and what moves
        shapes = [v.aval.shape for v in eqn.invars]
        moved = shapes[2] if "scatter" in name else eqn.outvars[0].aval.shape
        what = kind(name, shapes[0], moved)
        assert what, (name, shapes[0], moved, inside)
        kinds.add(what)
    assert kinds == {"x[token]", "segment_sum", "weight",
                     "weight, transposed"}


def test_a_layer_that_holds_every_expert_lowers_no_cond():
    layer = DroplessMoe(32, 16, 16, 2, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 256, 32))
    p = layer.init(jax.random.PRNGKey(8), x)
    text = str(jax.make_jaxpr(jax.grad(
        lambda q: jnp.sum(layer.apply(q, x)[0])))(p))
    assert "cond" not in text and "ragged_dot" in text
    stats = layer.apply(p, x)[1]
    assert int(stats["overflowed"]) == 0
    assert int(stats["rows"]) == int(stats["slots_here"]) == 2 * 256 * 2


# --------------------------------------------------- attention's parts
def test_rotary_turns_pairs_by_position_and_keeps_norms():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 3, 8))
    freqs = plain_frequencies(8, 1e4)
    y = rotary(x, freqs)
    np.testing.assert_allclose(y[:, 0], x[:, 0], atol=1e-6)  # position 0
    np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    # pair (i, i + 4) at position l turns by l * theta**(-2i/8)
    l, i = 3, 1
    ang = l * 1e4 ** (-2 * i / 8)
    np.testing.assert_allclose(
        y[0, l, :, i], x[0, l, :, i] * np.cos(ang)
        - x[0, l, :, i + 4] * np.sin(ang), rtol=1e-5)
    # relative: <rot(q, l), rot(k, m)> depends on l - m only
    q, k = x[:, :1, :1], x[:, 1:2, :1]
    def dot(lq, lk):
        pad = lambda v, l: jnp.pad(v, ((0, 0), (l, 0), (0, 0), (0, 0)))
        return jnp.sum(rotary(pad(q, lq), freqs)[:, lq]
                       * rotary(pad(k, lk), freqs)[:, lk])
    np.testing.assert_allclose(dot(4, 1), dot(7, 4), rtol=1e-5)


def test_mla_rotates_only_its_slice_and_shares_one_rotary_key():
    """The call's q and k, caught at the attention function: the first
    ``nope`` lanes carry no position, the rotary key is the same for every
    head, and the whole agrees with the reference's MLA."""
    seen = {}

    def catch(q, k, v, causal=True):
        seen.update(q=q, k=k, v=v)
        from mmlspark_tpu.parallel.sequence import full_attention
        return full_attention(q, k, v, causal=causal)
    attn = MlaAttention(32, 2, 24, 16, 12, 4, 16, dtype=jnp.float32,
                        attention_fn=catch)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, 32))
    p = attn.init(jax.random.PRNGKey(2), x)
    y = attn.apply(p, x)
    assert seen["q"].shape == seen["k"].shape == seen["v"].shape \
        == (1, 6, 2, 16)
    np.testing.assert_array_equal(seen["k"][:, :, 0, 12:],
                                  seen["k"][:, :, 1, 12:])
    assert not np.allclose(seen["k"][:, :, 0, :12], seen["k"][:, :, 1, :12])
    # the same tokens one position later: only the rotary lanes move
    attn.apply(p, jnp.concatenate([x[:, :1], x], 1))
    q1 = seen["q"][:, 1:]
    attn.apply(p, x)
    np.testing.assert_allclose(q1[..., :12], seen["q"][..., :12], atol=1e-6)
    assert not np.allclose(q1[..., 12:], seen["q"][..., 12:])
    mm = lambda eq, a, b: jnp.einsum(eq, a, b)
    want = ref._mla(ref.dims(CFG), mm, p["params"], x[0])
    np.testing.assert_allclose(y[0], want, rtol=1e-5, atol=1e-6)


def test_unequal_query_and_value_heads_are_taken_as_they_are():
    """Up to PR 50 the part refused values narrower than its keys
    (``attention_fn(q, k, v)`` took one head width); since PR 51 the
    attention call takes v's own last axis: keys of 16 over values of 8,
    the output projection reading ``heads x v_dim`` channels, and the
    result is the masked dense product's."""
    attn = MlaAttention(32, 2, 24, 16, 12, 4, 8, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, 32))
    p = attn.init(jax.random.PRNGKey(0), x)
    assert p["params"]["attn_out"]["kernel"].shape == (2 * 8, 32)
    assert p["params"]["attn_key_value_b"]["kernel"].shape == (16, 2 * 20)
    seen = []

    def spy(q, k, v, causal):
        seen.append((q.shape, k.shape, v.shape))
        s = jnp.einsum("blhd,bkhd->bhlk", q, k) / np.sqrt(q.shape[-1])
        s = jnp.where(jnp.tril(jnp.ones((6, 6), bool)), s, -jnp.inf)
        return jnp.einsum("bhlk,bkhd->blhd", jax.nn.softmax(s, -1), v)
    want = attn.clone(attention_fn=spy).apply(p, x)
    assert seen == [((1, 6, 2, 16), (1, 6, 2, 16), (1, 6, 2, 8))]
    np.testing.assert_allclose(attn.apply(p, x), want, rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------ the loss
@pytest.mark.parametrize("chunk", [5, 8, 64])
def test_chunked_cross_entropy_is_the_plain_one(chunk):
    key = jax.random.PRNGKey(0)
    h = jax.random.normal(key, (24, 16))
    w = jax.random.normal(jax.random.fold_in(key, 1), (16, 40))
    t = jax.random.randint(jax.random.fold_in(key, 2), (24,), 0, 40)
    m = (jnp.arange(24) % 5 != 0).astype(jnp.float32)

    def plain(h, w):
        return jnp.sum(optax.softmax_cross_entropy_with_integer_labels(
            h @ w, t) * m)

    def chunked(h, w):
        return chunked_cross_entropy(h, w, t, m, chunk, jnp.float32)
    np.testing.assert_allclose(chunked(h, w), plain(h, w), rtol=1e-6)
    for g, want in zip(jax.grad(chunked, (0, 1))(h, w),
                       jax.grad(plain, (0, 1))(h, w)):
        np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-6)


def test_next_token_loss_targets_one_and_two_ahead():
    B, L, D, V = 2, 6, 8, 12
    key = jax.random.PRNGKey(0)
    out = {"hidden": jax.random.normal(key, (B, L, D)),
           "mtp_hidden": jax.random.normal(jax.random.fold_in(key, 1),
                                           (B, L, D))}
    w = jax.random.normal(jax.random.fold_in(key, 2), (D, V))
    tokens = jax.random.randint(jax.random.fold_in(key, 3), (B, L), 0, V)
    loss, aux = next_token_loss(out, w, tokens, mtp_weight=0.3, chunk=4,
                                dtype=jnp.float32)
    ce = optax.softmax_cross_entropy_with_integer_labels
    main = ce(out["hidden"][:, :-1] @ w, tokens[:, 1:]).mean()
    mtp = ce(out["mtp_hidden"][:, :-2] @ w, tokens[:, 2:]).mean()
    np.testing.assert_allclose(aux["loss.main"], main, rtol=1e-6)
    np.testing.assert_allclose(aux["loss.mtp"], mtp, rtol=1e-6)
    np.testing.assert_allclose(loss, main + 0.3 * mtp, rtol=1e-6)
    only, aux = next_token_loss({"hidden": out["hidden"]}, w, tokens,
                                chunk=4, dtype=jnp.float32)
    assert set(aux) == {"loss.main"}
    np.testing.assert_allclose(only, main, rtol=1e-6)


def test_required_flops_follow_the_issue_count():
    import json
    with open(Path(__file__).resolve().parent.parent / "benchmark"
              / "configs" / "glm-4.7-flash.json") as f:
        cfg = json.load(f)
    per_token = ref.train_flops_per_item(cfg, 4096) / 4096
    assert 2.85e9 < per_token < 2.89e9            # "about 2.87 GFLOP"
    parts = ref._fwd_flops_per_token(cfg, 4096)
    assert 0.25 < parts["attention"] / parts["total"] < 0.27
    n = sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        ref.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))
    assert 706.0e6 < n < 707.0e6                  # 706.5M parameters here


def test_parameter_names_fall_under_the_sharding_rules_that_exist(params):
    """No new rule: the names were chosen for ``DEFAULT_RULES``. Expert
    banks lead with the ``expert`` axis, projections and feed-forward
    matrices split over ``tensor`` on the side their rule names, the
    router, its bias and every norm scale stay whole."""
    from jax.sharding import PartitionSpec as P
    from mmlspark_tpu.parallel.mesh import MeshSpec, make_mesh
    from mmlspark_tpu.parallel.sharding import param_shardings
    mesh = make_mesh(MeshSpec(data=1, expert=4, tensor=2), jax.devices())
    spec = {jax.tree_util.keystr(k): v.spec for k, v in
            jax.tree_util.tree_leaves_with_path(
                param_shardings(params, mesh))}
    ffn = "['params']['block1']['ffn']"
    attn = "['params']['block1']['attn']"
    assert spec[ffn + "['experts_gate']"] == P("expert", None, "tensor")
    assert spec[ffn + "['experts_up']"] == P("expert", None, "tensor")
    assert spec[ffn + "['experts_down']"] == P("expert", "tensor", None)
    whole = lambda spec: all(axis is None for axis in spec)
    assert whole(spec[ffn + "['router']['kernel']"])
    assert whole(spec[ffn + "['router_bias']"])
    assert spec[ffn + "['shared']['mlp_gate']['kernel']"] == P(None, "tensor")
    assert spec[ffn + "['shared']['mlp_down']['kernel']"] == P("tensor", None)
    for name in ("attn_query_a", "attn_query_b", "attn_key_value_a",
                 "attn_key_value_b"):
        assert spec[attn + f"['{name}']['kernel']"] == P(None, "tensor"), name
    assert spec[attn + "['attn_out']['kernel']"] == P("tensor", None)
    assert whole(spec[attn + "['query_norm']['scale']"])
    assert spec["['params']['lm_head']['kernel']"] == P(None, "tensor")
    assert spec["['params']['token_embedding']['embedding']"] \
        == P("tensor", None)
    assert spec["['params']['block0']['ffn']['mlp_up']['kernel']"] \
        == P(None, "tensor")
