"""``olmo_hybrid`` at its tiny preset against the plain reference
(``benchmark/references/olmo_hybrid.py``), and the parts it brought: the
gated delta rule with ``beta`` in (0, 2) at a state that is twice as wide
as it is high, softmax attention with a norm over the whole q and the
whole k projection and no positions, a block that norms each half's
OUTPUT, a list of kept names that a family may shorten. float32 on the
CPU.

Tolerances: both sides compute in float32 on one backend, so they differ
only by the order of additions (chunked products against a token-by-token
scan; a chunked loss against whole logits): 1e-5 relative on logits and
losses, 1e-4 on gradients, 2e-3 on the norm of three Adam steps (``g /
(sqrt(v) + eps)`` amplifies a relative gradient error where ``g`` is near
zero; ``A_log`` and ``dt_bias``, a few numbers a layer whose gradients lie
under ``eps``, against a thousandth of the median leaf's move). The rule
alone, at ``beta`` up to 2: 5e-5 of the largest element, values and
gradients (``I + A`` now has entries up to 2 under the diagonal, and its
inverse entries that grow with the chunk: the products are exact in
algebra and larger in magnitude, ``tests/test_qwen3_next.py`` holds the
same form to 2e-5 at ``beta`` under 1).
"""
import inspect
import itertools
import json
import sys
from pathlib import Path

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark.references import olmo_hybrid as ref  # noqa: E402
from mmlspark_tpu.models.zoo import build_model, decoder  # noqa: E402
from mmlspark_tpu.models.zoo.decoder import (  # noqa: E402
    OLMO_HYBRID_7B_LAYERS, PartsBlock)
from mmlspark_tpu.models.zoo.parts import (  # noqa: E402
    ATTN_QKV, DELTA_NET_QKVZ, MAMBA2_IN, MLP_GATE_UP, SELECTION,
    SHORT_CONV_IN,
    GatedDeltaNet, GroupedAttention, RMSNorm, SwiGluMlp)
from mmlspark_tpu.observability import metrics as obsmetrics  # noqa: E402
from mmlspark_tpu.ops import linear_attention as la  # noqa: E402
from mmlspark_tpu.ops.pallas_attention import FLASH_RESIDUALS  # noqa: E402
from mmlspark_tpu.ops.pallas_delta_rule import (  # noqa: E402
    DELTA_CHUNK_TILES)
from mmlspark_tpu.train.lm_loss import next_token_loss  # noqa: E402

KINDS = ("linear_attention",) * 3 + ("full_attention",)
CFG = dict(hidden_size=32, num_hidden_layers=4, layer_types=list(KINDS),
           num_attention_heads=4, num_key_value_heads=4,
           linear_num_key_heads=4, linear_num_value_heads=4,
           linear_key_head_dim=8, linear_value_head_dim=16,
           linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
           intermediate_size=48, vocab_size=96, rms_norm_eps=1e-6,
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
    return build_model("olmo_hybrid", **ref.zoo_args(cfg, 64))["module"]


def _loss_fn(module, chunk=16):
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
    """Every parameter away from its init: scales off 1, matrices large
    enough that q, k and the gates are no near-constants."""
    name = jax.tree_util.keystr(path)
    if "kernel" in name:
        return 8.0 * v
    if "scale" in name:
        return v + jnp.linspace(-0.5, 0.5, v.size).reshape(v.shape)
    return v


@pytest.fixture(scope="module")
def params():
    return jax.tree_util.tree_map_with_path(
        lambda path, v: _away(path, v) if "scale" in jax.tree_util.keystr(
            path) else v, ref.init_params(CFG, jax.random.PRNGKey(7)))


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


# ------------------------------------------- the rule at beta up to two
def _rule_inputs(L, decay, B=2, H=3, dk=8, dv=16, seed=0):
    """``beta`` within 2e-3 of 2 on a third of the tokens (the transition's
    eigenvalue along k near -1), spread over (0, 2) on the rest; ``decay``
    "init" (the initialiser's range) or "strong" (at least -20 a token: a
    quotient of exponentials would be 0 / 0)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    q = la.l2_normalize(jax.random.normal(ks[0], (B, L, H, dk)))
    k = la.l2_normalize(jax.random.normal(ks[1], (B, L, H, dk)))
    v = jax.random.normal(ks[2], (B, L, H, dv))
    noise = jax.random.normal(ks[3], (B, L, H))
    if decay == "init":
        g = -jnp.linspace(1e-3, 16.0, H) * jax.nn.softplus(1.0 + noise)
    else:
        g = -20.0 - 20.0 * jax.nn.softplus(noise)
    b = jax.random.normal(ks[4], (B, L, H))
    b = jnp.where(jax.random.uniform(ks[5], b.shape) < 1 / 3, 7.0 + b, b)
    return (q, k, v, g, 2.0 * jax.nn.sigmoid(b)), jax.random.normal(
        ks[6], v.shape)


@pytest.mark.parametrize("decay", ["init", "strong"])
@pytest.mark.parametrize("length,chunk", [(64, 16), (50, 16), (130, 64)])
def test_chunked_rule_at_beta_near_two_is_the_token_by_token_rule(
        length, chunk, decay):
    args, w = _rule_inputs(length, decay)
    assert float(args[4].max()) > 1.998 and float(args[4].min()) < 0.5

    def run(impl):
        def f(*a):
            return la.gated_delta_rule(*a, chunk=chunk, impl=impl)
        return jax.jit(lambda *a: (f(*a),) + jax.grad(
            lambda *b: jnp.sum(f(*b) * w), argnums=(0, 1, 2, 3, 4))(*a))(
                *args)
    want, got = run("recurrent"), run("chunked")
    assert got[0].shape == args[2].shape and got[0].dtype == jnp.float32
    for name, a, b in zip("o q k v g beta".split(), got, want):
        assert bool(jnp.isfinite(a).all()), name
        np.testing.assert_allclose(
            a, b, atol=5e-5 * float(jnp.abs(b).max()) + 1e-9, err_msg=name)


def test_a_token_at_beta_two_mirrors_the_state_along_its_key():
    """The four lines by hand at ``beta`` = 2 and no decay: the transition
    ``I - 2 k k^T`` of a unit key is a reflection (eigenvalue -1 along k),
    which ``beta`` in (0, 1) cannot reach."""
    k = la.l2_normalize(jnp.asarray([[1.0, 2.0, -1.0, 0.5]]))[0]
    S = jax.random.normal(jax.random.PRNGKey(0), (4, 6))
    z = jnp.zeros
    o = la.gated_delta_rule(
        *(x[None, :, None] for x in (
            jnp.stack([z(4), k]), jnp.stack([z(4), k]),
            jnp.stack([z(6), z(6)]))),
        z((1, 2, 1)), jnp.asarray([[[0.0], [2.0]]]), impl="recurrent")
    assert float(jnp.abs(o).max()) == 0.0       # v = 0 from a zero state
    mirrored = S - 2.0 * jnp.outer(k, k) @ S
    np.testing.assert_allclose(k @ mirrored, -(k @ S), rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------ the parts
def test_delta_net_layer_at_beta_scale_two_is_the_reference_layer():
    d = ref.dims(CFG)
    layer = GatedDeltaNet(32, 4, 4, 8, 16, 4, 1e-6, 8, jnp.float32,
                          beta_scale=2.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 21, 32))
    p = jax.tree_util.tree_map_with_path(
        _away, layer.init(jax.random.PRNGKey(2), x))
    got = jax.jit(layer.apply)(p, x)
    want = jax.jit(jax.vmap(lambda row: ref._delta_net(
        d, _mm, p["params"], row)))(x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # the scale is used: at 1 the layer is Qwen3-Next's, and another
    plain = GatedDeltaNet(32, 4, 4, 8, 16, 4, 1e-6, 8, jnp.float32).apply(
        p, x)
    assert float(jnp.abs(plain - got).max()) > 1e-3
    np.testing.assert_allclose(plain, jax.vmap(lambda row: ref._delta_net(
        dict(d, beta_scale=1.0), _mm, p["params"], row))(x), rtol=1e-4,
        atol=1e-5)


def test_attention_with_a_norm_over_the_whole_projection_and_no_positions():
    """The norm's mean square is over all ``heads x head_dim`` channels of
    q (and of k), not a head's; a token's output does not depend on the
    order of the tokens before it."""
    d = ref.dims(CFG)
    layer = GroupedAttention(32, 4, 4, 8, None, jnp.float32,
                             qk_norm_eps=1e-6)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 32))
    p = jax.tree_util.tree_map_with_path(
        _away, layer.init(jax.random.PRNGKey(2), x))
    assert p["params"]["query_norm"]["scale"].shape == (32,)
    assert p["params"]["key_norm"]["scale"].shape == (32,)
    got = jax.jit(layer.apply)(p, x)
    want = jax.jit(jax.vmap(lambda row: ref._attention(
        d, _mm, p["params"], row)))(x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    bare = GroupedAttention(32, 4, 4, 8, None, jnp.float32)
    assert set(bare.init(jax.random.PRNGKey(2), x)["params"]) == {
        "attn_query", "attn_key", "attn_value", "attn_out"}
    without = bare.apply({"params": {k: v for k, v in p["params"].items()
                                     if "norm" not in k}}, x)
    assert float(jnp.abs(without - got).max()) > 1e-3
    later = x.at[:, 5:].set(0.0)                        # causal
    np.testing.assert_allclose(layer.apply(p, later)[:, :5], got[:, :5],
                               rtol=1e-5, atol=1e-6)
    swapped = x.at[:, :2].set(x[:, 1::-1])              # no positions
    np.testing.assert_allclose(layer.apply(p, swapped)[:, 2:], got[:, 2:],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("split", [False, True], ids=["whole", "halves"])
def test_a_block_that_norms_its_outputs_is_its_two_equations(split):
    """``h = x + norm1(mixer(x))``, ``y = h + norm2(mlp(h))``, by hand from
    the parts, recomputed whole or in halves; without ``norm_output`` the
    block is the one it was."""
    def parts(norm_output):
        return decoder._remat_block(
            lambda n: RMSNorm(1e-6, name=n),
            lambda n: GroupedAttention(16, 4, 4, 4, None, jnp.float32,
                                       name=n),
            lambda n: SwiGluMlp(16, 24, jnp.float32, name=n), None,
            split=split, norm_output=norm_output)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 16))
    p = jax.tree_util.tree_map_with_path(
        _away, parts(True).init(jax.random.PRNGKey(2), x))
    q = p["params"]
    assert set(q) == {"norm1", "attn", "norm2", "ffn"}

    def sub(module, name, v):
        return module.apply({"params": q[name]}, v)
    mixer = GroupedAttention(16, 4, 4, 4, None, jnp.float32)
    mlp, norm = SwiGluMlp(16, 24, jnp.float32), RMSNorm(1e-6)
    got, stats = parts(True).apply(p, x)
    h = x + sub(norm, "norm1", sub(mixer, "attn", x))
    want = h + sub(norm, "norm2", sub(mlp, "ffn", h))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert stats == {}
    before, _ = parts(False).apply(p, x)
    h = x + sub(mixer, "attn", sub(norm, "norm1", x))
    np.testing.assert_allclose(
        before, h + sub(mlp, "ffn", sub(norm, "norm2", h)), rtol=1e-5,
        atol=1e-6)
    assert float(jnp.abs(before - got).max()) > 1e-3
    assert PartsBlock.__dataclass_fields__["norm_output"].default is False


# ------------------------------------------------- the model as a whole
def test_reference_tree_is_the_programs_tree_and_layer_types(monkeypatch,
                                                             params):
    module = _module()
    own = module.init(jax.random.PRNGKey(0), jnp.zeros((1, LEN), jnp.int32))
    shapes = lambda t: jax.tree_util.tree_map(lambda x: x.shape, t)
    assert shapes(own) == shapes(params)
    assert shapes(own) == ref.param_shapes(CFG)
    p = own["params"]
    assert {"lm_head", "token_embedding", "final_norm"} <= set(p)   # untied
    for i, kind in enumerate(KINDS):
        blk = p[f"block{i}"]
        assert set(blk) == {"norm1", "attn", "norm2", "ffn"}
        assert set(blk["ffn"]) == {"mlp_gate", "mlp_up", "mlp_down"}
        if kind == "linear_attention":
            assert set(blk["attn"]) == {
                "attn_qkvz", "attn_ba", "conv_kernel", "A_log", "dt_bias",
                "gate_norm", "attn_out"}
            assert blk["attn"]["gate_norm"]["scale"].shape == (16,)
        else:
            assert set(blk["attn"]) == {
                "attn_query", "attn_key", "attn_value", "attn_out",
                "query_norm", "key_norm"}
    assert ref.routed_blocks(CFG) == []
    # the published list: thirty-two layers, 24 : 8, full attention last
    # of every four
    assert len(OLMO_HYBRID_7B_LAYERS) == 32
    assert [i for i, k in enumerate(OLMO_HYBRID_7B_LAYERS)
            if k == "full_attention"] == list(range(3, 32, 4))
    whole = build_model("olmo_hybrid")["module"]
    assert _defaults("olmo_hybrid")["layer_types"] == OLMO_HYBRID_7B_LAYERS
    assert [i for i in range(32) if whole.mixers[i] is whole.mixers[3]] \
        == list(range(3, 32, 4)) and len(set(whole.ffns)) == 1
    assert whole.norm_output and whole.split and not whole.tied
    with pytest.raises(
            ValueError,
            match="'linear_attention' or 'full_attention' a layer"):
        build_model("olmo_hybrid_tiny", layer_types=("linear_attention",
                                                     "mamba"))
    with pytest.raises(ValueError):
        ref.dims(dict(CFG, num_hidden_layers=3))
    with pytest.raises(ValueError):
        ref.dims(dict(CFG, num_key_value_heads=2))
    # both inits: A_log in log(1e-3 .. 16), dt_bias 1, plain scales at 1
    for tree in (p, ref.init_params(CFG, jax.random.PRNGKey(7))["params"]):
        mixer = tree["block0"]["attn"]
        a = np.exp(np.asarray(mixer["A_log"]))
        assert np.all((a >= 1e-3 * 0.999) & (a <= 16.0 * 1.001))
        assert np.all(np.asarray(mixer["dt_bias"]) == 1)
        for name in ("norm1", "norm2"):
            assert np.all(np.asarray(tree["block0"][name]["scale"]) == 1)
        assert np.all(np.asarray(tree["block3"]["attn"]["query_norm"][
            "scale"]) == 1)
    # the tiny preset is this file's configuration
    seen, entry = [], _defaults("olmo_hybrid")
    monkeypatch.setattr(decoder, "olmo_hybrid", lambda **kw: seen.append(kw))
    build_model("olmo_hybrid_tiny")
    assert [{**entry, **kw} for kw in seen] == [
        {**entry, **ref.zoo_args(CFG, 64)}]


def test_logits_match_the_reference(params):
    tokens = _tokens(1)[0]
    got = _apply(params, jnp.asarray(tokens))
    assert got.shape == (ROWS, LEN, CFG["vocab_size"])
    assert got.dtype == jnp.float32
    for b in range(ROWS):
        want = _ref_logits(params, jnp.asarray(tokens[b]))
        np.testing.assert_allclose(got[b], want, rtol=1e-5, atol=1e-6)
    # beta's factor of two is in the result
    plain = dict(CFG, linear_allow_neg_eigval=False)
    other = ref.logits(plain, params, jnp.asarray(tokens[0]))
    assert float(jnp.abs(other - got[0]).max()) > 1e-4
    out = _module().apply(params, jnp.asarray(tokens), hidden=True)
    assert out["stats"] == {}
    np.testing.assert_allclose(
        out["hidden"] @ params["params"]["lm_head"]["kernel"], got,
        rtol=1e-5, atol=1e-6)


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


def test_the_references_walk_by_halves_gives_jax_grads_gradient():
    """``train_reference`` never holds the gradient whole, nor a block's:
    its first gradient, gathered half by half, is ``jax.grad`` of
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
    assert list(got["delta_norms"]) == [name for name in got["grad_norms"]]
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
    rule = {k: obsmetrics.counter(f"linear_attention.{k}").value for k in (
        "calls.chunked", "rule_calls.delta", "chunk_calls.xla",
        "chunk_calls.pallas")}
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
    # three Gated DeltaNet layers a trace (one for the aux keys, one for
    # the step), the tiny head widths on XLA's batched form
    after = {k: obsmetrics.counter(f"linear_attention.{k}").value
             for k in rule}
    assert {k: after[k] - rule[k] for k in rule} == {
        "calls.chunked": 6, "rule_calls.delta": 6, "chunk_calls.xla": 6,
        "chunk_calls.pallas": 0}
    moved = ref.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, state["params"], start))
    assert list(moved) == list(want["delta_norms"])
    # A_log and dt_bias: at this init their gradients lie under Adam's eps
    # (the leaf moves by 1e-4 where a matrix moves by 1), where g / (sqrt(v)
    # + eps) turns a rounding into the step: held, as the benchmark's
    # comparison holds a leaf, against the median leaf's move
    floor = 1e-3 * float(np.median(list(want["delta_norms"].values())))
    for k, v in moved.items():
        few = any(n in k for n in ("A_log", "dt_bias"))
        np.testing.assert_allclose(
            float(v), want["delta_norms"][k], rtol=3e-2 if few else 2e-3,
            atol=floor if few else 1e-12, err_msg=k)


# ---------------------------------------- what a block keeps, and how
def _hidden_grads(params, tokens):
    module = _module()
    return jax.jit(jax.grad(lambda p: jnp.sum(jnp.sin(module.apply(
        p, tokens, hidden=True)["hidden"]))))(params)


@pytest.fixture(scope="module")
def nothing_recomputed(params):
    """The gradients of the model with no block recomputed."""
    real = nn.remat
    try:
        nn.remat = lambda cls, **kw: cls
        return _hidden_grads(params, jnp.asarray(_tokens(4)[0]))
    finally:
        nn.remat = real


_NAMES = (FLASH_RESIDUALS, MLP_GATE_UP, DELTA_CHUNK_TILES,
          DELTA_NET_QKVZ, ATTN_QKV)     # ``_remat_block``'s one list, but
# for the names this family has no value of (``lfm2_moe``'s and
# ``granite_hybrid``'s mixers', ``keye_vl2``'s selection)
_NOT_HERE = (SHORT_CONV_IN, MAMBA2_IN, SELECTION)


@pytest.mark.parametrize("split", [True, False], ids=["halves", "whole"])
@pytest.mark.parametrize("keep", [
    names for n in range(len(_NAMES) + 1)
    for names in itertools.combinations(_NAMES, n)],
    ids=lambda names: "+".join(n.split("_")[0] for n in names) or "none")
def test_every_sub_list_of_kept_names_gives_the_same_gradients(
        monkeypatch, params, nothing_recomputed, keep, split):
    """A family hands ``_remat_block`` the names it lets go; whatever the
    sub-list it keeps, and whether the block is recomputed in halves or
    whole, the gradients are those of a block that recomputes nothing."""
    seen = []
    real_policy = jax.checkpoint_policies.save_only_these_names

    def spy(*names):
        seen.append(names)
        return real_policy(*names)
    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                        spy)
    real = decoder._remat_block
    monkeypatch.setattr(
        decoder, "_remat_block", lambda *a, **kw: real(*a, **dict(
            kw, split=split,
            let_go=tuple(n for n in _NAMES if n not in keep))))
    got = _hidden_grads(params, jnp.asarray(_tokens(4)[0]))
    assert {tuple(sorted(names)) for names in seen} == {
        tuple(sorted(keep + _NOT_HERE))}
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(nothing_recomputed)):
        np.testing.assert_allclose(
            g, w, rtol=1e-6, atol=1e-6 * float(jnp.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))


def test_this_family_lets_go_of_names_of_the_one_list():
    let_go = build_model("olmo_hybrid")["module"].let_go
    assert let_go == (MLP_GATE_UP, ATTN_QKV) and set(let_go) < set(_NAMES)
    assert build_model("olmo_hybrid_tiny")["module"].let_go == let_go
    # the five other families let go of none and keep the whole list
    for family in ("glm4_moe_lite", "qwen3_next", "granite_hybrid",
                   "lfm2_moe", "laguna"):
        assert build_model(family)["module"].let_go == ()


# -------------------------------------------- the benchmark's own counts
def _cell_config():
    with open(REPO / "benchmark" / "configs" / "olmo-hybrid-7b.json") as f:
        return json.load(f)


def _count(tree):
    return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple)))


def test_parameter_counts_of_the_published_defaults_at_the_cells_cut():
    """ISSUE 38's arithmetic, from ``jax.eval_shape`` of the zoo entry's
    own init and from the reference's shapes."""
    cfg = _cell_config()
    module = build_model("olmo_hybrid", vocab=cfg["vocab_size"],
                         layer_types=OLMO_HYBRID_7B_LAYERS[:4])["module"]
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)))["params"]
    own = jax.tree_util.tree_map(lambda x: x.shape, shapes)
    assert own == ref.param_shapes(cfg)["params"]
    assert _count(own) == 928_862_196
    assert _count(own["block0"]) == 215_570_172
    assert _count(own["block0"]["attn"]) == 88_750_332
    assert _count(own["block0"]["attn"]["attn_qkvz"]) == 66_355_200
    assert _count(own["block0"]["attn"]["attn_ba"]) == 230_400
    assert _count(own["block0"]["attn"]["conv_kernel"]) == 46_080
    assert _count(own["block0"]["attn"]["attn_out"]) == 22_118_400
    assert _count(own["block0"]["ffn"]) == 126_812_160
    assert _count(own["block3"]) == 185_809_920
    assert _count(own["block3"]["attn"]) == 58_990_080
    assert _count(own["token_embedding"]) == _count(own["lm_head"]) \
        == 48_168_960
    assert "928,862,196" in cfg["deployment"]["parameters_here"]


def test_required_flops_follow_the_counts():
    """ISSUE 38 counts 1,842 MFLOP a token forward: 3 x 437 (Gated DeltaNet
    layers: 177.5 the projections, 6.1 the chunked rule, 253.6 the
    feed-forward part), 434.5 (the attention layer at 8,192: 118.0 + 62.9
    the causal half), 96.3 (the head); 45.3 TFLOP a row trained."""
    cfg = _cell_config()
    parts = ref._fwd_flops_per_token(cfg, 8192)
    rule = ref.delta_rule_flops_per_token(ref.dims(cfg), 64)
    assert parts["mlp"] == 2 * 3 * 3840 * 11008
    assert rule["total"] == 30 * (2 * 64 * (3 * 96 + 2 * 192 + 64)
                                 + 3 * 2 * 96 * 192) == 6_144_000
    assert 177.3e6 < parts["delta_net"] - rule["total"] < 177.9e6
    assert 436.5e6 < parts["delta_net"] + parts["mlp"] < 437.5e6
    assert parts["attention"] == pytest.approx(
        2 * 4 * 3840 * 3840 + 2 * 4096 * 30 * 2 * 128)
    assert 434e6 < parts["attention"] + parts["mlp"] < 435e6
    assert parts["head"] == 2 * 3840 * 12544
    assert 1842e6 < parts["total"] < 1843e6
    assert ref.train_flops_per_item(cfg, 8192) == pytest.approx(
        3 * 8192 * parts["total"])
    assert 45.2e12 < ref.train_flops_per_item(cfg) < 45.4e12
    assert 0.29 < 3 * parts["delta_net"] / parts["total"] < 0.31
    # one step of the cell: 3 layers x 1 row x 30 heads x 128 chunks
    call = ref.kernel_calls(cfg, 1, 8192)
    shape = {"rows": 1, "len": 8192, "heads": 30, "key_dim": 96,
             "value_dim": 192, "chunk": 64, "layers": 3}
    assert call == {
        "flash_fwd": {"rows": 1, "len": 8192, "heads": 30, "head_dim": 128},
        "delta_rule": shape, "delta_chunk": shape}
    chunks = 3 * 1 * 30 * 128
    flops, nbytes = ref.delta_rule_cost(shape)
    assert flops == pytest.approx(chunks * 3 * 4 * 64 * 96 * 192)
    assert flops == pytest.approx(3 * 3 * 8192 * rule["walk"])
    assert 6.4e9 < nbytes < 6.6e9
    flops, nbytes = ref.delta_chunk_cost(shape)
    # the rule without its walk, forward and backward
    assert flops == pytest.approx(
        3 * 3 * 8192 * (rule["total"] - rule["walk"]))
    forward = (2 * 64 * 96 * 4 + 64 * 192 * 2 + 2 * 64 * 4) \
        + (3 * 64 * 96 * 2 + 64 * 192 * 4 + 64 * 64 * 2) \
        + (64 * 96 * 2 + 64 * 64 * 2) + (96 * 192 * 4 + 64 * 192 * 4) \
        + 64 * 192 * 4
    assert nbytes == pytest.approx(chunks * 3 * forward)
    # bandwidth-bound on a v5e by a factor of about ten
    assert 8 < (nbytes / 819e9) / (flops / 197e12) < 12


def test_configuration_holds_the_catalogued_numbers():
    """Every number of the catalogue's row under its own key, but for the
    three reduced ones; no width among those."""
    cfg = _cell_config()
    published = dict(
        vocab_size=100352, hidden_size=3840, intermediate_size=11008,
        num_hidden_layers=32, num_attention_heads=30,
        num_key_value_heads=30, max_position_embeddings=65536,
        rms_norm_eps=1e-6, layer_types=list(OLMO_HYBRID_7B_LAYERS),
        linear_num_key_heads=30, linear_num_value_heads=30,
        linear_key_head_dim=96, linear_value_head_dim=192,
        linear_conv_kernel_dim=4)
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == sorted(cfg["reduced"]) == [
        "layer_types", "num_hidden_layers", "vocab_size"]
    assert set(cfg["reduced_from"]) == set(cfg["reduced"])
    for flag, value in dict(
            model_type="olmo_hybrid", hidden_act="silu", attention_bias=False,
            tie_word_embeddings=False, linear_allow_neg_eigval=True,
            rope_parameters={"rope_theta": None}).items():
        assert cfg[flag] == value, flag
    dep = cfg["deployment"]
    assert dep["chips_sharing_each_layer"] == 1
    assert dep["pipeline_stages"] * cfg["num_hidden_layers"] \
        == dep["num_hidden_layers_published"] == 32
    assert cfg["vocab_size"] * dep["chips_sharing_each_table"] \
        == dep["vocab_size_published"] == 100352
    assert cfg["layer_types"] == list(OLMO_HYBRID_7B_LAYERS[:4])
    assert (cfg["runner"], cfg["reference"]) == ("train_lm_dense",
                                                 "olmo_hybrid")
    assert cfg["program"] == {"zoo": "olmo_hybrid", "loss_chunk": 2048,
                              "chunk": 64}
    assert {"norm_wiring", "qk_norm", "positions", "attention_head_dim",
            "projection_columns", "recomputation"} <= set(cfg["assumed"])
    # the zoo entry's defaults are the published numbers
    entry = _defaults("olmo_hybrid")
    uncut = dict(cfg, **{k: published[k] for k in cfg["reduced"]})
    args = ref.zoo_args(uncut, 8192)
    args.pop("max_len")
    for k, v in args.items():
        assert entry[k] == v, k
    assert entry["head_dim"] * entry["heads"] == entry["dim"]
    # the cell, its traffic and its three metrics are data files
    with open(REPO / "BENCHMARK.json") as f:
        bench = json.load(f)
    cell = [w for w in bench["workloads"]
            if w["name"] == "olmo-hybrid-7b-train-8k"]
    assert cell and cell[0]["config"] == "olmo-hybrid-7b" \
        and cell[0]["traffic"] == "train-lm-8k" and cell[0]["chips"] == 1
    for name in ("linattn.state_walk_ms", "linattn.state_walk_roofline",
                 "linattn.chunk_kernel_roofline"):
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
    assert spec[mixer + "['attn_qkvz']['kernel']"] == P(None, "tensor")
    assert spec[mixer + "['attn_out']['kernel']"] == P("tensor", None)
    for name in ("['conv_kernel']", "['A_log']", "['dt_bias']",
                 "['gate_norm']['scale']"):
        assert whole(spec[mixer + name]), name
    soft = "['params']['block3']['attn']"
    for name in ("attn_query", "attn_key", "attn_value"):
        assert spec[soft + f"['{name}']['kernel']"] == P(None, "tensor"), name
    assert spec[soft + "['attn_out']['kernel']"] == P("tensor", None)
    for name in ("query_norm", "key_norm"):
        assert whole(spec[soft + f"['{name}']['scale']"]), name
    ffn = "['params']['block3']['ffn']"
    assert spec[ffn + "['mlp_gate']['kernel']"] == P(None, "tensor")
    assert spec[ffn + "['mlp_down']['kernel']"] == P("tensor", None)
    assert whole(spec["['params']['block3']['norm1']['scale']"])
    assert spec["['params']['lm_head']['kernel']"] == P(None, "tensor")
