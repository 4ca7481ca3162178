"""``kimi_linear`` at a toy size against the plain reference
(``benchmark/references/kimi_linear.py``), and what it brought: the gated
delta rule with a decay a key CHANNEL (Kimi Delta Attention) in three
forms, its chunk-local half as Pallas calls, a mixer part around it,
latent attention without a query rank and without positions whose keys are
wider than its values, and the flash kernels taught a second head width.
float32 on the CPU.

Tolerances: both sides compute in float32 on one backend, so they differ
only by the order of additions (chunked products against a token-by-token
loop, grouped products and a chunked loss against dense loops and whole
logits): 1e-5 relative on logits and losses, 1e-4 on gradients, 2e-3 on
the norm of three Adam steps (``g / (sqrt(v) + eps)`` amplifies a relative
gradient error where ``g`` is near zero); the kernels in interpret mode
against the dense forms 2e-5 absolute on unit normal inputs.
"""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark.references import kimi_linear as ref  # noqa: E402
from mmlspark_tpu.models.zoo import build_model  # noqa: E402
from mmlspark_tpu.models.zoo.moe import DroplessMoe  # noqa: E402
from mmlspark_tpu.models.zoo.parts import (  # noqa: E402
    KimiDeltaAttention, MlaAttention, SwiGluMlp)
from mmlspark_tpu.observability import metrics as obsmetrics  # noqa: E402
from mmlspark_tpu.ops import linear_attention as la  # noqa: E402
from mmlspark_tpu.ops import pallas_attention as pa  # noqa: E402
from mmlspark_tpu.ops import pallas_kda  # noqa: E402
from mmlspark_tpu.parallel import sequence  # noqa: E402
from mmlspark_tpu.train.lm_loss import next_token_loss  # noqa: E402

CFG = dict(hidden_size=32, num_hidden_layers=5,
           linear_attn_config={"kda_layers": [1, 2, 3, 5],
                               "full_attn_layers": [4], "num_heads": 2,
                               "head_dim": 8, "short_conv_kernel_size": 4},
           num_attention_heads=2, q_lora_rank=None, kv_lora_rank=16,
           qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
           mla_use_nope=True, intermediate_size=48, first_k_dense_replace=1,
           moe_layer_freq=1, moe_intermediate_size=8, num_shared_experts=1,
           num_experts=8, num_experts_per_token=2,
           routed_scaling_factor=2.446,
           moe_router_activation_func="sigmoid", moe_renormalize=True,
           num_expert_group=1, topk_group=1, num_nextn_predict_layers=0,
           tie_word_embeddings=False, rms_norm_eps=1e-5, vocab_size=96,
           program={"chunk": 8,
                    "zoo_args": {"dtype": jnp.float32, "gate_grad": False}},
           deployment={"num_experts_published": 8, "experts_first": 0})
OPT = dict(learning_rate=1e-2, beta1=0.9, beta2=0.95, eps=1e-8,
           weight_decay=0.1)
ROWS, LEN = 2, 20       # two and a half chunks of 8


def _tokens(seed, steps=1):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], size=(steps, ROWS, LEN)).astype(np.int32)


def _module(cfg=CFG):
    return build_model("kimi_linear", **ref.zoo_args(cfg, 64))["module"]


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
    """Scales off 1, biases off their constants and the gates' matrices
    large enough that decays, betas and gates differ by token and channel,
    so that none is a factor a wrong wiring could hide behind."""
    name = jax.tree_util.keystr(path)
    if "scale" in name or "dt_bias" in name:
        return v + jnp.linspace(-0.5, 0.5, v.size).reshape(v.shape)
    if "router_bias" in name:
        return v + jnp.linspace(-0.02, 0.02, v.size)
    if "router']['kernel" in name or "attn_beta" in name \
            or "attn_decay" in name or "attn_gate" in name:
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


# ------------------------------------------ the rule, a decay a channel
def _rule_inputs(B, L, H, dk, dv, span, seed=0):
    """Unit q and k, normal v, beta in (0, 1) and a decay a channel drawn
    uniformly from ``-span`` to 0 a token."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = la.l2_normalize(jax.random.normal(ks[0], (B, L, H, dk)))
    k = la.l2_normalize(jax.random.normal(ks[1], (B, L, H, dk)))
    v = jax.random.normal(ks[2], (B, L, H, dv))
    g = -span * jax.random.uniform(ks[3], (B, L, H, dk))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, L, H)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (B, L, H, dv))


def _value_and_grads(args, w, **kw):
    def out(*a):
        return la.gated_delta_rule(*a, dtype=jnp.float32, **kw)
    return out(*args), jax.grad(
        lambda *a: jnp.sum(out(*a) * w), argnums=(0, 1, 2, 3, 4))(*args)


@pytest.mark.parametrize("span", [1.0, 30.0], ids=["weak", "to-30-a-token"])
@pytest.mark.parametrize("shape,chunk,executor", [
    ((2, 44, 3, 8, 16), 8, "xla"),          # ragged row, dk != dv
    ((1, 200, 2, 128, 128), 64, "pallas"),  # the calls, interpreted
], ids=["xla", "pallas"])
def test_the_three_forms_of_the_rule_agree_and_so_do_their_gradients(
        shape, chunk, executor, span):
    """Token by token, chunked by XLA's batched products and chunked with
    the chunk-local half in the Pallas calls: one function. At decays down
    to -30 a token a chunk's running sum passes -1900 a channel, where a
    quotient of two exponentials is 0 / 0; every form stays finite and
    they agree, values and all five gradients (the decay's among them)."""
    args, w = _rule_inputs(*shape, span)
    names = [f"linear_attention.kda_chunk_calls.{e}"
             for e in ("pallas", "xla")]
    before = [obsmetrics.counter(n).value for n in names]
    want, want_g = _value_and_grads(args, w, chunk=chunk, impl="recurrent")
    got, got_g = _value_and_grads(args, w, chunk=chunk, impl="chunked")
    took = [obsmetrics.counter(n).value - b for n, b in zip(names, before)]
    assert (took[0] > 0, took[1] > 0) == (executor == "pallas",
                                          executor == "xla")
    assert bool(jnp.isfinite(got).all()) and got.shape == args[2].shape
    np.testing.assert_allclose(got, want, atol=2e-6)
    for name, g, wg in zip("q k v g beta".split(), got_g, want_g):
        assert bool(jnp.isfinite(g).all()), name
        np.testing.assert_allclose(
            g, wg, atol=2e-5 * max(1.0, float(jnp.abs(wg).max())),
            err_msg=name)
    if executor == "pallas":
        # and XLA's chunked form, which the calls are held to, beside them
        q, k, v, g, beta = args
        xla = la._chunked_kda(q * q.shape[-1] ** -0.5, k, v, g, beta, chunk,
                              jnp.float32)
        np.testing.assert_allclose(got, xla, atol=2e-6)


@pytest.mark.parametrize("impl", ["recurrent", "chunked"])
def test_one_decay_in_every_channel_is_the_scalar_rule(impl):
    """``g`` (B, L, H, dk) holding the same number in every channel of a
    head is the rule ``g`` (B, L, H) runs, to rounding: the form is read
    off the shape, the mathematics is one."""
    (q, k, v, _, beta), _ = _rule_inputs(2, 44, 3, 8, 16, 1.0)
    g = -3.0 * jax.random.uniform(jax.random.PRNGKey(1), (2, 44, 3))
    wide = jnp.broadcast_to(g[..., None], q.shape)
    kw = dict(chunk=8, impl=impl, dtype=jnp.float32)
    np.testing.assert_allclose(
        la.gated_delta_rule(q, k, v, wide, beta, **kw),
        la.gated_delta_rule(q, k, v, g, beta, **kw), atol=2e-6)


def test_the_rule_counts_its_kind_and_refuses_what_it_cannot_run():
    (q, k, v, g, beta), _ = _rule_inputs(1, 16, 2, 8, 8, 1.0)
    name = "linear_attention.rule_calls.kda"
    before = obsmetrics.counter(name).value
    la.gated_delta_rule(q, k, v, g, beta, chunk=8)
    assert obsmetrics.counter(name).value == before + 1
    with pytest.raises(ValueError, match="shapes"):     # dk of another width
        la.gated_delta_rule(q, k, v, g[..., :4], beta, chunk=8)
    with pytest.raises(ValueError, match="shapes"):     # grouped key heads
        la.gated_delta_rule(q[:, :, :1], k[:, :, :1], v, g[:, :, :1], beta,
                            chunk=8)
    with pytest.raises(ValueError, match="power of two"):
        la.gated_delta_rule(q, k, v, g, beta, chunk=6, impl="chunked")
    assert pallas_kda.supports(64, 32, 32, 128, 128)
    assert not pallas_kda.supports(64, 16, 32, 128, 128)    # grouped
    assert not pallas_kda.supports(64, 30, 30, 96, 192)     # other widths
    assert not pallas_kda.supports(32, 32, 32, 128, 128)
    assert not pallas_kda.supports(8, 2, 2, 8, 8)           # the tiny preset


def test_the_chunk_calls_carry_names_of_their_own_and_keep_their_tiles():
    """Four Pallas calls under ``kda_chunk_*`` (no ``delta_chunk``, no
    ``flash``, no ``attention`` in them: no accepted pattern reads them),
    the walk under ``kda_state_walk``; the forward's tiles carry
    ``DELTA_CHUNK_TILES``, so a block recomputed under ``_remat_block``'s
    list holds no second forward call."""
    from mmlspark_tpu.ops.pallas_delta_rule import DELTA_CHUNK_TILES
    args, w = _rule_inputs(1, 128, 1, 128, 128, 1.0)

    def loss(*a):
        return jnp.sum(la.gated_delta_rule(*a, dtype=jnp.float32) * w)
    text = jax.jit(jax.grad(loss)).lower(*args).as_text(debug_info=True)
    for name in ("kda_chunk_fwd", "kda_chunk_bwd", "kda_chunk_out",
                 "kda_chunk_out_bwd", "kda_state_walk"):
        assert name in text, name
    assert "delta_chunk_fwd" not in text and "gated_delta_rule/" not in text
    policy = jax.checkpoint_policies.save_only_these_names(
        DELTA_CHUNK_TILES)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        jax.checkpoint(loss, policy=policy)))(*args))
    assert jaxpr.count("name=kda_chunk_fwd") == 1
    assert str(jax.make_jaxpr(jax.grad(jax.checkpoint(loss)))(
        *args)).count("name=kda_chunk_fwd") == 2


# ------------------------------------------------------------ the parts
def test_the_kda_part_is_the_references_layer(params):
    d = ref.dims(CFG)
    layer = KimiDeltaAttention(32, 2, 8, 4, 1e-5, 8, jnp.float32)
    p = params["params"]["block1"]["attn"]
    assert p["dt_bias"].shape == (16,) and p["A_log"].shape == (2,)
    assert p["attn_decay_a"]["kernel"].shape == (32, 8)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, LEN, 32))
    got = layer.apply({"params": p}, x)
    want = jax.jit(lambda x: ref._kda(d, _mm, p, x))
    for b in range(2):
        np.testing.assert_allclose(got[b], want(x[b]), rtol=1e-5, atol=1e-6)
    assert jax.tree_util.tree_map(
        lambda a: a.shape, layer.init(jax.random.PRNGKey(0), x)["params"]) \
        == jax.tree_util.tree_map(lambda a: a.shape, dict(p))
    text = jax.jit(lambda x: layer.apply({"params": p}, x)).lower(
        x).as_text(debug_info=True)
    for scope in ("kimi_delta_attention/kda_conv",
                  "kimi_delta_attention/kda_decay",
                  "kimi_delta_attention/kda_state_walk"):
        assert scope in text, scope
    assert "gated_delta_net" not in text


def test_the_decay_is_a_channels_own(params):
    """Raising ONE channel's ``dt_bias`` moves the output (that channel of
    the state decays faster) and leaves the other head's rows of the
    output alone: the decay is a vector, and a head's own."""
    layer = KimiDeltaAttention(32, 2, 8, 4, 1e-5, 8, jnp.float32)
    p = dict(params["params"]["block1"]["attn"])
    x = jax.random.normal(jax.random.PRNGKey(5), (1, LEN, 32))

    def heads(p):       # the rule's output before W_o mixes the heads
        out = dict(p, attn_out={"kernel": jnp.eye(16, 32)})
        return layer.apply({"params": out}, x)[0, :, :16].reshape(LEN, 2, 8)
    base = heads(p)
    moved = heads(dict(p, dt_bias=p["dt_bias"].at[3].add(4.0)))
    assert float(jnp.abs(moved[:, 0] - base[:, 0]).max()) > 1e-7
    assert np.array_equal(np.asarray(moved[:, 1]), np.asarray(base[:, 1]))


def test_the_latent_part_is_the_references_layer(params):
    """No query rank, no query norm, keys of 12 over values of 8."""
    d = ref.dims(CFG)
    layer = MlaAttention(32, 2, None, 16, 8, 4, 8, eps=1e-5,
                         dtype=jnp.float32, turn=False)
    p = params["params"]["block3"]["attn"]
    assert set(p) == {"attn_query", "attn_key_value_a", "key_value_norm",
                      "attn_key_value_b", "attn_out"}
    assert p["attn_query"]["kernel"].shape == (32, 2 * 12)
    assert p["attn_key_value_b"]["kernel"].shape == (16, 2 * 16)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, LEN, 32))
    got = layer.apply({"params": p}, x)
    want = jax.jit(lambda x: ref._mla(d, _mm, p, x))
    for b in range(2):
        np.testing.assert_allclose(got[b], want(x[b]), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("turn", [False, True], ids=["nope", "turned"])
def test_without_the_turn_the_layer_knows_no_positions(params, turn):
    """Causal softmax with no positions is a set function of the past:
    swapping two earlier tokens leaves a later row's output alone; with
    the turn (GLM's setting) it moves."""
    layer = MlaAttention(32, 2, None, 16, 8, 4, 8, eps=1e-5,
                         dtype=jnp.float32, turn=turn)
    p = {"params": params["params"]["block3"]["attn"]}
    x = jax.random.normal(jax.random.PRNGKey(6), (1, LEN, 32))
    swapped = x.at[0, 2].set(x[0, 7]).at[0, 7].set(x[0, 2])
    same = np.allclose(layer.apply(p, swapped)[0, 12:],
                       layer.apply(p, x)[0, 12:], atol=1e-6)
    assert same == (not turn)


# ----------------------------------------- the model and its reference
def test_reference_tree_is_the_programs_tree_and_layer_kinds(params):
    own = jax.eval_shape(_module().init, jax.random.PRNGKey(0),
                         jnp.zeros((1, LEN), jnp.int32))
    shapes = lambda t: jax.tree_util.tree_map(lambda x: x.shape, t)  # noqa
    assert shapes(own) == shapes(params)
    blocks = own["params"]
    assert "lm_head" in blocks              # untied tables
    for i in range(5):
        mixer, ffn = blocks[f"block{i}"]["attn"], blocks[f"block{i}"]["ffn"]
        assert ("A_log" in mixer) == (i != 3)
        assert ("attn_key_value_a" in mixer) == (i == 3)
        assert ("mlp_gate" in ffn) == (i == 0)
        assert ("router" in ffn) == ("shared" in ffn) == (i > 0)
    assert ref.routed_blocks(CFG) == [f"block{i}" for i in (1, 2, 3, 4)]
    assert ref.parameters(CFG) == sum(
        x.size for x in jax.tree_util.tree_leaves(own))
    tiny = jax.eval_shape(
        build_model("kimi_linear_tiny")["module"].init,
        jax.random.PRNGKey(0), jnp.zeros((1, LEN), jnp.int32))
    assert shapes(tiny) == shapes(own)


def test_the_published_configuration_counts_its_parameters():
    """The benchmark's file: every published width, layers 1 to 5 of 27, 8
    of 256 experts held, an eighth of the tables: the count the issue
    reckons, from the reference's shapes and from the program's own tree;
    and the registry entry's defaults are the published model, 49.1B."""
    with open(Path(__file__).resolve().parent.parent / "benchmark"
              / "configs" / "kimi-linear-48b-a3b.json") as f:
        cfg = json.load(f)
    assert ref.parameters(cfg) == 602_434_432
    assert str(ref.parameters(cfg)) in cfg["deployment"][
        "parameters_here"].replace(",", "")
    module = build_model("kimi_linear", **ref.zoo_args(cfg, 16384))["module"]
    tree = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 512), jnp.int32))
    shapes = jax.tree_util.tree_map(lambda x: tuple(x.shape), tree)
    assert shapes == ref.param_shapes(cfg)
    calls = ref.kernel_calls(cfg, 1, 16384, 1000.0)
    assert calls["flash_fwd"] == {"rows": 1, "len": 16384, "heads": 32,
                                  "key_dim": 192, "value_dim": 128}
    assert calls["kda_chunk"]["layers"] == 4
    parts = ref._fwd_flops_per_token(cfg, 16384)
    # the one latent layer's core, 2 x (192 + 128) x L / 2 x 32 a token
    assert abs(parts["mla"] - 2.0 * 320 * 8192 * 32
               - 2.0 * (2304 * 6144 + 2304 * 576 + 512 * 8192
                        + 4096 * 2304)) < 1.0
    whole = jax.eval_shape(
        build_model("kimi_linear")["module"].init, jax.random.PRNGKey(0),
        jnp.zeros((1, 64), jnp.int32))
    count = sum(x.size for x in jax.tree_util.tree_leaves(whole))
    assert count == 49_122_681_728
    published = dict(cfg, num_hidden_layers=27, num_experts=256,
                     vocab_size=163840, linear_attn_config=dict(
                         cfg["linear_attn_config"],
                         kda_layers=[l for l in range(1, 28)
                                     if l % 4 and l != 27],
                         full_attn_layers=[4, 8, 12, 16, 20, 24, 27]))
    assert ref.parameters(published) == count


def test_lists_that_do_not_cover_the_layers_raise():
    with pytest.raises(ValueError, match="kda_layers"):
        build_model("kimi_linear_tiny", full_attn_layers=(4, 5))
    with pytest.raises(ValueError, match="kda_layers"):
        build_model("kimi_linear_tiny", kda_layers=(1, 2, 3, 6))
    with pytest.raises(ValueError, match="kda_layers"):
        ref.dims(dict(CFG, num_hidden_layers=6))
    with pytest.raises(ValueError, match="published layer"):
        ref.dims(dict(CFG, q_lora_rank=8))


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


@pytest.mark.parametrize("gate_grad", [False, True],
                         ids=["gate-frozen", "gate-trained"])
def test_losses_and_gradients_match_the_reference(params, gate_grad):
    """The cell's setting, a frozen gate, and the other."""
    cfg = dict(CFG, program={"chunk": 8, "zoo_args": {
        "dtype": jnp.float32, "gate_grad": gate_grad}})
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
    assert len(routers) == 4
    for g in routers:       # a frozen gate's router gets EXACTLY nothing
        assert np.any(g) == gate_grad
    assert not any(np.any(v) for k, v in named.items()
                   if "router_bias" in k)
    # every leaf of every KDA mixer gets a gradient, the decay's own among
    # them
    for leaf in ("A_log", "dt_bias", "attn_decay_a", "attn_decay_b",
                 "attn_beta", "attn_gate_b", "conv_key", "gate_norm"):
        got = [v for k, v in named.items() if leaf in k]
        assert len(got) == 4 and all(np.any(g) for g in got), leaf


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
    assert float(m["moe.slots_here"]) == 4 * ROWS * LEN * 2
    assert float(m["moe.overflow_layers"]) == 0
    assert want["mtp"] == [] and len(want["routing"]) == 4
    assert want["routing"][0]["choice"].shape == (ROWS * LEN, 2)
    assert want["routing"][0]["ranked"].shape == (ROWS * LEN, 8)


def test_the_references_blocks_change_no_value(params, monkeypatch):
    """The reference computes a mixer's heads in groups, a head's queries
    and the dense part's and the loss's rows in blocks, so that a row of
    16,384 tokens fits beside 9.6 GB of weights and moments; at the toy's
    size the defaults are one group and one block: with one head a group
    and blocks of 4 rows the loss, the routing and every gradient are the
    same numbers."""
    tokens = jnp.asarray(_tokens(5)[0][0])

    def run():
        return jax.value_and_grad(
            lambda p: ref.sequence_loss(CFG, None, 1, p, tokens),
            has_aux=True)(params)
    (want, routing), want_g = run()
    monkeypatch.setattr(ref, "HEAD_GROUP", 1)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 4)
    monkeypatch.setattr(ref, "ROW_BLOCK", 4)
    (got, got_routing), got_g = run()
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for (c, r), (wc, wr) in zip(got_routing, routing):
        assert np.array_equal(np.asarray(c), np.asarray(wc))
        np.testing.assert_allclose(r, wr, rtol=1e-5, atol=1e-6)
    _close(got_g, want_g, rtol=1e-5)


def test_the_control_precision_moves_the_reference():
    """``quant`` rounds every product's operands: the control's loss and
    gradient are other numbers than the float32 ones; and the rounding,
    written out in float32 arithmetic, is float8_e4m3fn's own value for
    value: weights' scale, activations', the subnormal grid, the ends."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(np.concatenate([
        rng.normal(0, 0.02, 50000), rng.normal(0, 1, 50000),
        rng.normal(0, 30, 20000), rng.uniform(-0.004, 0.004, 20000),
        [0.0, 448.0, -448.0, 2 ** -9, 2 ** -10, 1.5 * 2 ** -9, 0.015625,
         240.0, 464.0, 1e-12]]).astype(np.float32))
    want = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    assert np.array_equal(np.asarray(jax.jit(ref._fp8)(x)), np.asarray(want))
    with pytest.raises(ValueError, match="control precision"):
        ref._products("int4")
    p = ref.init_params(CFG, jax.random.PRNGKey(3))
    tokens = jnp.asarray(_tokens(4)[0][0])
    fine = ref.sequence_loss(CFG, None, 1, p, tokens)[0]
    coarse = ref.sequence_loss(CFG, "fp8", 1, p, tokens)[0]
    assert np.isfinite(float(coarse)) and abs(float(fine - coarse)) > 1e-6


# ------------------------------------------------------ the routed layer
def _layer(held, first, experts=16, top_k=4, **kw):
    return DroplessMoe(
        32, experts, 8, top_k, experts_held=(held, first), scaling=2.446,
        shared=lambda m: SwiGluMlp(32, 8, jnp.float32, name=m),
        dtype=jnp.float32, **kw)


def _share(p, first, count):
    ffn = dict(p["params"])
    for name in ("experts_gate", "experts_up", "experts_down"):
        ffn[name] = ffn[name][first:first + count]
    return {"params": ffn}


@pytest.mark.parametrize("held", [2, 4, 16], ids=lambda h: f"{16 // h}-chips")
def test_the_shares_add_up_to_the_uncut_reference_the_shared_expert_once(
        held):
    """The deployment's layout at toy widths: 16 experts, four a token,
    ``16 / held`` chips with ``held`` each (the cell's 32 chips of 8 at
    toy size: 8 of 2), one shared expert that every chip computes alike.
    The shares' partial results, the shared expert counted ONCE, sum to
    the uncut layer as the REFERENCE computes it (a dense loop over all
    16), every slot computed exactly once."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    p = _layer(16, 0).init(jax.random.PRNGKey(3), x)
    p["params"]["router"]["kernel"] = 8.0 * p["params"]["router"]["kernel"]
    p["params"]["router_bias"] = jnp.linspace(-0.02, 0.02, 16)
    d = dict(ref.dims(CFG), experts=16, held=16, first=0, top_k=4)
    want = jax.vmap(lambda row: ref._experts(d, _mm, p["params"], row)[0])(x)
    shared = SwiGluMlp(32, 8, jnp.float32).apply(
        {"params": p["params"]["shared"]}, x)
    total, slots = 0.0, 0
    for first in range(0, 16, held):
        y, stats = _layer(held, first).apply(_share(p, first, held), x)
        total, slots = total + (y - shared), slots + int(stats["slots_here"])
    np.testing.assert_allclose(total + shared, want, rtol=1e-5, atol=1e-6)
    assert slots == 2 * 16 * 4


# ---------------------------------------- the flash kernels, two widths
L_K, H_K = 512, 2


def _qkvd(dk, dv, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return tuple(jax.random.normal(k, (1, L_K, H_K, d), dtype)
                 for k, d in zip(ks, (dk, dk, dv, dv)))


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("dk,dv", [(48, 32), (32, 64), (192, 128)],
                         ids=lambda d: str(d))
def test_flash_at_two_head_widths_matches_the_masked_dense_product(
        dk, dv, causal):
    """Forward and backward in interpret mode, tiles of 128: keys wider
    than the values (the latent layer's 192 over 128, and a toy of the
    same ratio), and narrower; the scale is the KEYS' width's."""
    q, k, v, do = _qkvd(dk, dv)
    got, pull = jax.vjp(lambda q, k, v: pa.flash_attention(
        q, k, v, causal, 128, 128), q, k, v)
    want, pull_ref = jax.vjp(lambda q, k, v: sequence._reference_attention(
        q, k, v, causal), q, k, v)
    assert got.shape == v.shape
    np.testing.assert_allclose(got, want, atol=2e-5)
    for g, w in zip(pull(do), pull_ref(do)):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=2e-5)


def test_full_attention_takes_two_widths_through_the_kernel():
    q, k, v, _ = _qkvd(48, 32, seed=3)
    name = "attention.fused_calls.flash"
    before = obsmetrics.counter(name).value
    got = sequence.full_attention(q, k, v, True, "require")
    assert obsmetrics.counter(name).value == before + 1
    np.testing.assert_allclose(
        got, sequence._reference_attention(q, k, v, True), atol=2e-5)


def test_the_kernels_cap_counts_the_bytes_it_holds():
    """K + V of one (batch, head), rows padded to whole lane tiles, in the
    operands' dtype, within 16 MiB: the cell's call (16,384 x (192 | 128)
    in bfloat16: 12 MiB) is taken, and float32's edge at head width 64 is
    where it was measured."""
    assert pa.supports((1, 16384, 32, 192), v_dim=128, itemsize=2)
    assert not pa.supports((1, 16384, 32, 192), v_dim=128, itemsize=4)
    assert pa.supports((1, 16384, 2, 64))
    assert not pa.supports((1, 16384 + 256, 2, 64))
    assert pa.supports((1, 32768, 2, 64), itemsize=2)
    assert not pa.supports((1, 32768 + 256, 2, 64), itemsize=2)
    assert pa.supports((1, 8192, 32, 128)) \
        and not pa.supports((1, 16384 + 256, 2, 128))
    assert not pa.supports((1, 1024, 2, 36), v_dim=32)      # sublanes
