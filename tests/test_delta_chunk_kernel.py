"""The gated delta rule's chunk-local half as Pallas calls
(``ops/pallas_delta_rule.py``, interpreted on the CPU) against the two
forms that were there: XLA's batched products (``_chunked``, which other
shapes still take) and the token-by-token float32 rule.

Tolerances. float32 operands: all three compute in float32 on one
backend and differ by the order of additions; 5e-5 of the largest
element, values and gradients (``tests/test_qwen3_next.py`` holds the
XLA form to 2e-5 at head width 8; at width 128 and decays of -20 a token
both chunked forms read 2.1e-5 on ``g``). bfloat16 operands: the Pallas
path and the XLA form round the same operands at the same places, 2e-2
of the norm against the float32 rule (``chip_smoke.BF16_REL_TOL``) and
against each other.
"""
import collections
import sys
from pathlib import Path

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark.references import qwen3_next as ref  # noqa: E402
from mmlspark_tpu.models.zoo import build_model  # noqa: E402
from mmlspark_tpu.models.zoo.parts import GatedDeltaNet  # noqa: E402
from mmlspark_tpu.observability import metrics as obsmetrics  # noqa: E402
from mmlspark_tpu.ops import linear_attention as la  # noqa: E402
from mmlspark_tpu.ops import pallas_delta_rule as pdr  # noqa: E402
# the flash kernel's twin of the last section here: what ``decoder.py``'s
# ``nn.remat`` is for the blocks compared with, and a jaxpr's Pallas calls
from tests.test_glm4_moe_lite import _REMAT, _pallas_calls  # noqa: E402

NAMES = "o q k v g beta".split()
# a state that fills no whole lanes (Olmo-Hybrid's 96 x 192, ``beta`` up to
# 2): four heads a program, side by side in the rows' lanes; six heads are
# a program and a half, so the second program's last two heads lie outside
# the arrays
WIDE = dict(Hk=6, Hv=6, d=96, dv=192, beta_scale=2.0)


def _inputs(L, decay, Hk=1, Hv=2, B=1, d=128, seed=0, dv=None,
            beta_scale=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = la.l2_normalize(jax.random.normal(ks[0], (B, L, Hk, d)))
    k = la.l2_normalize(jax.random.normal(ks[1], (B, L, Hk, d)))
    v = jax.random.normal(ks[2], (B, L, Hv, dv or d))
    noise = jax.random.normal(ks[3], (B, L, Hv))
    if decay == "init":         # -A softplus(dt_bias + .), A up to 16
        g = -jnp.linspace(1e-3, 16.0, Hv) * jax.nn.softplus(1.0 + noise)
    else:                       # at least -20 a token: 0 / 0 as a quotient
        g = -20.0 - 20.0 * jax.nn.softplus(noise)
    beta = beta_scale * jax.nn.sigmoid(
        jax.random.normal(ks[4], (B, L, Hv)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], v.shape)


def _run(args, w, impl, dtype=None, chunk=64):
    def f(*a):
        return la.gated_delta_rule(*a, chunk=chunk, impl=impl, dtype=dtype)
    return jax.jit(lambda *a: (f(*a),) + jax.grad(
        lambda *b: jnp.sum(f(*b) * w), argnums=(0, 1, 2, 3, 4))(*a))(*args)


def _chunk_calls():
    return {k: obsmetrics.counter(
        f"linear_attention.chunk_calls.{k}").value for k in ("pallas", "xla")}


@pytest.fixture
def xla_form(monkeypatch):
    """The XLA form on shapes the Pallas path would take: the test steers
    the choice, the program reads it from the shapes alone."""
    def run(args, w, dtype):
        with monkeypatch.context() as m:
            m.setattr(pdr, "supports", lambda *a: False)
            return _run(args, w, "chunked", dtype)
    return run


@pytest.mark.parametrize("decay", ["init", "strong"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("heads", [(2, 2), (1, 2), WIDE],
                         ids=["one_to_one", "grouped", "six_of_96x192"])
@pytest.mark.parametrize("length", [128, 150], ids=["whole", "ragged"])
def test_pallas_path_is_the_xla_form_and_the_token_by_token_rule(
        length, heads, dtype, decay, xla_form):
    args, w = _inputs(length, decay, **heads) if isinstance(heads, dict) \
        else _inputs(length, decay, *heads)
    before = _chunk_calls()
    got = _run(args, w, "chunked", dtype)
    mid = _chunk_calls()
    xla = xla_form(args, w, dtype)
    after = _chunk_calls()
    # _run traces the rule twice: for the output, and under jax.grad
    assert (mid["pallas"] - before["pallas"], mid["xla"] - before["xla"],
            after["pallas"] - mid["pallas"]) == (2, 0, 0)
    assert after["xla"] - mid["xla"] == 2
    want = _run(args, w, "recurrent")
    assert got[0].shape == args[2].shape and got[0].dtype == jnp.float32
    for name, a, x, b in zip(NAMES, got, xla, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert bool(jnp.isfinite(a).all()), name
        if dtype == jnp.float32:
            tol = 5e-5 * float(jnp.abs(b).max()) + 1e-9
            np.testing.assert_allclose(a, b, atol=tol, err_msg=name)
            np.testing.assert_allclose(a, x, atol=tol, err_msg=name)
        else:
            # the floor: at -20 a token g's gradient is of the order of
            # 1e-11 where the others are of the order of 1
            tol = 2e-2 * float(jnp.linalg.norm(b)) + 1e-8
            assert float(jnp.linalg.norm(a - b)) <= tol, name
            assert float(jnp.linalg.norm(a - x)) <= tol, name


@pytest.mark.parametrize("heads", [dict(Hk=1, Hv=2), WIDE],
                         ids=["grouped", "six_of_96x192"])
def test_more_chunks_than_one_program_holds_and_two_rows(heads):
    """Nine chunks: two programs of eight, the second mostly padding."""
    args, w = _inputs(64 * 9, "init", B=2, **heads)
    got, want = _run(args, w, "chunked"), _run(args, w, "recurrent")
    for name, a, b in zip(NAMES, got, want):
        np.testing.assert_allclose(
            a, b, atol=5e-5 * float(jnp.abs(b).max()), err_msg=name)


@pytest.mark.parametrize("kind", ["random", "ones"])
def test_the_kernels_inverse_is_inv_unit_lower(kind):
    n = pdr.CHUNK
    if kind == "random":
        a = np.tril(np.random.default_rng(3).normal(size=(n, n)), -1)
    else:   # all keys alike and beta = 1: the powers grow like binomials
        a = np.tril(np.ones((n, n)), -1)
    a = jnp.asarray(a, jnp.float32)

    def kernel(a_ref, t_ref):
        r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
        t_ref[...] = pdr.inv_unit_lower_tile(a_ref[...], r, c)
    got = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=True)(a)
    assert got.dtype == jnp.float32
    want = la.inv_unit_lower(a)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, want, atol=1e-5 * scale)
    np.testing.assert_allclose(
        got, np.linalg.inv(np.eye(n) + np.asarray(a, np.float64)),
        atol=(1e-5 if kind == "random" else 2e-3) * scale)


def test_the_shapes_the_calls_take():
    """Whole lanes a head (any grouping of value heads under key heads),
    or as many key as value heads whose lanes end on a tile four heads or
    fewer at a time; nothing else, and no other chunk."""
    assert pdr.heads_per_program(128, 128) == pdr.heads_per_program(
        128, 256) == 1
    assert pdr.heads_per_program(96, 192) == 4
    assert pdr.heads_per_program(64, 64) == 2
    assert pdr.supports(64, 16, 32, 128, 128)
    assert pdr.supports(64, 30, 30, 96, 192)
    assert pdr.supports(64, 6, 6, 96, 192)
    assert not pdr.supports(64, 15, 30, 96, 192)    # grouped, ragged lanes
    assert not pdr.supports(64, 30, 30, 80, 160)    # 8 heads to a tile
    assert not pdr.supports(64, 4, 4, 8, 16)        # the tiny presets
    assert not pdr.supports(32, 30, 30, 96, 192)
    assert not pdr.supports(64, 3, 4, 128, 128)


@pytest.mark.parametrize("case,d,chunk", [
    ("chunk_32", 128, 32), ("head_width_64", 64, 64)])
def test_a_shape_the_calls_do_not_take_runs_the_xla_form(case, d, chunk):
    args, w = _inputs(128, "init", 1, 2, d=d)
    before = _chunk_calls()
    got = _run(args, w, "chunked", chunk=chunk)
    after = _chunk_calls()
    assert (after["pallas"] - before["pallas"],
            after["xla"] - before["xla"]) == (0, 2)
    # the rule's numbers do not depend on the chunk or on who computes it
    want = _run(args, w, "chunked") if d == 128 \
        else _run(args, w, "recurrent")
    if d == 128:
        assert _chunk_calls()["pallas"] - after["pallas"] == 2
    for name, a, b in zip(NAMES, got, want):
        np.testing.assert_allclose(
            a, b, atol=5e-5 * float(jnp.abs(b).max()), err_msg=name)


def test_supports_reads_the_shapes():
    assert pdr.supports(64, 16, 32, 128, 128)
    assert pdr.supports(64, 2, 2, 256, 128)
    assert not pdr.supports(32, 16, 32, 128, 128)
    assert not pdr.supports(64, 16, 32, 64, 128)
    assert not pdr.supports(64, 16, 32, 128, 192)
    assert not pdr.supports(64, 3, 32, 128, 128)
    # whole pairs of chunks, in whole programs of eight pairs at most
    assert [pdr.padded_length(n) for n in (1, 64, 130, 576, 1025, 4096)] \
        == [128, 128, 256, 640, 2048, 4096]


def test_value_heads_that_are_no_whole_groups_are_refused():
    args, _ = _inputs(64, "init", 2, 3, d=8)
    with pytest.raises(ValueError, match="shapes"):
        la.gated_delta_rule(*args)
    # and q, k at value-head width are what they always were
    args, w = _inputs(64, "init", 2, 2, d=8)
    got = la.gated_delta_rule(*args, chunk=16)
    want = la.gated_delta_rule(*args, impl="recurrent")
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("length", [128, 70], ids=["whole", "ragged"])
def test_delta_net_layer_on_the_pallas_path_is_the_reference_layer(length):
    """``test_delta_net_layer_is_the_reference_layer``'s layer at head
    width 128 and chunk 64: q and k go in at key-head width."""
    d = dict(lk_heads=1, lv_heads=2, lk=128, lv=128, eps=1e-6)
    layer = GatedDeltaNet(32, 1, 2, 128, 128, 4, 1e-6, 64, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, length, 32))
    p = layer.init(jax.random.PRNGKey(2), x)
    p = jax.tree_util.tree_map_with_path(
        lambda path, v: 8.0 * v if "kernel" in jax.tree_util.keystr(path)
        else v, p)
    before = _chunk_calls()
    got = jax.jit(layer.apply)(p, x)
    assert _chunk_calls()["pallas"] - before["pallas"] == 1
    want = jax.jit(jax.vmap(lambda row: ref._delta_net(
        d, jnp.einsum, p["params"], row)))(x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


# -------------------------- what a block keeps of the call's forward tiles
_DELTA_LAYERS = 3


def _block_grads(jaxpr=False):
    """Gradients of one period of ``qwen3_next`` (three Gated DeltaNet
    blocks and a softmax one) at head width 128 and chunk 64, where the
    rule takes the Pallas calls, under ``nn.remat`` as it stands when
    called, or their jaxpr."""
    module = build_model(
        "qwen3_next", vocab=48, dim=32, depth=_DELTA_LAYERS + 1, heads=2,
        kv_heads=1, head_dim=16, rotary_fraction=0.25, linear_key_heads=1,
        linear_value_heads=2, linear_key_dim=128, linear_value_dim=128,
        conv_width=4, expert_hidden=16, shared_hidden=16, num_experts=4,
        top_k=2, chunk=64, dtype=jnp.float32)["module"]
    tokens = jnp.asarray(np.random.default_rng(5).integers(
        0, 48, size=(1, 128)).astype(np.int32))
    params = module.init(jax.random.PRNGKey(3), tokens)

    def loss(p):
        return jnp.sum(jnp.sin(module.apply(p, tokens, hidden=True)[
            "hidden"]))
    if jaxpr:
        return jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    return jax.jit(jax.grad(loss))(params)


@pytest.fixture(scope="module")
def kept_grads():
    """The module's own gradients, once."""
    return _block_grads()


@pytest.mark.parametrize("remat,forward_calls", [
    ("kept", 1), ("input_only", 2), ("nothing_recomputed", 1)])
def test_a_recomputed_block_holds_one_forward_call_of_the_tiles(
        monkeypatch, kept_grads, remat, forward_calls):
    """Three Gated DeltaNet blocks in the gradient's jaxpr: the backward
    pass of a block that keeps the five tiles ``delta_chunk`` wrote
    (``DELTA_CHUNK_TILES``) has no second ``delta_chunk_fwd`` call; the
    backward call, which makes its tiles again, and the output's two run
    as often as they did (the walk between them is still recomputed, so
    ``delta_chunk_out`` is too). The values kept are the ones the
    recomputation would have made: the gradients are those of a block
    that keeps its input alone and of one that recomputes nothing."""
    monkeypatch.setattr(nn, "remat", _REMAT[remat])
    calls = collections.Counter(_pallas_calls(_block_grads(jaxpr=True)))
    assert calls[pdr._FWD_NAME] == forward_calls * _DELTA_LAYERS
    assert calls[pdr._BWD_NAME] == _DELTA_LAYERS
    assert calls[pdr._OUT_NAME] == _DELTA_LAYERS * (
        1 if remat == "nothing_recomputed" else 2)
    assert calls[pdr._OUT_BWD_NAME] == _DELTA_LAYERS
    if remat == "kept":
        return
    for (path, g), w in zip(
            jax.tree_util.tree_leaves_with_path(kept_grads),
            jax.tree_util.tree_leaves(_block_grads())):
        np.testing.assert_allclose(
            g, w, rtol=1e-6, atol=1e-6 * float(jnp.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))


def test_the_name_on_the_tiles_is_inert_without_a_policy():
    """Differentiated outside any recomputation (``chip_smoke.py``'s leg,
    the tests above), the call's forward runs once and the name is an
    identity in the program."""
    args, w = _inputs(128, "init")
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
        la.gated_delta_rule(*a, chunk=64) * w), argnums=(0, 1, 2, 3, 4)))(
            *args).jaxpr
    calls = collections.Counter(_pallas_calls(jaxpr))
    assert calls == {pdr._FWD_NAME: 1, pdr._BWD_NAME: 1, pdr._OUT_NAME: 1,
                     pdr._OUT_BWD_NAME: 1}
