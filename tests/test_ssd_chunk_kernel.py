"""The chunk-local half of Mamba-2's state-space rule as Pallas calls
(``ops/pallas_ssd.py``, interpreted on the CPU) against the two forms that
were there: XLA's batched products (``_ssd_chunked``, which other shapes
still take) and the token-by-token float32 rule.

Tolerances. float32 operands: all three compute in float32 on one backend
and differ by the order of additions; 5e-5 of the largest element on the
output and on the gradients of ``x``, ``B``, ``C``; 5e-4 on those of ``dt``
and ``A``, which are sums over a row of terms of both signs through the
running sum ``G`` (down to -400 a chunk at the published decay; the calls
make ``G`` in eight doubling steps, XLA as a ``cumsum``: the two add in
different orders). bfloat16 operands: the Pallas path and
the XLA form round the same operands at the same places, 2e-2 of the norm
against the float32 rule (``chip_smoke.BF16_REL_TOL``) and against each
other.
"""
import collections
import sys
from pathlib import Path

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark.references import granite_hybrid as ref  # noqa: E402
from mmlspark_tpu.models.zoo import build_model  # noqa: E402
from mmlspark_tpu.models.zoo.parts import Mamba2Mixer  # noqa: E402
from mmlspark_tpu.observability import metrics as obsmetrics  # noqa: E402
from mmlspark_tpu.ops import linear_attention as la  # noqa: E402
from mmlspark_tpu.ops import pallas_ssd as pss  # noqa: E402
from tests.test_glm4_moe_lite import _REMAT, _pallas_calls  # noqa: E402

NAMES = "y x dt A B C".split()
CHUNK = pss.CHUNK
# the narrowest heads the calls take: a program's heads fill one tile of lanes
HEADS, WIDTH, STATE = pss.HEADS, pss.LANES // pss.HEADS, 128


def _inputs(L, decay, B=1, H=HEADS, P=WIDTH, G=1, N=STATE, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B, L, H, P))
    if decay == "init":         # Mamba-2's own: A in -[1, 16], dt small
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H)) - 3.0)
        A = -jnp.linspace(1.0, 16.0, H)
    else:                       # the published extreme, dt A = -1.6 a token:
        dt = jnp.full((B, L, H), 0.1) + 0.01 * jax.random.uniform(
            ks[1], (B, L, H))   # a chunk passes float32's range four times
        A = jnp.full((H,), -16.0)
    Bm = jax.random.normal(ks[2], (B, L, G, N)) * N ** -0.5
    Cm = jax.random.normal(ks[3], (B, L, G, N))
    return (x, dt, A, Bm, Cm), jax.random.normal(ks[4], x.shape)


def _run(args, w, impl, dtype=None, chunk=CHUNK):
    def f(*a):
        return la.ssd(*a, chunk=chunk, impl=impl, dtype=dtype)
    return jax.jit(lambda *a: (f(*a),) + jax.grad(
        lambda *b: jnp.sum(f(*b) * w), argnums=(0, 1, 2, 3, 4))(*a))(*args)


def _chunk_calls():
    return {k: obsmetrics.counter(
        f"linear_attention.ssd_chunk_calls.{k}").value
        for k in ("pallas", "xla")}


@pytest.fixture
def xla_form(monkeypatch):
    """The XLA form on shapes the Pallas path would take: the test steers
    the choice, the program reads it from the shapes alone."""
    def run(args, w, dtype):
        with monkeypatch.context() as m:
            m.setattr(pss, "supports", lambda *a: False)
            return _run(args, w, "chunked", dtype)
    return run


def _close(name, a, b, dtype):
    assert a.shape == b.shape and a.dtype == b.dtype, name
    assert bool(jnp.isfinite(a).all()), name
    if dtype == jnp.float32:
        tol = (5e-4 if name in ("dt", "A") else 5e-5) \
            * float(jnp.abs(b).max()) + 1e-9
        np.testing.assert_allclose(a, b, atol=tol, err_msg=name)
    else:
        assert float(jnp.linalg.norm(a - b)) \
            <= 2e-2 * float(jnp.linalg.norm(b)) + 1e-8, name


@pytest.mark.parametrize("decay", ["init", "strong"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("length", [2 * CHUNK, 2 * CHUNK + 44],
                         ids=["whole", "ragged"])
def test_pallas_path_is_the_xla_form_and_the_token_by_token_rule(
        length, dtype, decay, xla_form):
    args, w = _inputs(length, decay)
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
    assert got[0].shape == args[0].shape and got[0].dtype == jnp.float32
    for name, a, x, b in zip(NAMES, got, xla, want):
        _close(name, a, b, dtype)
        _close(name, a, x, dtype)


def test_a_chunk_of_the_published_decay_is_past_float32s_range_here_too():
    """``tests/test_granite_hybrid.py``'s control, at this file's "strong"
    inputs: a quotient of two exponentials of the running sum would be 0 /
    0 inside one chunk; the calls' outputs and gradients are finite (the
    test above) because every decay is the exponential of a difference."""
    (_, dt, A, _, _), _ = _inputs(CHUNK, "strong")
    G = jnp.cumsum(dt * A, axis=1)
    assert float(G.min()) < 4 * np.log(np.finfo(np.float32).tiny)
    quotient = jnp.exp(G)[:, :, None] / jnp.exp(G)[:, None, :]
    assert not bool(jnp.isfinite(quotient).all())


@pytest.mark.parametrize("shape", [
    dict(B=2, H=HEADS, G=1), dict(B=1, H=2 * HEADS, G=2),
    dict(B=2, H=2 * HEADS, G=1), dict(B=1, H=4 * HEADS, G=2, P=64)],
    ids=["two_rows", "two_groups_a_program_each",
         "two_programs_a_group", "two_groups_of_two_programs_at_64"])
def test_rows_groups_and_programs_of_heads(shape):
    """Three chunks a row (the walk carries a state between the calls'
    programs), a group's ``B`` and ``C`` read by the programs of its heads
    and by no other group's, and their gradients summed over them."""
    args, w = _inputs(3 * CHUNK - 9, "init", **shape)
    got, want = _run(args, w, "chunked"), _run(args, w, "recurrent")
    for name, a, b in zip(NAMES, got, want):
        _close(name, a, b, jnp.float32)


def test_supports_reads_the_shapes():
    assert pss.supports(256, 64, 64, 1, 128)        # granite-4.0-h-micro
    assert pss.supports(256, 32, 8, 2, 128)
    assert pss.supports(256, 16, 64, 1, 256)
    assert not pss.supports(128, 64, 64, 1, 128)    # another chunk
    assert not pss.supports(256, 4, 8, 1, 8)        # the tiny presets
    assert not pss.supports(256, 64, 64, 1, 64)     # a state of half a tile
    assert not pss.supports(256, 6, 64, 1, 128)     # no whole program
    assert not pss.supports(256, 16, 64, 4, 128)    # four heads a group
    assert not pss.supports(256, 6, 64, 4, 128)     # heads in no groups
    assert not pss.supports(256, 8, 64, 1, 128)     # half a program
    assert not pss.supports(256, 16, 4, 1, 128)     # a program of 64 lanes


@pytest.mark.parametrize("case,kw,chunk", [
    ("chunk_128", {}, 128), ("state_64", dict(N=64), CHUNK),
    ("six_heads_in_two_groups", dict(H=6, G=2), CHUNK)])
def test_a_shape_the_calls_do_not_take_runs_the_xla_form(case, kw, chunk):
    args, w = _inputs(CHUNK, "init", **kw)
    before = _chunk_calls()
    got = _run(args, w, "chunked", chunk=chunk)
    after = _chunk_calls()
    assert (after["pallas"] - before["pallas"],
            after["xla"] - before["xla"]) == (0, 2)
    want = _run(args, w, "recurrent")
    assert _chunk_calls() == after      # the token-by-token form counts none
    for name, a, b in zip(NAMES, got, want):
        _close(name, a, b, jnp.float32)


def test_a_row_shorter_than_a_chunk_takes_no_call_under_auto():
    args, w = _inputs(CHUNK - 1, "init")
    before = _chunk_calls()
    got, want = _run(args, w, "auto"), _run(args, w, "recurrent")
    assert _chunk_calls() == before
    np.testing.assert_array_equal(got[0], want[0])


def test_one_call_of_each_kind_and_no_second_forward_outside_a_block():
    """Differentiated outside any recomputation the rule is four Pallas
    calls under their scopes' names, between them the one scan."""
    args, w = _inputs(CHUNK, "init")
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
        la.ssd(*a) * w), argnums=(0, 1, 2, 3, 4)))(*args).jaxpr
    assert collections.Counter(_pallas_calls(jaxpr)) == {
        pss._FWD_NAME: 1, pss._BWD_NAME: 1, pss._OUT_NAME: 1,
        pss._OUT_BWD_NAME: 1}
    names = {pss._FWD_NAME, pss._BWD_NAME, pss._OUT_NAME, pss._OUT_BWD_NAME}
    assert all(n.startswith("ssd_chunk_") for n in names) and not any(
        word in n for n in names for word in ("flash", "attention", "delta"))


@pytest.mark.parametrize("length", [CHUNK, CHUNK + 30],
                         ids=["whole", "ragged"])
def test_mixer_layer_on_the_pallas_path_is_the_reference_layer(length):
    """``tests/test_granite_hybrid.py``'s layer at sixteen heads of 8, a
    state of 128 and the published chunk."""
    d = dict(m_heads=HEADS, m_head=WIDTH, state=STATE, groups=1, eps=1e-5)
    layer = Mamba2Mixer(32, HEADS, WIDTH, STATE, 1, 4, 1e-5, CHUNK,
                        dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, length, 32))
    p = layer.init(jax.random.PRNGKey(2), x)

    def away(path, v):          # every parameter away from its init
        name = jax.tree_util.keystr(path)
        if "kernel" in name or "conv_bias" in name:
            return 8.0 * v
        if "D_skip" in name or "scale" in name:
            return v + jnp.linspace(-0.5, 0.5, v.size).reshape(v.shape)
        return v
    p = jax.tree_util.tree_map_with_path(away, p)
    before = _chunk_calls()
    got = jax.jit(layer.apply)(p, x)
    assert _chunk_calls()["pallas"] - before["pallas"] == 1
    want = jax.jit(jax.vmap(lambda row: ref._mamba(
        d, jnp.einsum, p["params"], row)))(x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


# ------------------------------- a block recomputed in halves, on the calls
_MAMBA_LAYERS = 2


def _block_grads(jaxpr=False):
    """Gradients of three blocks of ``granite_hybrid`` (two Mamba-2 mixers
    around a softmax layer) at sixteen heads of 8, a state of 128 and the
    published chunk, where the rule takes the Pallas calls, under
    ``nn.remat`` as it stands when called, or their jaxpr."""
    module = build_model(
        "granite_hybrid_tiny", layer_types=("mamba", "attention", "mamba"),
        mamba_heads=HEADS, mamba_head_dim=WIDTH, state=STATE, chunk=CHUNK,
        max_len=2 * CHUNK)["module"]
    tokens = jnp.asarray(np.random.default_rng(5).integers(
        0, 96, size=(1, CHUNK + 40)).astype(np.int32))
    params = module.init(jax.random.PRNGKey(3), tokens)

    def loss(p):
        return jnp.sum(jnp.sin(module.apply(p, tokens, hidden=True)[
            "hidden"]))
    if jaxpr:
        return jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    return jax.jit(jax.grad(loss))(params)


@pytest.mark.parametrize("remat,forward_calls", [
    ("kept", 2), ("nothing_recomputed", 1)])
def test_a_block_recomputed_in_halves_is_the_block_not_recomputed(
        monkeypatch, remat, forward_calls):
    """A recomputed half runs the two forward calls again and the two
    backward calls once; recomputation changes no gradient (1e-4 of a
    leaf's largest: ``A_log``'s and ``dt_bias``'s are sums over the row of
    terms of both signs, which XLA adds in another order around a
    recomputed call)."""
    before = _chunk_calls()
    kept = _block_grads()
    assert _chunk_calls()["pallas"] - before["pallas"] >= _MAMBA_LAYERS
    assert _chunk_calls()["xla"] == before["xla"]
    monkeypatch.setattr(nn, "remat", _REMAT[remat])
    calls = collections.Counter(_pallas_calls(_block_grads(jaxpr=True)))
    assert calls == {pss._FWD_NAME: forward_calls * _MAMBA_LAYERS,
                     pss._OUT_NAME: forward_calls * _MAMBA_LAYERS,
                     pss._BWD_NAME: _MAMBA_LAYERS,
                     pss._OUT_BWD_NAME: _MAMBA_LAYERS}
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(kept),
                            jax.tree_util.tree_leaves(_block_grads())):
        np.testing.assert_allclose(
            g, w, rtol=1e-5, atol=1e-4 * float(jnp.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))
